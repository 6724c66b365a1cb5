"""The plain float32 reference against the program's eager forward at a tiny
size (on the chip the same comparison runs at published widths inside every
run's set-up), and the shape arithmetic against the program's own count."""

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM
from paddle_tpu.models.gpt import gpt_tiny
from perfbench.harness import costs, reference


def _tiny():
    paddle.seed(0)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def test_reference_logits_agree_with_the_eager_forward():
    cfg, model = _tiny()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 48)).astype(np.int32)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    weights = reference.weights_of(model)
    got = np.asarray(reference.logits(weights, ids[0], cfg.num_layers,
                                      cfg.num_heads, cfg.layer_norm_eps))
    # float32 both sides; only the order of sums differs
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    last = np.asarray(reference.logits(weights, ids[0], cfg.num_layers,
                                       cfg.num_heads, cfg.layer_norm_eps,
                                       last=5))
    assert np.allclose(last, got[-5:], atol=1e-6)


def test_reference_loss_agrees_with_the_fused_loss():
    cfg, model = _tiny()
    tok = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 33)).astype(np.int32)
    ids, labels = tok[:, :-1], tok[:, 1:]
    with paddle.no_grad():
        want = float(model.loss_fused(paddle.to_tensor(ids),
                                      paddle.to_tensor(labels),
                                      num_chunks=8).numpy())
    got = reference.next_token_loss(reference.weights_of(model), ids, labels,
                                    cfg.num_layers, cfg.num_heads,
                                    cfg.layer_norm_eps)
    assert abs(got - want) < 1e-4


def test_param_count_from_shapes_is_the_models():
    cfg, model = _tiny()
    sizes = {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
             "num_layers": cfg.num_layers,
             "max_position_embeddings": cfg.max_position_embeddings,
             "intermediate_size": cfg.intermediate_size}
    have = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert costs.param_count(sizes) == have
    gpt3 = {"vocab_size": 50304, "hidden_size": 2048, "num_layers": 24,
            "max_position_embeddings": 2048, "intermediate_size": 8192}
    assert costs.param_count(gpt3) == 1_315_819_520
    # decode: 32 slots of 300 live tokens, bf16 weights and cache
    need = costs.decode_step_min_bytes(gpt3, 2, 2, 32 * 300)
    assert need == (1_315_819_520 - 2048 * 2048) * 2 + 9600 * 196_608
