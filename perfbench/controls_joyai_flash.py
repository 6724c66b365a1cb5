#!/usr/bin/env python3
"""Controls of the reference check of ``kind: serve_latent_moe``
configurations: does the check refuse what it is there to refuse?

    python3 perfbench/controls_joyai_flash.py --seeds <n> [<n> ..]
        [--config joyai_flash_ep16_serve_bf16] [--faults latent_8bit,..]
        [--rehearse-cpu]

For each seed it builds the configuration's model as a run of the cell
would, and puts in the served path's place the plain float32 reference with
one thing wrong: ``latent_8bit`` (the cached rows ``(c_kv | RoPE(k_r))``
rounded to float8_e4m3fn, what a pool of the nearest precision below the
configuration's would hold), ``no_rope_score`` (the rotary part of the score
left out), ``k_unrotated`` (``k_r`` cached without its rotation),
``no_shared_expert``, ``scaling_1`` (``routed_scaling_factor`` 1.0),
``no_bias`` (the router's correction bias ignored), ``scale_nope``
(softmax scale ``1 / sqrt(qk_nope_head_dim)``). Each goes through the
runner's own ``compare`` under the configuration's own limits, over prompts
as long and as many positions as the cell checks, and has to come out **not
ok**; ``none`` (the reference unchanged) has to come out ok. One JSON line a
(seed, fault) with the readings; the exit code is 1 if any control came out
on the wrong side.

The program's part is not run (no scheduler, no kernel): it is float32
arithmetic at the published widths wherever JAX puts it. ``--rehearse-cpu``
takes the configuration's toy sizes, for the benchmark's own tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_DIR)

FAULTS = {
    "none": {},
    "latent_8bit": {"kv_dtype": "float8_e4m3fn"},
    "no_rope_score": {"without": ("rope_score",)},
    "k_unrotated": {"without": ("k_rotation",)},
    "no_shared_expert": {"without": ("shared_expert",)},
    "scaling_1": {"without": ("routed_scaling",)},
    "no_bias": {"without": ("correction_bias",)},
    "scale_nope": {"without": ("softmax_scale",)},
}


def control(runner, model, cfg, config: dict, seed: int, fault: str,
            say=lambda msg: None) -> dict:
    """The check's verdict on the reference with ``fault`` in the served
    path's place (what the scheduler alone can get wrong is given as
    sound)."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness.load import STREAM_TOKENS, rng

    reference = runner.reference
    chk = config["reference_check"]
    positions = int(chk["decode_positions"]) + 1
    r = rng(seed, STREAM_TOKENS, 996)
    kw = dict(FAULTS[fault])
    if "kv_dtype" in kw:
        kw["kv_dtype"] = getattr(jnp, kw["kv_dtype"])
    weights = reference.weights_of(model)
    requests = []
    for prompt in runner.check_prompts(config, cfg.vocab_size, seed):
        tokens = np.concatenate([prompt, r.integers(
            0, cfg.vocab_size, positions - 1).astype(np.int32)])
        routing = {"own": [], "report": []}
        logits = np.asarray(reference.logits(
            weights, tokens, runner.reference_config(config), last=positions,
            routing=routing, **kw))
        requests.append({"logits": logits, "tokens": tokens,
                         "choices": [np.asarray(c) for c in routing["own"]]})
    served = {"requests": requests, "step_tokens_agree": 1.0,
              "pools_whole": True,
              "live_rows": config["scheduler"]["max_num_seqs"],
              "decode_path": chk["decode_path"]}
    return runner.compare(served, weights, config, say)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--config", default="joyai_flash_ep16_serve_bf16")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from perfbench.harness.spec import BENCH_DIR, load_json, load_module

    config = load_json(os.path.join(BENCH_DIR, "configs",
                                    args.config + ".json"))
    if args.rehearse_cpu:
        config.update(config["rehearsal"])
    runner = load_module("runners", config["kind"])
    say = lambda msg: print(f"[controls] {msg}", flush=True)
    wrong = 0
    for seed in args.seeds:
        cfg, model = runner.build_model(config, seed)
        for fault in args.faults.split(","):
            out = control(runner, model, cfg, config, seed, fault, say)
            as_wanted = out["ok"] == (fault == "none")
            wrong += not as_wanted
            print(json.dumps({
                "seed": seed, "fault": fault, "ok": out["ok"],
                "as_wanted": as_wanted,
                "err_of_scale": out["err_of_scale"],
                "rms_of_scale": out["rms_of_scale"],
                "router_differs": out["router_differs"],
                "router_beyond": out["router_beyond"]}), flush=True)
        del model
    say(f"{wrong} control(s) on the wrong side of the limits")
    return int(wrong > 0)


if __name__ == "__main__":
    sys.exit(main())
