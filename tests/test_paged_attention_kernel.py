"""The Pallas paged decode-attention kernels (ops/pallas/paged_attention.py,
its siblings for folded pools and for a latent layer's one pool)
and its gate in models/kv_cache.py.

On the CPU the kernel runs through the Pallas interpreter (``_interpret``,
as flash_attention's splash tests do). Oracle: ``_masked_attention`` over
the gathered pages, the formulation the kernel replaces for ``s == 1``.
The last tests compile the kernel for a described v5e at the benchmark's
real shapes: what the chip's compiler would refuse is refused here.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, kv_cache
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

BS, D = 16, 128
TOL = {"float32": 2e-6, "bfloat16": 1.6e-2}   # bf16: 2 ulp of an O(1) result


@pytest.fixture()
def interpreted():
    pa._interpret = True
    yield
    pa._interpret = False


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """Serving programs compile fresh (tests/test_serving_sched.py says why)."""
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _heads(dtype, gqa):
    """The least head counts the gate accepts: the KV heads fill a tile."""
    kvh = pa.sublane_tile(dtype)
    return (2 * kvh if gqa else kvh), kvh


def _case(seed, batch, max_blocks, dtype, gqa, lengths):
    rng = np.random.default_rng(seed)
    n_heads, kvh = _heads(dtype, gqa)
    nb = batch * max_blocks + 3
    kp, vp = (jnp.asarray(rng.standard_normal((nb, BS, kvh, D)), dtype)
              for _ in "kv")
    q = jnp.asarray(rng.standard_normal((batch, 1, n_heads, D)), dtype)
    # shuffled, non-contiguous pages; block 0 belongs to nobody
    table = (1 + rng.permutation(nb - 1)[:batch * max_blocks]).reshape(
        batch, max_blocks).astype(np.int32)
    pos = np.asarray(lengths, np.int32) - 1
    return q, kp, vp, table, pos


def _oracle(q, kp, vp, table, pos):
    return np.asarray(kv_cache._paged_attend_xla(
        q, kp, vp, jnp.asarray(table), jnp.asarray(pos)), np.float32)


def _kernel(q, kp, vp, table, pos, **kw):
    return np.asarray(pa.paged_attention_decode(
        q[:, 0], kp, vp, jnp.asarray(table), jnp.asarray(pos) + 1, **kw),
        np.float32)[:, None]


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, "page_short", "full"])
def test_one_row_ragged_length_matches_gather(interpreted, length, dtype, gqa):
    max_blocks = 3
    n = {"page_short": (max_blocks - 1) * BS, "full": max_blocks * BS}.get(
        length, length)
    case = _case(n, 1, max_blocks, dtype, gqa, [n])
    # two pages a group: a full table is two groups, the second half live
    got = _kernel(*case, pages_per_group=2)
    np.testing.assert_allclose(got, _oracle(*case), atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("pages_per_group", [4, None], ids=["4", "default"])
@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batch_of_shuffled_tables_matches_gather(interpreted, dtype, gqa,
                                                 pages_per_group):
    """Four rows over 17-page tables (not a multiple of the group): one
    position, mid-page, one page short of full, full."""
    max_blocks = 17
    lengths = [1, 5 * BS + 7, (max_blocks - 1) * BS, max_blocks * BS]
    case = _case(3, 4, max_blocks, dtype, gqa, lengths)
    got = _kernel(*case, pages_per_group=pages_per_group)
    np.testing.assert_allclose(got, _oracle(*case), atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_idle_rows_leave_live_rows_unchanged_and_finite(interpreted, dtype):
    """An idle slot's row is all -1 with pos 0: it reads block 0, its result
    is finite, and the live rows beside it read what they read without it."""
    lengths = [40, 1, 3 * BS, 1]
    q, kp, vp, table, pos = _case(5, 4, 3, dtype, False, lengths)
    # block 0 holds what a NaN-poisoned free block would: the idle rows
    # read it (clamped -1) but nothing of it may reach a live row
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    idle = np.array([1, 3])
    table[idle] = -1
    pos[idle] = 0
    got = _kernel(q, kp, vp, table, pos)
    live = np.array([0, 2])
    alone = _kernel(q[live], kp, vp, table[live], pos[live])
    np.testing.assert_array_equal(got[live], alone)
    np.testing.assert_allclose(got[live], _oracle(
        q[live], kp, vp, table[live], pos[live]), atol=TOL[dtype], rtol=0)
    kp, vp = kp.at[0].set(0.0), vp.at[0].set(0.0)
    assert np.isfinite(_kernel(q, kp, vp, table, pos)).all()


def test_stale_scratch_never_reaches_a_result():
    """Scratch memory starts as NaN bit patterns (the TPU interpreter's
    ``uninitialized_memory="nan"``): pages past the live length are never
    copied, and what the buffers held before must not reach ``P x V``."""
    from jax.experimental.pallas import tpu as pltpu

    pa._interpret = pltpu.InterpretParams(uninitialized_memory="nan")
    try:
        case = _case(9, 2, 5, "float32", False, [3, 2 * BS + 1])
        got = _kernel(*case, pages_per_group=4)
    finally:
        pa._interpret = False
    np.testing.assert_allclose(got, _oracle(*case), atol=TOL["float32"],
                               rtol=0)


# ---- the gate ---------------------------------------------------------------

def _attend_args(s=1, d=D, kvh=8, n_heads=8, q_dtype="float32",
                 pool_dtype="float32"):
    q = jnp.zeros((2, s, n_heads, d), q_dtype)
    pool = jnp.zeros((4, BS, kvh, d), pool_dtype)
    return q, pool, pool, jnp.zeros((2, 2), jnp.int32), jnp.ones(
        (2,), jnp.int32)


@pytest.mark.parametrize("kw, forced, want", [
    (dict(), False, "xla"),                       # the CPU is not a TPU
    (dict(), True, "pallas"),
    (dict(s=2), True, "xla"),                     # prefill, chunks, verify
    (dict(d=64), True, "xla"),                    # head size not 128 k
    (dict(kvh=4, n_heads=8), True, "xla"),        # KV heads under a tile
    (dict(q_dtype="bfloat16"), True, "xla"),      # q and pool differ
    (dict(q_dtype="float16", pool_dtype="float16"), True, "xla"),
], ids=["cpu", "forced", "s2", "d64", "kvh4", "mixed", "fp16"])
def test_gate_table(kw, forced, want):
    pa._interpret = forced
    try:
        kv_cache._last_path = None
        out = kv_cache._paged_attend(*_attend_args(**kw))
    finally:
        pa._interpret = False
    assert kv_cache._last_path == want
    assert out.shape == _attend_args(**kw)[0].shape


def test_selected_kernel_that_raises_is_not_swallowed(interpreted,
                                                      monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "paged_attention_decode", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        kv_cache._paged_attend(*_attend_args())
    assert kv_cache._last_path == "pallas"


def test_kernel_refuses_shapes_its_gate_refuses():
    q, kp, vp, table, pos = _attend_args(d=64)
    with pytest.raises(ValueError, match="does not support"):
        pa.paged_attention_decode(q[:, 0], kp, vp, table, pos)


def test_sharded_step_names_the_xla_formulation(interpreted):
    """The tp step must reach no pallas_call (GSPMD does not partition one):
    its cache step is the write and ``_paged_attend_xla`` by name, whatever
    the gate would say of the same shapes."""
    from paddle_tpu.serving.sharded import step

    q, kp, vp, table, pos = _attend_args()
    kv_cache._last_path = None
    out, kp2, vp2, pos2 = step._paged_cache_xla(q, q, q, kp, vp, table, pos)
    assert kv_cache._last_path is None
    want = kv_cache._paged_cache_raw(q, q, q, kp, vp, table, pos)
    assert kv_cache._last_path == "pallas"
    for got, ref in zip((out, kp2, vp2, pos2), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)
    jaxpr = str(jax.make_jaxpr(step._paged_cache_xla)(
        q, q, q, kp, vp, table, pos))
    assert "pallas_call" not in jaxpr
    assert "pallas_call" in str(jax.make_jaxpr(kv_cache._paged_cache_raw)(
        q, q, q, kp, vp, table, pos))


# ---- through the scheduler --------------------------------------------------

@pytest.fixture(scope="module")
def wide_head_model():
    """The least GPT whose head size the gate accepts: 8 heads x 128."""
    paddle.seed(11)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=1024, num_layers=2, num_heads=8,
        max_position_embeddings=64))
    model.eval()
    return model


def _generate(model, prompts, **cfg):
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(**cfg))
    outs = sched.generate(prompts, max_new_tokens=8)
    return [np.asarray(o) for o in outs], sched.metrics.snapshot()


@pytest.mark.parametrize("cfg, preempts", [
    (dict(max_num_seqs=4, max_seq_len=64, block_size=8), False),
    # both admit, both cannot finish: the younger is preempted and resumed
    (dict(max_num_seqs=2, max_seq_len=64, block_size=4, num_blocks=6), True),
], ids=["roomy", "forced_preemption"])
def test_scheduler_greedy_tokens_equal_the_xla_path(wide_head_model, cfg,
                                                    preempts):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n) for n in ((10, 9) if preempts
                                                 else (10, 9, 17, 3))]
    kv_cache._last_path = None
    want, _ = _generate(wide_head_model, prompts, **cfg)
    assert kv_cache._last_path == "xla"
    pa._interpret = True
    try:
        got, m = _generate(wide_head_model, prompts, **cfg)
    finally:
        pa._interpret = False
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert (m["preemptions"] >= 1) == preempts
    assert m["free_blocks"] == m["total_blocks"]


# ---- the chip's compiler, without the chip -----------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch, max_blocks, num_blocks, n_heads, kvh, dtype", [
    (32, 128, 3480, 16, 16, "bfloat16"),   # the benchmark's decode program
    (1, 17, 17, 16, 16, "bfloat16"),       # its reference check
    (4, 17, 64, 32, 16, "bfloat16"),       # GQA
    (4, 17, 64, 8, 8, "float32"),
], ids=["decode_1p3b", "check_1p3b", "gqa", "float32"])
def test_compiles_for_v5e(one_chip, batch, max_blocks, num_blocks, n_heads,
                          kvh, dtype):
    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    pool = shape((num_blocks, BS, kvh, D), dtype)
    compiled = jax.jit(pa.paged_attention_decode).lower(
        shape((batch, n_heads, D), dtype), pool, pool,
        shape((batch, max_blocks), "int32"),
        shape((batch,), "int32")).compile()
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "tpu_custom_call" in text
    # the pool is read in place: no copy of it, no scratch of its size
    pool_bytes = num_blocks * BS * kvh * D * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


# ---- the sibling for folded pools (ops/pallas/paged_attention_gqa.py) --------

from paddle_tpu.ops.pallas import paged_attention_gqa as pg  # noqa: E402


@pytest.fixture()
def gqa_interpreted():
    pg._interpret = True
    yield
    pg._interpret = False


def _folded_case(seed, batch, max_blocks, dtype, lengths, window, n_heads=8,
                 kvh=2, dk=24, dv=16, bs=4):
    """Folded pools (K rows wider than V rows), shuffled pages, and for a
    window layer a table that holds only the pages its window reaches."""
    rng = np.random.default_rng(seed)
    nb = batch * max_blocks + 3
    kp = jnp.asarray(rng.standard_normal((nb, bs, kvh * dk)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, bs, kvh * dv)), dtype)
    q = jnp.asarray(rng.standard_normal((batch, 1, n_heads, dk)), dtype)
    table = (1 + rng.permutation(nb - 1)[:batch * max_blocks]).reshape(
        batch, max_blocks).astype(np.int32)
    pos = np.asarray(lengths, np.int32) - 1
    sink = jnp.asarray(rng.standard_normal(n_heads), jnp.float32)
    base = None
    if window is not None:
        base = np.maximum(pos - window + 1, 0) // bs * bs
    return q, kp, vp, table, pos, window, sink, base, kvh


def _folded_oracle(q, kp, vp, table, pos, window, sink, base, kvh):
    return np.asarray(kv_cache._paged_attend_xla(
        q, kp, vp, jnp.asarray(table), jnp.asarray(pos), window, sink,
        None if base is None else jnp.asarray(base), kvh), np.float32)


def _folded_kernel(q, kp, vp, table, pos, window, sink, base, kvh, **kw):
    return np.asarray(pg.paged_attention_gqa_decode(
        q[:, 0], kp, vp, jnp.asarray(table), jnp.asarray(pos) + 1,
        window=window, sink=sink,
        base=None if base is None else jnp.asarray(base), **kw),
        np.float32)[:, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind, lengths, max_blocks, pages", [
    ("full", [1, 4, 5, 23, 40], 10, 4),       # whole context, 3 groups
    ("full", [17, 3], 6, None),               # one group holds the table
    ("window", [1, 7, 8, 9, 31, 12], 3, 2),   # window 8: 3 pages, 2 groups
    ("window", [40, 2, 16], 3, None),
], ids=["full_groups", "full_one_group", "window_groups", "window_one"])
def test_folded_kernel_matches_gather_for_both_layer_kinds(
        gqa_interpreted, dtype, kind, lengths, max_blocks, pages):
    window = 8 if kind == "window" else None
    case = _folded_case(11, len(lengths), max_blocks, dtype, lengths, window)
    if kind == "full":
        case = case[:6] + (None,) + case[7:]       # full layers have no sink
    got = _folded_kernel(*case, pages_per_group=pages)
    np.testing.assert_allclose(got, _folded_oracle(*case), atol=TOL[dtype],
                               rtol=0)


def test_folded_kernel_idle_rows_are_finite_and_window_reads_no_more(
        gqa_interpreted):
    case = list(_folded_case(5, 3, 3, "float32", [20, 1, 9], 8))
    case[3][1] = -1                                   # an idle row's table
    case[4][1] = -1                                   # length 0
    got = _folded_kernel(*case)
    assert np.isfinite(got).all()
    want = _folded_oracle(*case)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-6)
    # what lies behind the window is never read: poison the pool's other
    # blocks and the result does not move
    q, kp, vp, table = case[:4]
    used = np.unique(table[[0, 2]])
    poison = np.setdiff1d(np.arange(kp.shape[0]), used)
    case[1], case[2] = kp.at[poison].set(np.nan), vp.at[poison].set(np.nan)
    np.testing.assert_allclose(_folded_kernel(*case)[[0, 2]], got[[0, 2]],
                               atol=0)


@pytest.mark.parametrize("forced, s, want", [
    (False, 1, "xla"), (True, 1, "pallas"), (True, 2, "xla")],
    ids=["cpu", "forced", "s2"])
def test_gate_takes_the_folded_kernel_by_the_pools_layout(forced, s, want):
    pg._interpret = forced
    try:
        q = jnp.zeros((2, s, 8, 24), "float32")
        kp, vp = jnp.zeros((4, 4, 48), "float32"), jnp.zeros((4, 4, 32),
                                                             "float32")
        kv_cache._last_path = None
        out = kv_cache._paged_attend(
            q, kp, vp, jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32),
            kv_heads=2)
    finally:
        pg._interpret = False
    assert kv_cache._last_path == want
    assert out.shape == (2, s, 8, 16)


@pytest.mark.parametrize("kind, kvh, max_blocks, num_blocks, batch", [
    ("full", 4, 256, 20000, 128),      # the MiMo-V2.5 cell's decode program
    ("window", 8, 9, 1152, 128),
    ("full", 4, 33, 33, 1),            # its reference check
    ("window", 8, 9, 9, 1),
], ids=["full_decode", "window_decode", "full_check", "window_check"])
def test_folded_kernel_compiles_for_v5e(one_chip, kind, kvh, max_blocks,
                                        num_blocks, batch):
    def shape(dims, dt="bfloat16"):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    window = 128 if kind == "window" else None
    fn = lambda q, kp, vp, t, n, b, s: pg.paged_attention_gqa_decode(
        q, kp, vp, t, n, window=window, sink=s if window else None, base=b)
    compiled = jax.jit(fn).lower(
        shape((batch, 64, 192)), shape((num_blocks, 16, kvh * 192)),
        shape((num_blocks, 16, kvh * 128)),
        shape((batch, max_blocks), "int32"), shape((batch,), "int32"),
        shape((batch,), "int32"), shape((64,), "float32")).compile()
    text = compiled.as_text()
    assert f"paged_gqa_decode_{kind}" in text and "tpu_custom_call" in text
    pool_bytes = num_blocks * 16 * kvh * 192 * 2
    if batch > 1:      # the pools are read in place
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


# ---- the sibling for a latent layer's one pool (ops/pallas/paged_mla_decode.py)

from paddle_tpu.ops.pallas import paged_mla_decode as pm  # noqa: E402


@pytest.fixture()
def mla_interpreted():
    pm._interpret = True
    yield
    pm._interpret = False


def _latent_case(seed, batch, max_blocks, dtype, lengths, n_heads=4, row=40,
                 v_dim=32, bs=4):
    """One pool of rows padded to a lane tile (zeros in the padding),
    pages in shuffled pool order (no row's pages are neighbours), the
    absorbed query of every head against them."""
    rng = np.random.default_rng(seed)
    nb = batch * max_blocks + 3
    width = kv_cache.latent_row_width(row)
    pool = jnp.zeros((nb, bs, width), dtype).at[:, :, :row].set(
        jnp.asarray(rng.standard_normal((nb, bs, row)), dtype))
    q = jnp.asarray(rng.standard_normal((batch, 1, n_heads, row)), dtype)
    table = (1 + rng.permutation(nb - 1)[:batch * max_blocks]).reshape(
        batch, max_blocks).astype(np.int32)
    return q, pool, table, np.asarray(lengths, np.int32) - 1, v_dim, 0.25


def _latent_oracle(q, pool, table, pos, v_dim, scale):
    """The XLA formulation: the table's pages gathered, every head against
    the same rows, the value a row's first ``v_dim`` lanes."""
    keys = pool[jnp.maximum(jnp.asarray(table), 0)].reshape(
        q.shape[0], -1, 1, pool.shape[-1])
    wide = jnp.pad(q, ((0, 0),) * 3 + ((0, pool.shape[-1] - q.shape[-1]),))
    return np.asarray(kv_cache._masked_attention(
        wide, keys, keys[..., :v_dim], jnp.asarray(pos), scale=scale),
        np.float32)


def _latent_kernel(q, pool, table, pos, v_dim, scale, **kw):
    return np.asarray(pm.paged_mla_decode(
        q[:, 0], pool, jnp.asarray(table), jnp.asarray(pos) + 1, v_dim=v_dim,
        scale=scale, **kw), np.float32)[:, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lengths, max_blocks, pages, bs", [
    ([1, 3, 4, 5, 23, 39], 10, 4, 4),         # ragged, 3 groups
    ([17, 3], 6, None, 4),                    # one group holds the table
    ([23, 0, 39, 0, 0, 5], 10, 4, 4),         # idle rows (0) between live ones
    ([511, 512, 513, 700], 44, 32, 16),       # a group's edges; 513: one page
    ([1100, 40], 72, 32, 16),                 # a row longer than two groups
], ids=["groups", "one_group", "idle_rows", "group_edges", "three_groups"])
def test_latent_kernel_matches_gather(mla_interpreted, dtype, lengths,
                                      max_blocks, pages, bs):
    case = _latent_case(13, len(lengths), max_blocks, dtype, lengths, bs=bs)
    _, _, table, pos, _, _ = case
    idle = np.asarray(lengths) == 0
    table[idle], pos[idle] = -1, -1           # as the scheduler frees a slot
    got = _latent_kernel(*case, pages_per_group=pages)
    assert got.shape == (len(lengths), 1, 4, 32)
    assert not got[idle].any()                # exact zeros, never scratch
    np.testing.assert_allclose(got[~idle], _latent_oracle(*case)[~idle],
                               atol=TOL[dtype], rtol=0)


def test_latent_kernel_idle_rows_are_finite_and_dead_pages_are_not_read(
        mla_interpreted):
    q, pool, table, pos, v_dim, scale = _latent_case(
        5, 3, 4, "float32", [14, 1, 9])
    table[1], pos[1] = -1, -1                         # an idle row
    run = lambda p: _latent_kernel(q, p, table, pos, v_dim, scale,
                                   pages_per_group=2)
    got = run(pool)
    assert np.isfinite(got).all() and not got[1].any()
    # an idle row reads nothing, and pages past a live row's length are
    # never copied: poison every block the live rows' live pages do not
    # name and the result does not move
    used = np.concatenate([table[0, :4], table[2, :3]])
    poison = np.setdiff1d(np.arange(pool.shape[0]), used)
    np.testing.assert_array_equal(run(pool.at[poison].set(np.nan)), got)


@pytest.mark.parametrize("forced, s, dtype, want", [
    (False, 1, "float32", "xla"), (True, 1, "float32", "pallas"),
    (True, 2, "float32", "xla"), (True, 1, "float16", "xla")],
    ids=["cpu", "forced", "s2", "fp16"])
def test_gate_takes_the_latent_kernel_by_the_pools_layout(forced, s, dtype,
                                                          want):
    pm._interpret = forced
    try:
        kv_cache._last_path = None
        out, pool2, pos2 = kv_cache._latent_attend_raw(
            jnp.zeros((2, s, 4, 40), dtype), jnp.ones((2, s, 40), dtype),
            jnp.zeros((4, 4, 128), dtype), jnp.ones((2,), jnp.int32),
            jnp.asarray([[0, 1], [2, 3]], jnp.int32), v_dim=32, scale=0.25)
    finally:
        pm._interpret = False
    assert kv_cache._last_path == want
    assert out.shape == (2, s, 4, 32) and list(pos2) == [1 + s, 1 + s]
    # the new rows landed at position 1 of each row's first page, padded
    assert (np.asarray(pool2, np.float32)[[0, 2], 1, :40] == 1).all()
    assert not np.asarray(pool2, np.float32)[:, :, 40:].any()


@pytest.mark.parametrize("batch, max_blocks, num_blocks", [
    (128, 512, 36000),      # the JoyAI-LLM-Flash cell's decode program
    (1, 35, 35),
], ids=["decode", "one_row"])
def test_latent_kernel_compiles_for_v5e(one_chip, batch, max_blocks,
                                        num_blocks):
    def shape(dims, dt="bfloat16"):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    fn = lambda q, pool, t, n: pm.paged_mla_decode(
        q, pool, t, n, v_dim=512, scale=192 ** -0.5)
    compiled = jax.jit(fn).lower(
        shape((batch, 32, 576)), shape((num_blocks, 16, 640)),
        shape((batch, max_blocks), "int32"),
        shape((batch,), "int32")).compile()
    text = compiled.as_text()
    assert "paged_mla_decode" in text and "tpu_custom_call" in text
    if batch > 1:      # the pool is read in place
        assert (compiled.memory_analysis().temp_size_in_bytes
                < num_blocks * 16 * 640 * 2 // 8)
    # a row of the published width is not whole lane tiles: refused
    assert not pm.supports((batch, 32, 576), "bfloat16",
                           (num_blocks, 16, 576), "bfloat16", 512)
