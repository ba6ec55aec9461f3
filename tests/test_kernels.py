"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# glr_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_channels,h", [(1, 32), (5, 300), (8, 128), (13, 513)])
def test_glr_scan_matches_oracle(n_channels, h):
    # force the Pallas kernel (interpret off-TPU): the auto backend would
    # pick the jnp oracle on CPU and compare it against itself
    hist = jax.random.bernoulli(KEY, 0.4, (n_channels, h)).astype(jnp.float32)
    counts = jnp.asarray(
        np.random.default_rng(0).integers(0, h + 1, n_channels), jnp.int32)
    got = ops.glr_scan(hist, counts, backend="pallas_interpret")
    want = ref.glr_scan(hist, counts)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@given(st.integers(2, 40), st.floats(0.05, 0.95), st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_glr_scan_property(n, p, seed):
    k = jax.random.PRNGKey(seed)
    hist = jax.random.bernoulli(k, p, (3, 64)).astype(jnp.float32)
    counts = jnp.array([n, 1, 0], jnp.int32)
    got = ops.glr_scan(hist, counts, backend="pallas_interpret")
    want = ref.glr_scan(hist, counts)
    np.testing.assert_allclose(got[:1], want[:1], rtol=1e-4, atol=1e-4)
    assert got[1] == -np.inf and got[2] == -np.inf   # n < 2 -> no split point


def test_glr_scan_detects_synthetic_changepoint():
    h = jnp.concatenate([jnp.zeros((1, 100)), jnp.ones((1, 100))], axis=1)
    stat = ops.glr_scan(h, jnp.array([200]), backend="pallas_interpret")
    assert float(stat[0]) > 50.0


# ---------------------------------------------------------------------------
# glr_scan backend dispatch (the GLR-CUCB detector hot path)
# ---------------------------------------------------------------------------

def test_glr_scan_dispatch_backends_agree():
    hist = jax.random.bernoulli(KEY, 0.3, (6, 96)).astype(jnp.float32)
    counts = jnp.array([0, 1, 2, 50, 96, 96], jnp.int32)   # incl. full buffer
    a = ops.glr_scan(hist, counts, backend="pallas_interpret")
    b = ops.glr_scan(hist, counts, backend="jnp")
    c = ops.glr_scan(hist, counts)                          # auto (jnp on CPU)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=1e-5)


def test_glr_scan_dispatch_rejects_unknown_backend():
    hist = jnp.zeros((2, 32))
    with pytest.raises(ValueError, match="unknown backend"):
        ops.glr_scan(hist, jnp.array([4, 4]), backend="cuda")


_PALLAS_CALLS = {
    "glr_scan": lambda: ops.glr_scan(jnp.zeros((2, 32)), jnp.array([4, 4]),
                                     backend="pallas"),
    "glr_step": lambda: ops.glr_step(
        jnp.zeros((2, 32)), *(jnp.zeros((2,)),) * 4, jnp.ones((2,), bool),
        backend="pallas"),
    "weighted_aggregate": lambda: ops.weighted_aggregate(
        jnp.zeros((3, 8)), jnp.ones((3,)), backend="pallas"),
    "robust_trimmed": lambda: ops.robust_trimmed(
        jnp.zeros((3, 8)), jnp.ones((3,)), jnp.asarray(3.0),
        jnp.asarray(1.0), backend="pallas"),
    "flash_attention": lambda: ops.flash_attention(
        *(jnp.zeros((1, 1, 8, 8)),) * 3, backend="pallas"),
    "client_gather": lambda: ops.client_gather(
        jnp.zeros((3, 2, 8), jnp.uint8), jnp.array([0]), backend="pallas"),
}


@pytest.mark.parametrize("name", sorted(_PALLAS_CALLS))
def test_explicit_pallas_backend_raises_off_tpu(name):
    """``backend="pallas"`` asks for the compiled kernel: off-TPU that is an
    error, never a silent switch to interpret mode."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel runs here")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _PALLAS_CALLS[name]()


def _drive_glr_cucb(sched, t_rounds, n, m):
    """Run a jitted select/update loop long enough to wrap the ring buffer."""

    @jax.jit
    def step(state, t_key):
        t, k = t_key
        ch, aux = sched.select(state, t, k, jnp.ones((m,)))
        # deterministic reward stream with a mid-stream mean flip so the
        # detector has something to look at
        flip = (t >= t_rounds // 2)
        rewards = jnp.where(
            flip, (ch % 2 == 0).astype(jnp.float32),
            (ch % 2 == 1).astype(jnp.float32))
        return sched.update(state, t, ch, rewards, aux), state.restarts

    ts = jnp.arange(t_rounds)
    keys = jax.random.split(KEY, t_rounds)
    state = sched.init(KEY)
    state, _ = jax.lax.scan(step, state, (ts, keys))
    return state


@pytest.mark.parametrize("history", [16, 64])   # 16 << rounds: ring-buffer-full
def test_glr_cucb_update_backend_equivalence(history):
    """Pallas (interpret) and jnp detector paths agree inside a jitted
    GLRCUCB.update, including once the history ring buffer has wrapped."""
    from repro.core.bandits import GLRCUCB
    rounds, n, m = 120, 5, 2

    def make(backend):
        return GLRCUCB(n, m, history=history, detector_stride=3,
                       min_samples=8, detector_backend=backend)

    st_jnp = _drive_glr_cucb(make("jnp"), rounds, n, m)
    st_pal = _drive_glr_cucb(make("pallas_interpret"), rounds, n, m)
    assert int(st_jnp.restarts) == int(st_pal.restarts)
    np.testing.assert_allclose(
        np.asarray(st_jnp.mu_tilde), np.asarray(st_pal.mu_tilde),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(st_jnp.counts), np.asarray(st_pal.counts))
    assert int(st_jnp.tau) == int(st_pal.tau)


# ---------------------------------------------------------------------------
# weighted_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,p,dtype", [
    (2, 64, jnp.float32),
    (8, 5000, jnp.bfloat16),
    (16, 2048, jnp.float32),
    (5, 2049, jnp.bfloat16),     # non-aligned P exercises padding
])
def test_weighted_aggregate_matches_oracle(m, p, dtype):
    upd = (jax.random.normal(KEY, (m, p)) * 2).astype(dtype)
    sc = jax.random.uniform(jax.random.fold_in(KEY, 1), (m,))
    # pin the kernel backend: the CPU auto-dispatch returns the oracle itself
    got = ops.weighted_aggregate(upd, sc, backend="pallas_interpret")
    want = ref.weighted_aggregate(upd, sc)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_weighted_aggregate_mask_semantics():
    upd = jnp.stack([jnp.ones((32,)), jnp.full((32,), 100.0)])
    sc = jnp.array([1.0, 0.0])                 # masked-out client contributes 0
    np.testing.assert_allclose(
        ops.weighted_aggregate(upd, sc, backend="pallas_interpret"), 1.0)
    np.testing.assert_allclose(
        ops.weighted_aggregate(upd, sc, backend="jnp"), 1.0)


@given(st.integers(1, 12), st.integers(1, 300), st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_weighted_aggregate_property(m, p, seed):
    k = jax.random.PRNGKey(seed)
    upd = jax.random.normal(k, (m, p))
    sc = jax.random.uniform(jax.random.fold_in(k, 1), (m,))
    got = ops.weighted_aggregate(upd, sc, backend="pallas_interpret")
    np.testing.assert_allclose(got, ref.weighted_aggregate(upd, sc),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# client_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dtype,ids", [
    ((300, 4, 16), jnp.uint8, [0, 299, 256, 5, 130]),  # ids in the partial block
    ((300, 8, 784), jnp.uint8, [299, 0, 257, 128]),    # the trainer's d = 784
    ((256, 3, 8), jnp.uint8, [255, 0, 127, 128]),      # whole blocks; 3 rows
    ((100, 5, 16), jnp.uint8, [99, 0, 50]),            # N < 128: all partial
    ((300, 64), jnp.int32, [0, 299, 1, 200]),          # labels, (N, n)
    ((300, 9, 24), jnp.int32, [[3, 299], [0, 128]]),   # vmapped over seeds
    ((300, 2, 8), jnp.uint8, list(range(299, 169, -1))),  # M = 130 > 128
    ((130, 600, 784), jnp.uint8, [129, 0, 128, 5]),    # n = 600: 8 row tiles
    ((200, 6, 16384), jnp.uint8, list(range(199, 69, -1))),  # tiles, M > 128
], ids=["tail", "d784", "whole_blocks", "n_lt_128", "labels", "vmapped",
        "m_gt_128", "n600_d784", "row_tiles_m_gt_128"])
def test_client_gather_matches_take(shape, dtype, ids):
    """The kernel returns exactly the bytes of ``jnp.take`` for every id,
    the first, the last and those past the last whole 128-client block."""
    bits = jax.random.bits(KEY, shape, jnp.uint32)
    x = (bits.astype(jnp.uint8) if dtype == jnp.uint8
         else jax.lax.bitcast_convert_type(bits, jnp.int32))
    ids = jnp.asarray(ids, jnp.int32)
    gather = lambda i: ops.client_gather(x, i, backend="pallas_interpret")
    got = jax.vmap(gather)(ids) if ids.ndim == 2 else gather(ids)
    want = jax.vmap(lambda i: ref.client_gather(x, i))(ids) if ids.ndim == 2 \
        else ref.client_gather(x, ids)
    assert got.dtype == x.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape,dtype,tile,ok", [
    ((100000, 64, 784), jnp.uint8, 64, True),   # the population trainer
    ((100000, 64), jnp.int32, 1, True),         # its labels
    ((10000, 600, 784), jnp.uint8, 75, True),   # McMahan's 600 a client
    ((100, 601, 784), jnp.uint8, 1, True),      # n prime: a row at a time
    ((100, 4, 32768), jnp.uint8, 1, True),      # the widest row that fits
    ((100, 4, 32772), jnp.uint8, 0, False),     # one row over the budget
    ((100, 4, 6), jnp.uint8, None, False),         # rows not whole words
    ((100, 4, 8), jnp.float32, None, False),
    ((100,), jnp.int32, None, False),
], ids=["fedcnn", "labels", "n600", "n_prime", "widest", "too_wide",
        "odd_bytes", "float", "1d"])
def test_client_gather_supports(shape, dtype, tile, ok):
    """The kernel reads a client's rows in tiles that fit its VMEM budget,
    whatever the rows a client holds, and says which arrays it cannot
    read, so that the caller keeps XLA's gather for them."""
    from repro.kernels import client_gather as cg
    assert cg.supports(shape, dtype) is ok
    if tile is not None:
        n, d = (1, shape[1]) if len(shape) == 2 else shape[1:]
        assert cg._rows_per_tile(n, d, dtype) == tile
        tiles = 4 * max(tile, 1) + 4                 # and the temporaries
        fits = tiles * cg._row_bytes(d, dtype) <= cg.BLOCK_BUDGET_BYTES
        assert fits is ok


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window", [
    (1, 2, 2, 128, 64, True, 0),
    (2, 4, 2, 257, 72, True, 0),      # GQA + non-aligned seq + padded head dim
    (1, 4, 1, 200, 128, False, 0),    # MQA encoder-style
    (1, 2, 2, 300, 64, True, 64),     # sliding window
    (2, 8, 4, 64, 96, True, 16),
])
def test_flash_attention_matches_oracle(b, hq, hkv, s, d, causal, window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, hq, s, d), jnp.float32) * 0.5
    k = jax.random.normal(k2, (b, hkv, s, d), jnp.float32) * 0.5
    v = jax.random.normal(k3, (b, hkv, s, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              backend="pallas_interpret")
    want = ref.mha_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = jax.random.normal(KEY, (1, 2, 128, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 128, 64), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, backend="pallas_interpret")
    want = ref.mha_attention(q, k, v)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=3e-2, atol=3e-2)


def test_flash_attention_matches_model_attn_core():
    """The Pallas kernel and the model's chunked XLA path agree."""
    from repro.models.attention import attn_core
    q = jax.random.normal(KEY, (1, 4, 300, 64)) * 0.3
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 300, 64)) * 0.3
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 300, 64))
    a = ops.flash_attention(q, k, v, causal=True, backend="pallas_interpret")
    b = attn_core(q, k, v, causal=True, chunk=128)
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
