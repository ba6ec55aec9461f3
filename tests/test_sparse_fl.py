"""Sparse event-driven FL substrate (``repro.fl.sparse``).

The load-bearing guarantee: at M = N with every client available, the
sparse trainer reproduces the dense ``AsyncFLTrainer`` **bitwise** — the
top-M selection degenerates to the identity permutation, every gather /
scatter is an identity move, and the PRNG streams line up fold-for-fold.
Plus the sparse-only semantics the dense runtime has no analogue for:
slot eviction with starvation-free re-grant, quarantine × staleness ×
sparse-scheduling interplay, availability gating, and the client-axis
sharding hook.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.availability import AlwaysOn, MarkovChurn
from repro.core.bandits import GLRCUCB, RandomScheduler
from repro.core.channels import make_scenario, make_stationary
from repro.core.faults import NaNGradFaults
from repro.data.pipeline import client_batch_indices, gather_client_batches
from repro.fl import (
    AsyncFLConfig,
    AsyncFLTrainer,
    SparseFLConfig,
    SparseAsyncFLTrainer,
)
from repro.fl.sparse import _DATA_TAG
from repro.sim import shard as _shard

KEY = jax.random.PRNGKey(0)
D, NEX, B, E = 4, 12, 3, 2


def _loss(p, x, y):
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


def _params():
    return {"w": jnp.zeros((D,), jnp.float32),
            "b": jnp.zeros((), jnp.float32)}


def _client_data(n, seed=0):
    rng = np.random.default_rng(seed)
    cx = jnp.asarray(rng.normal(size=(n, NEX, D)).astype(np.float32))
    # continuous targets: local gradients are nonzero almost surely (a
    # zero gradient would legitimately pass any update-norm quarantine cap)
    cy = jnp.asarray(rng.normal(size=(n, NEX)).astype(np.float32))
    return cx, cy


def _dense_batches(cx, cy, keys):
    """The dense-side round data for parity runs: the SAME per-round,
    per-client-id fold derivation the sparse round executes on device."""
    n = cx.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    bxs, bys = [], []
    for r in range(keys.shape[0]):
        kd = jax.random.fold_in(keys[r], _DATA_TAG)
        idx = client_batch_indices(kd, ids, NEX, E, B)
        bx, by = gather_client_batches(cx, cy, ids, idx)
        bxs.append(bx)
        bys.append(by)
    return jnp.stack(bxs), jnp.stack(bys)


def _assert_state_parity(dense_state, sparse_state, metrics_d, metrics_s):
    pairs = [
        ("params", dense_state.params, sparse_state.params),
        ("buffers", dense_state.buffers, sparse_state.buffers),
        ("has_update", dense_state.has_update, sparse_state.has_update),
        ("last_success", dense_state.last_success, sparse_state.last_success),
        ("aoi", dense_state.aoi, sparse_state.aoi),
        ("staleness", dense_state.staleness, sparse_state.staleness),
        ("contrib", dense_state.contrib, sparse_state.contrib),
        ("zeta", dense_state.zeta, sparse_state.zeta),
        ("contrib_buf", dense_state.contrib_buf, sparse_state.contrib_buf),
        ("sched_state", dense_state.sched_state, sparse_state.sched_state),
        ("env_state", dense_state.env_state, sparse_state.env_state),
    ]
    for name, a, b in pairs:
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=f"leaf of {name}")
    for k in metrics_d:
        np.testing.assert_array_equal(
            np.asarray(metrics_d[k]), np.asarray(metrics_s[k]),
            err_msg=f"metric {k}")


# ---------------------------------------------------------------------------
# dense parity at M = N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("faults", [None, NaNGradFaults(rate=0.3)],
                         ids=["clean", "nan_faults"])
def test_sparse_reproduces_dense_bitwise_at_m_equals_n(faults):
    n, nch, r = 6, 8, 10
    cx, cy = _client_data(n)
    sched = GLRCUCB(nch, n, history=32)
    proc = make_scenario("piecewise", n_channels=nch, horizon=r,
                         n_breakpoints=2)
    rk = jax.random.fold_in(KEY, 77)

    dense = AsyncFLTrainer(
        AsyncFLConfig(n_clients=n, n_channels=nch, local_epochs=E,
                      staleness_cap=3, max_update_norm=50.0),
        sched, proc, _loss, faults=faults, realize_key=rk)
    sparse = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=n, n_channels=nch, batch_size=B,
                       local_epochs=E, staleness_cap=3, max_update_norm=50.0),
        sched, proc, _loss, faults=faults, realize_key=rk)

    keys = jax.random.split(jax.random.PRNGKey(9), r)
    bx, by = _dense_batches(cx, cy, keys)
    ds, dm = dense.run(dense.init(_params(), KEY), bx, by, keys)
    ss, sm = sparse.run(sparse.init(_params(), KEY), cx, cy, keys)

    _assert_state_parity(ds, ss, dm, {k: sm[k] for k in dm})
    # selection degenerated to the identity permutation every round
    np.testing.assert_array_equal(np.asarray(ss.slot_clients),
                                  np.arange(n, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(ss.slot_of),
                                  np.arange(n, dtype=np.int32))


def test_always_on_availability_is_bitwise_inert():
    """Attaching the always_on process changes no round arithmetic: the
    availability stream lives on its own fold tag."""
    n, m, nch, r = 24, 4, 6, 8
    cx, cy = _client_data(n)
    env = make_stationary(jnp.linspace(0.9, 0.3, nch))
    mk = lambda avail: SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch, batch_size=B,
                       local_epochs=E),
        GLRCUCB(nch, m, history=32), env, _loss, availability=avail)
    keys = jax.random.split(KEY, r)
    s0, m0 = mk(None).run(mk(None).init(_params(), KEY), cx, cy, keys)
    tr = mk(AlwaysOn())
    s1, m1 = tr.run(tr.init(_params(), KEY), cx, cy, keys)
    for a, b in [(s0.params, s1.params), (s0.aoi, s1.aoi),
                 (s0.buffers, s1.buffers), (s0.slot_clients, s1.slot_clients)]:
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in m0:
        np.testing.assert_array_equal(np.asarray(m0[k]), np.asarray(m1[k]))


# ---------------------------------------------------------------------------
# sparse regime: M << N
# ---------------------------------------------------------------------------

def test_sparse_run_finite_and_serves_population_under_churn():
    n, m, nch, r = 64, 4, 6, 40
    cx, cy = _client_data(n)
    tr = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch, batch_size=B,
                       local_epochs=1, staleness_cap=5),
        GLRCUCB(nch, m, history=32),
        make_stationary(jnp.linspace(0.9, 0.4, nch)), _loss,
        availability=MarkovChurn(p_drop=0.1, p_rejoin=0.5))
    st, mets = tr.run(tr.init(_params(), KEY), cx, cy,
                      jax.random.split(jax.random.PRNGKey(1), r))
    for leaf in jax.tree_util.tree_leaves((st.params, st.aoi, st.zeta,
                                           mets["local_loss"])):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert float(jnp.sum(mets["n_success"])) > 0
    # AoI-driven priorities spread grants across the population: most of the
    # 64 clients aggregated at least once within 40 rounds of 4 grants
    assert int(jnp.sum(st.aoi < r)) > n // 2
    # slot pool invariants: owners are a valid injective map
    owners = np.asarray(st.slot_clients)
    assert len(set(owners.tolist())) == m
    inv = np.asarray(st.slot_of)
    for j, c in enumerate(owners):
        assert inv[c] == j


# ---------------------------------------------------------------------------
# satellite: quarantine x staleness x sparse scheduling
# ---------------------------------------------------------------------------

def _sparse_trainer(n, m, nch, env, **cfg_kw):
    return SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch, batch_size=B,
                       local_epochs=1, **cfg_kw),
        RandomScheduler(nch, m), env, _loss)


def test_all_quarantined_rounds_are_bitwise_noop_and_regrant():
    """Every upload quarantined (absurd norm cap): params stay BITWISE at
    init, nothing aggregates, and the quarantined clients re-enter S_t so
    the rejection can never deadlock the schedulable set."""
    n, m, nch, r = 16, 4, 6, 12
    cx, cy = _client_data(n)
    good = make_stationary(jnp.full((nch,), 1.0))     # channel never fails
    tr = _sparse_trainer(n, m, nch, good, max_update_norm=1e-12)
    st0 = tr.init(_params(), KEY)
    st, mets = tr.run(st0, cx, cy, jax.random.split(KEY, r))
    for la, lb in zip(jax.tree_util.tree_leaves(st0.params),
                      jax.tree_util.tree_leaves(st.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert float(jnp.sum(mets["n_success"])) == 0.0
    # every scheduled-and-rejected client re-entered S_t (trains at next
    # grant) and its poisoned buffer was revoked
    sel = np.asarray(st.slot_clients)
    assert bool(jnp.all(jnp.take(st.last_success, st.slot_clients) == 1.0))
    assert bool(jnp.all(jnp.take(st.has_update, st.slot_clients) == 0.0))


def test_quarantined_nan_client_regrants_and_population_recovers():
    """30% NaN-corrupted clients under quarantine at M << N: the global
    model never ingests a NaN, and corruption does not starve the
    population — re-granted clients eventually aggregate a clean retrain."""
    n, m, nch, r = 16, 4, 6, 48
    cx, cy = _client_data(n)
    tr = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch, batch_size=B,
                       local_epochs=1),
        RandomScheduler(nch, m),
        make_stationary(jnp.full((nch,), 0.95)), _loss,
        faults=NaNGradFaults(rate=0.3))
    st, mets = tr.run(tr.init(_params(), KEY), cx, cy,
                      jax.random.split(jax.random.PRNGKey(5), r))
    for leaf in jax.tree_util.tree_leaves(st.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert float(jnp.sum(mets["n_success"])) > 0
    # no starvation: every client in the population aggregated at least once
    assert bool(jnp.all(st.aoi < r)), np.asarray(st.aoi)


def test_buffer_age_is_distinct_from_aoi_under_sparse_scheduling():
    """All-Bad channels: AoI grows uniformly (no deliveries), while the
    buffer-age counter resets at each retrain — the two age notions must
    not be conflated by the sparse gather/scatter."""
    n, m, nch, r = 16, 4, 6, 10
    cx, cy = _client_data(n)
    bad = make_stationary(jnp.zeros((nch,)))          # channel never succeeds
    tr = _sparse_trainer(n, m, nch, bad)
    st, mets = tr.run(tr.init(_params(), KEY), cx, cy,
                      jax.random.split(KEY, r))
    assert float(jnp.sum(mets["n_success"])) == 0.0
    np.testing.assert_array_equal(np.asarray(st.aoi), np.full((n,), r + 1.0))
    # clients that trained since have a younger buffer than their AoI
    assert bool(jnp.any(st.staleness < st.aoi))
    assert not bool(jnp.array_equal(st.staleness, st.aoi))


# ---------------------------------------------------------------------------
# client-axis sharding hook
# ---------------------------------------------------------------------------

def test_shard_clients_placement_is_bitwise_inert():
    n, m, nch, r = 32, 4, 6, 6
    cx, cy = _client_data(n)
    tr = _sparse_trainer(n, m, nch, make_stationary(jnp.linspace(0.9, 0.3, nch)))
    keys = jax.random.split(KEY, r)
    st_plain, mets_plain = tr.run(tr.init(_params(), KEY), cx, cy, keys)
    mesh = _shard.sweep_mesh()
    cx_s, cy_s = _shard.shard_clients((cx, cy), mesh)
    st_s, mets_s = tr.run(tr.init(_params(), KEY), cx_s, cy_s, keys)
    for la, lb in zip(jax.tree_util.tree_leaves(st_plain),
                      jax.tree_util.tree_leaves(st_s)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in mets_plain:
        np.testing.assert_array_equal(np.asarray(mets_plain[k]),
                                      np.asarray(mets_s[k]))


def test_gather_kernel_path_is_bitwise_inert():
    """Three rounds that read the scheduled clients' uint8 examples and int32
    labels with the ``client_gather`` kernel (interpret mode) train on the
    same bytes as XLA's gather: every state leaf and metric equal bitwise.
    Only clients 0-3 and 294-299 are schedulable, so the rounds read the
    first client and those past the last whole 128-client block."""
    n, m, nch, r, d, classes = 300, 8, 6, 3, 16, 4
    rng = np.random.default_rng(5)
    cx = jnp.asarray(rng.integers(0, 256, (n, NEX, d), dtype=np.uint8))
    cy = jnp.asarray(rng.integers(0, classes, (n, NEX), dtype=np.int32))

    def loss(p, x, y):
        logits = (x.astype(jnp.float32) / 255.0) @ p["w"] + p["b"]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    params = {"w": jnp.zeros((d, classes), jnp.float32),
              "b": jnp.zeros((classes,), jnp.float32)}
    tr = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch, batch_size=B,
                       local_epochs=E),
        GLRCUCB(nch, m, history=16),
        make_stationary(jnp.linspace(0.9, 0.4, nch)), loss)
    ids = jnp.arange(n)
    st0 = tr.init(params, KEY)._replace(
        avail=((ids < 4) | (ids >= n - 6)).astype(jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(3), r)
    st_jnp, mets_jnp = tr.run(st0, cx, cy, keys)
    st_k, mets_k = tr._run_plain(st0, cx, cy, keys, tr.env,
                                 ("pallas_interpret", "pallas_interpret"))
    assert float(jnp.sum(mets_jnp["n_success"])) > 0
    assert {0, n - 1} <= set(np.asarray(st_jnp.slot_clients).tolist())
    for la, lb in zip(jax.tree_util.tree_leaves(st_jnp),
                      jax.tree_util.tree_leaves(st_k)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in mets_jnp:
        np.testing.assert_array_equal(np.asarray(mets_jnp[k]),
                                      np.asarray(mets_k[k]))
