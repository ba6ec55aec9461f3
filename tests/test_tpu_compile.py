"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed alongside
jaxlib, and it compiles for a topology that is described but not attached.
That catches what interpret mode cannot (Mosaic lowering gaps, tiling and
VMEM limits) at the shapes the scheduling service, the population trainer
and the sweeps use.  Each test asserts that the compiled HLO holds the
kernel (``tpu_custom_call``), not an XLA fallback.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and test workers
import every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import glr_scan as _glr
from repro.kernels import glr_step as _gs
from repro.kernels import robust_agg as _ra
from repro.kernels import weighted_aggregate as _wa


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # before the TPU library loads: otherwise it logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _glr_operands(sharding, lead, n, h):
    """Shapes of one fused detector step: cum (*lead, N, H); the per-channel
    scalars (*lead, N); sched bool."""
    col = lambda dt=jnp.float32: _sds(sharding, lead + (n,), dt)
    return (_sds(sharding, lead + (n, h)), col(), col(), col(), col(),
            col(jnp.bool_))


@pytest.mark.parametrize("split_grid", ["all", "geometric"])
def test_glr_step_compiles(one_chip, no_persistent_cache, split_grid):
    fn = functools.partial(_gs.glr_step, split_grid=split_grid,
                           interpret=False)
    text = _compile_text(fn, *_glr_operands(one_chip, (), 16, 1024))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("split_grid", ["all", "geometric"])
def test_glr_step_tenants_compiles(one_chip, no_persistent_cache, split_grid):
    fn = functools.partial(_gs.glr_step_tenants, split_grid=split_grid,
                           interpret=False)
    text = _compile_text(fn, *_glr_operands(one_chip, (256,), 16, 256))
    assert "tpu_custom_call" in text


def test_glr_step_custom_vmap_compiles(one_chip, no_persistent_cache):
    """The serving loop's form: ``vmap`` of the 2-D step over the slot batch
    lowers through the custom_vmap rule to the tenant-grid kernel."""
    step = _gs.vmappable_glr_step("all", False)
    text = _compile_text(jax.vmap(step),
                         *_glr_operands(one_chip, (64,), 16, 256))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_aggregate_compiles(one_chip, no_persistent_cache, dtype):
    fn = functools.partial(_wa.weighted_aggregate, interpret=False)
    text = _compile_text(fn, _sds(one_chip, (64, 1 << 20), dtype),
                         _sds(one_chip, (64,)))
    assert "tpu_custom_call" in text


def test_weighted_aggregate_vmapped_bf16_compiles(one_chip,
                                                  no_persistent_cache):
    """The seed-batched FL engine's form: a vmap over the bf16 kernel."""
    fn = jax.vmap(functools.partial(_wa.weighted_aggregate, interpret=False))
    text = _compile_text(fn, _sds(one_chip, (4, 64, 1 << 18), jnp.bfloat16),
                         _sds(one_chip, (4, 64)))
    assert "tpu_custom_call" in text


def test_robust_trimmed_compiles(one_chip, no_persistent_cache):
    fn = functools.partial(_ra.robust_trimmed, interpret=False)
    text = _compile_text(fn, _sds(one_chip, (64, 1 << 20)),
                         _sds(one_chip, (64,)), _sds(one_chip, ()),
                         _sds(one_chip, ()))
    assert "tpu_custom_call" in text


def test_glr_scan_compiles(one_chip, no_persistent_cache):
    fn = functools.partial(_glr.glr_scan, interpret=False)
    text = _compile_text(fn, _sds(one_chip, (16, 1024)),
                         _sds(one_chip, (16,), jnp.int32))
    assert "tpu_custom_call" in text


def _serve_step_text(monkeypatch, sharding, mesh=None):
    """Compile the serving loop's step (launcher defaults: 64 request rows,
    GLR-CUCB N=16 M=4 H=256) with the TPU dispatch the chip would take."""
    from repro.core.bandits import GLRCUCB
    from repro.sim.serve import init_slots, make_serve_step

    # code that asks which backend it runs on must see the chip's answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sched = GLRCUCB(16, 4, history=256, detector_stride=5, split_grid="auto")
    rows = -(-257 // 4) * 4 if mesh is not None else None
    state = jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, x.dtype),
        jax.eval_shape(lambda: init_slots(sched, 256, rows=rows)))
    rep = sharding if mesh is None else jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    b = 64
    args = (state, _sds(rep, (b,), jnp.int32), _sds(rep, (b, 16)),
            _sds(rep, (b, 2), jnp.uint32), _sds(rep, (b, 4)),
            _sds(rep, (b, 4)), _sds(rep, (b,), jnp.bool_),
            _sds(rep, (b,), jnp.bool_))
    out = jax.tree_util.tree_map(lambda s: s.sharding, state)
    return jax.jit(make_serve_step(sched, mesh=mesh), donate_argnums=(0,),
                   out_shardings=(out, None, None)).lower(
                       *args).compile().as_text()


def test_serve_step_compiles(one_chip, no_persistent_cache, monkeypatch):
    assert "tpu_custom_call" in _serve_step_text(monkeypatch, one_chip)


def test_sharded_serve_step_compiles_on_four_chips(topo, no_persistent_cache,
                                                   monkeypatch):
    """The sharded server's step over a 2x2 mesh: the compiler cannot
    partition a Pallas kernel, so the per-request math must sit inside
    ``shard_map`` while the slot state stays sharded."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(topo.devices), ("cases",))
    text = _serve_step_text(monkeypatch,
                            NamedSharding(mesh, PartitionSpec("cases")),
                            mesh=mesh)
    assert "tpu_custom_call" in text


def _run_plain_text(monkeypatch, sharding, gather):
    """Compile ``SparseAsyncFLTrainer.run``'s program (a linear classifier,
    4 of 4,096 clients a round, 2 rounds) for 64 uint8 examples of 784
    bytes a client, their int32 labels, and the gather ``gather``."""
    from repro.core.bandits import GLRCUCB
    from repro.core.channels import make_stationary
    from repro.fl import SparseAsyncFLTrainer, SparseFLConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(p, x, y):
        logits = (x.astype(jnp.float32) / 255.0) @ p["w"]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    tr = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=4096, n_sched=4, n_channels=6, batch_size=8,
                       local_epochs=2),
        GLRCUCB(6, 4, history=16), make_stationary(jnp.linspace(0.9, 0.4, 6)),
        loss)
    sds = functools.partial(jax.tree_util.tree_map,
                            lambda x: _sds(sharding, x.shape, x.dtype))
    params = {"w": jnp.zeros((784, 10))}
    state = sds(jax.eval_shape(tr.init, params, jax.random.PRNGKey(0)))
    return tr._run_plain.lower(
        tr, state, _sds(sharding, (4096, 64, 784), jnp.uint8),
        _sds(sharding, (4096, 64), jnp.int32),
        _sds(sharding, (2, 2), jnp.uint32), sds(tr.env),
        (gather, gather)).compile().as_text()


@pytest.mark.parametrize("gather,copies", [("jnp", 1), ("pallas", 0)])
def test_run_reads_client_minor_dataset_in_place(one_chip, no_persistent_cache,
                                                 monkeypatch, gather, copies):
    """A TPU stores the (N, 64, 784) uint8 dataset client-minor.  XLA's
    gather inside the round scan copies all of it to client-major in every
    call; the ``client_gather`` kernel reads it where it lies."""
    import re
    text = _run_plain_text(monkeypatch, one_chip, gather)
    assert re.search(r"u8\[4096,64,784\]\{0,2,1\S* parameter\(", text)
    dataset_copies = re.findall(r"= u8\[4096,64,784\]\S* copy\(", text)
    assert len(dataset_copies) == copies
    kernels = re.findall(r"%client_gather\S* = \S+ custom-call\(", text)
    assert len(kernels) == (2 if gather == "pallas" else 0)   # x and y


@pytest.mark.parametrize("shape,dtype", [
    ((4096, 64, 784), jnp.uint8),      # the population trainer's clients
    ((4096, 600, 784), jnp.uint8),     # McMahan's 600 examples a client
    ((4096, 600), jnp.int32),          # their labels
])
def test_client_gather_compiles(one_chip, no_persistent_cache, monkeypatch,
                                shape, dtype):
    """The gather kernel fits VMEM whatever the examples a client holds: it
    reads a client's rows in tiles of a fixed budget."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile_text(lambda x, i: ops.client_gather(x, i, "pallas"),
                         _sds(one_chip, shape, dtype),
                         _sds(one_chip, (20,), jnp.int32))
    assert "tpu_custom_call" in text
