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
