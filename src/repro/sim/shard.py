"""Device-sharded sweep buckets (`shard_map` over a 1-D mesh).

Sweep buckets are embarrassingly parallel: every batch entry of a
``simulate_aoi_regret_batch`` call is an independent (env, key, hp)
simulation.  This module splits the batch axis across a 1-D device mesh
with ``jax.shard_map`` — each device runs the same vmapped
scan over its slice of the bucket, with no cross-device communication at
all — so multi-chip hosts sweep D buckets' worth of Monte-Carlo cases in
the wall-clock of one.

Two properties make the path safe to keep on everywhere:

* **single-device identity** — on a 1-device mesh the local shard is the
  whole batch, so the shard-mapped program computes exactly the unsharded
  engine's vmap; results are bitwise identical (asserted in
  ``tests/test_shard.py``, which CI also runs under a forced 4-device CPU
  mesh).
* **pad-to-device-count** — batch sizes that don't divide the mesh are
  padded by cycling existing entries (``i % B`` gather); the duplicate
  rows compute real simulations whose results are sliced off again, so
  padding never fabricates inputs the policies haven't seen.

``sweep(..., shard=True)`` routes every regret bucket through here; the
direct API below serves homogeneous batches.  Scenario-process buckets
shard identically: the sweep driver realizes them to stacked canonical
``ChannelEnv``s *before* the shard_map dispatch, so the sharded program
never sees a scenario family — only the two canonical forms.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.regret import simulate_aoi_regret_impl

_AXIS = "cases"


def sweep_mesh(devices=None) -> Mesh:
    """A 1-D mesh over ``devices`` (default: all local devices), axis "cases"."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), (_AXIS,))


def batch_size(tree) -> int:
    """Leading-axis length shared by every leaf of a batched pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("batch_size: pytree has no array leaves")
    sizes = {int(jnp.shape(l)[0]) for l in leaves}
    if len(sizes) != 1:
        raise ValueError(f"batch_size: inconsistent leading axes {sorted(sizes)}")
    return sizes.pop()


def pad_batch(tree, multiple: int) -> Tuple[object, int]:
    """Pad every leaf's leading axis up to the next multiple of ``multiple``.

    Padding entries cycle the real ones (index ``i % B``), so they are valid
    simulation inputs; returns ``(padded_tree, original_batch)``.  A batch
    already divisible (including ``multiple=1``) is returned untouched.
    """
    b = batch_size(tree)
    bp = -(-b // multiple) * multiple
    if bp == b:
        return tree, b
    idx = jnp.arange(bp) % b
    return jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0), tree), b


def unpad_batch(tree, b: int):
    """Strip pad rows: slice every leaf's leading axis back to ``b``."""
    return jax.tree_util.tree_map(lambda x: x[:b], tree)


def shard_clients(tree, mesh: Optional[Mesh] = None):
    """Place (N,)-leading per-client arrays over the 1-D "cases" mesh.

    The sparse FL substrate's client axis (``repro.fl.sparse`` — (N,)
    scalars and (N, n, ...) datasets) is embarrassingly parallel outside
    top-k and the (M,) gathers, so a ``NamedSharding`` over the same mesh
    the sweep driver uses lets XLA partition the O(N) element-wise work
    across devices.  On a single device this is the identity placement —
    results are bitwise unchanged (asserted in ``tests/test_sparse_fl.py``).
    N must divide the device count; ``pad_batch`` the tree first if not.
    """
    mesh = sweep_mesh() if mesh is None else mesh
    sharding = jax.sharding.NamedSharding(mesh, P(_AXIS))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)


def shard_slots(tree, mesh: Optional[Mesh] = None):
    """Place tenant-slot arrays (leading axis = slot rows) over the mesh.

    The serving tier's ``TenantSlots`` leaves all lead with the slot axis
    (``rows``, mesh-divisible — ``SchedServer`` pads with extra scratch
    rows), and the serve step is gather / per-row compute / scatter on slot
    indices, so a ``NamedSharding`` over the same 1-D "cases" mesh splits
    the O(capacity) state residency across devices with no cross-device
    traffic beyond the (slots,) gathers (the per-row math itself runs
    replicated inside ``shard_map``: see ``make_serve_step``).  On a single
    device this is the identity placement — serving results are bitwise
    unchanged (asserted in ``tests/test_serve_scale.py``, which CI also
    runs under a forced 4-device CPU mesh).
    """
    mesh = sweep_mesh() if mesh is None else mesh
    sharding = jax.sharding.NamedSharding(mesh, P(_AXIS))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)


_FN_CACHE: dict = {}


def _sched_cache_key(scheduler, hp_axis):
    """Cache identity for a builder: when the traced scalars arrive through
    ``hparams`` (hp_axis set) the compiled program only depends on the
    scheduler's structure, so schedulers differing in traced values share
    one entry (``hp_signature``); with hp baked in (hp_axis None, hparams
    None) the values are trace constants and the full config is the key."""
    sig = getattr(scheduler, "hp_signature", None)
    if hp_axis is not None and sig is not None:
        return sig()
    return scheduler


def build_sharded(
    scheduler,
    horizon: int,
    collect_curve: bool,
    mesh: Mesh,
    env_axis: Optional[int] = 0,
    key_axis: Optional[int] = 0,
    hp_axis: Optional[int] = 0,
):
    """The unjitted shard-mapped bucket runner ``(envs, keys, hparams) -> out``.

    Axis-0 operands are split across the mesh ("cases"-sharded, leading axis
    must be divisible — see ``pad_batch``); ``None``-axis operands are
    replicated to every device.  Cached per (policy family, horizon, mesh,
    axes) — see ``_sched_cache_key`` — so repeated sweeps and grids with
    different traced values reuse one callable (and its jit cache entry).
    """
    cache_key = ("fn", _sched_cache_key(scheduler, hp_axis), horizon,
                 collect_curve, mesh, env_axis, key_axis, hp_axis)
    cached = _FN_CACHE.get(cache_key)
    if cached is not None:
        return cached

    def run(envs, keys, hparams):
        def one(env, key, hp):
            return simulate_aoi_regret_impl(
                scheduler, env, key, horizon, collect_curve, hp=hp)

        return jax.vmap(one, in_axes=(env_axis, key_axis, hp_axis))(
            envs, keys, hparams)

    spec = lambda axis: P(_AXIS) if axis == 0 else P()
    fn = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(spec(env_axis), spec(key_axis), spec(hp_axis)),
        out_specs=P(_AXIS),
        check_vma=False,
    )
    _FN_CACHE[cache_key] = fn
    return fn


def build_fl_sharded(trainer, mesh: Mesh):
    """The unjitted shard-mapped FL bucket runner
    ``(states, bx, by, keys, envs) -> (final_states, metrics)``.

    Every operand is "cases"-sharded on axis 0 (leading axes must divide the
    mesh — see ``pad_batch``); each device runs ``trainer._run_vmapped`` —
    the exact program the unsharded engine executes — over its slice, so a
    1-device mesh is bitwise identical to ``simulate_fl_batch``.  Cached per
    (trainer ``bucket_signature``, mesh): equal-signature trainers share one
    callable and its jit cache entry.
    """
    sig_fn = getattr(trainer, "bucket_signature", None)
    tr_sig = sig_fn() if sig_fn is not None else trainer
    cache_key = ("fl_fn", tr_sig, mesh)
    cached = _FN_CACHE.get(cache_key)
    if cached is not None:
        return cached

    def run(states, bx, by, keys, envs):
        return trainer._run_vmapped(states, bx, by, keys, envs=envs,
                                    env_axis=0)

    fn = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS)),
        out_specs=P(_AXIS),
        check_vma=False,
    )
    _FN_CACHE[cache_key] = fn
    return fn


def _jitted_sharded(scheduler, horizon, collect_curve, mesh, env_axis, key_axis, hp_axis):
    cache_key = ("jit", _sched_cache_key(scheduler, hp_axis), horizon,
                 collect_curve, mesh, env_axis, key_axis, hp_axis)
    cached = _FN_CACHE.get(cache_key)
    if cached is None:
        cached = jax.jit(build_sharded(
            scheduler, horizon, collect_curve, mesh,
            env_axis, key_axis, hp_axis))
        _FN_CACHE[cache_key] = cached
    return cached


def sharded_aoi_regret_batch(
    scheduler,
    envs,
    keys,
    horizon: int,
    collect_curve: bool = True,
    env_axis: Optional[int] = 0,
    key_axis: Optional[int] = 0,
    hparams=None,
    hp_axis: Optional[int] = None,
    mesh: Optional[Mesh] = None,
):
    """``simulate_aoi_regret_batch`` with the batch axis sharded over a mesh.

    Same signature and results as the unsharded engine (bitwise identical on
    a single device); mapped operands are padded to the device count and the
    pad rows sliced off the result.  ``mesh=None`` uses all local devices.
    """
    if env_axis is None and key_axis is None and hp_axis is None:
        raise ValueError("sharded_aoi_regret_batch: nothing to batch over "
                         "(env_axis, key_axis and hp_axis are all None)")
    mesh = sweep_mesh() if mesh is None else mesh
    d = int(mesh.devices.size)

    mapped = [x for x, a in ((envs, env_axis), (keys, key_axis),
                             (hparams, hp_axis)) if a == 0]
    b = batch_size(mapped)

    def pad(x):  # a leaf-less mapped operand ({} hparams) needs no padding
        return pad_batch(x, d)[0] if jax.tree_util.tree_leaves(x) else x

    if env_axis == 0:
        envs = pad(envs)
    if key_axis == 0:
        keys = pad(keys)
    if hp_axis == 0:
        hparams = pad(hparams)

    fn = _jitted_sharded(
        scheduler, horizon, collect_curve, mesh, env_axis, key_axis, hp_axis)
    out = fn(envs, keys, hparams)
    return unpad_batch(out, b) if (-b) % d else out
