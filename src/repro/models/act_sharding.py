"""Activation sharding constraints (logical names, mesh-agnostic).

GSPMD propagates parameter shardings into activations greedily; with FSDP
(weights sharded over 'data' on the embed dim) it happily contracts over
the data-sharded dim and leaves the *batch* replicated — turning 2.5 GB of
per-device logits into 40 GB.  Pinning the batch axis at block boundaries
(the MaxText recipe) keeps the propagation honest.

``constrain(x, ...)`` is a no-op when no mesh is active (CPU unit tests)
or when an axis doesn't divide, so model code can sprinkle constraints
freely.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_LAYOUT_BATCH_AXES = {"tp": ("pod", "data"), "fsdp": ("data", "model")}
_BATCH_AXES = _LAYOUT_BATCH_AXES["tp"]
# 'seq' resolves to the tensor axis under TP (Megatron-style sequence
# parallelism for the residual stream between blocks: checkpointed scan
# carries shrink by the tensor-axis size); no tensor axis exists under FSDP.
_LAYOUT_SEQ_AXIS = {"tp": "model", "fsdp": None}
_SEQ_AXIS = _LAYOUT_SEQ_AXIS["tp"]


def set_layout(layout: str) -> None:
    """Select the activation layout ('tp' | 'fsdp') — see launch.shardings."""
    global _BATCH_AXES, _SEQ_AXIS
    _BATCH_AXES = _LAYOUT_BATCH_AXES[layout]
    _SEQ_AXIS = _LAYOUT_SEQ_AXIS[layout]


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh
    try:  # `with mesh:` (physical Mesh context) doesn't set the abstract mesh
        from jax._src.mesh import thread_resources
        phys = thread_resources.env.physical_mesh
        if not phys.empty:
            return phys
    except Exception:  # noqa: BLE001
        pass
    return None


def constrain(x, *logical: Optional[str]):
    """Apply with_sharding_constraint using logical names.

    logical entries: 'batch' (all data axes), 'model', 'data', or None.
    Silently skips when no mesh is active or a dim doesn't divide.
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        return x
    axis_sizes = dict(mesh.shape)
    spec, used = [], set()
    for dim, name in zip(x.shape, logical):
        if name == "seq":
            name = _SEQ_AXIS
            if name is None:
                spec.append(None)
                continue
        if name == "batch":
            axes = tuple(a for a in _BATCH_AXES if a in axis_sizes)
            total = 1
            for a in axes:
                total *= axis_sizes[a]
            if axes and dim % total == 0 and not used.intersection(axes):
                spec.append(axes if len(axes) > 1 else axes[0])
                used.update(axes)
            else:
                spec.append(None)
        elif name in axis_sizes and name not in used and dim % axis_sizes[name] == 0:
            spec.append(name)
            used.add(name)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
