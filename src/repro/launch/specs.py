"""Assigned input shapes + ShapeDtypeStruct stand-ins for the dry-run.

The four shapes (the assignment matrix's columns):

    train_4k      seq=4096    global_batch=256   train_step
    prefill_32k   seq=32768   global_batch=32    prefill (forward, last-token
                                                 logits — encoder forward for
                                                 hubert)
    decode_32k    seq=32768   global_batch=128   serve_step (1 token, full KV)
    long_500k     seq=524288  global_batch=1     serve_step (1 token; ring /
                                                 recurrent state — the
                                                 sub-quadratic requirement)

``input_specs`` returns sharded ShapeDtypeStructs only — no allocation.
Full-attention archs serve long_500k through the sliding-window ring cache
(window 4096), our first-class long-context serve option; hubert-xlarge is
encoder-only and skips both decode shapes (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.shardings import batch_pspec, logical_to_pspec
from repro.models.model import Model

LONG_CTX_WINDOW = 4096  # ring-cache window for full-attention archs @ 500k


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def supported(cfg: ModelConfig, shape_name: str) -> Tuple[bool, str]:
    shape = SHAPES[shape_name]
    if shape.mode == "decode" and cfg.is_encoder:
        return False, "encoder-only: no autoregressive decode step"
    return True, ""


def serve_window(cfg: ModelConfig, shape_name: str) -> int:
    """Ring window used for this (arch, shape): 0 = full cache."""
    if shape_name != "long_500k":
        return 0
    if cfg.arch_type in ("ssm",):
        return 0                       # no attention cache at all
    if cfg.local_attn_window:
        return 0                       # hybrid: its own local window applies
    return LONG_CTX_WINDOW             # dense/MoE/VLM: sliding-window serve


def _sds(shape, dtype, mesh: Mesh, pspec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))


def batch_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                layout: str = "tp") -> Dict[str, Any]:
    """ShapeDtypeStructs for one forward/train batch."""
    bp = batch_pspec(mesh, layout)
    b, s = shape.global_batch, shape.seq_len
    bspec = bp if b % _data_size(mesh) == 0 else P()
    if cfg.arch_type == "audio":
        frame_tail = (None, "model") if layout == "tp" else (None, None)
        return {
            "frames": _sds((b, s, cfg.d_model), jnp.bfloat16, mesh, bspec + frame_tail),
            "labels": _sds((b, s), jnp.int32, mesh, bspec + (None,)),
            "mask": _sds((b, s), jnp.bool_, mesh, bspec + (None,)),
        }
    out = {"tokens": _sds((b, s), jnp.int32, mesh, bspec + (None,))}
    if cfg.arch_type == "vlm":
        out["vision_embeds"] = _sds(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16,
            mesh, bspec + (None, None))
    return out


def _data_size(mesh: Mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------

_CACHE_RULES = {
    # key-name -> logical axes per rank (batch axis resolved separately)
    "k": {5: (None, "batch", None, "seq", None), 4: ("batch", None, "seq", None)},
    "v": {5: (None, "batch", None, "seq", None), 4: ("batch", None, "seq", None)},
    "latent": {4: (None, "batch", "seq", None), 3: ("batch", "seq", None)},
    "k_rope": {4: (None, "batch", "seq", None), 3: ("batch", "seq", None)},
    "ssm_state": {5: (None, "batch", "model_dim", None, None), 4: ("batch", "model_dim", None, None)},
    "conv_x": {4: (None, "batch", None, "model_dim"), 3: ("batch", None, "model_dim")},
    "conv_b": {4: (None, "batch", None, None), 3: ("batch", None, None)},
    "conv_c": {4: (None, "batch", None, None), 3: ("batch", None, None)},
    "conv": {4: (None, "batch", None, "model_dim"), 3: ("batch", None, "model_dim")},
    "h": {3: (None, "batch", "model_dim"), 2: ("batch", "model_dim")},
    "pos": {0: ()},
}

_LOGICAL_CACHE = {"seq": "model", "model_dim": "model"}


def cache_pspec(key: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Sharding for one cache entry.  KV sequence -> 'model' (distributed
    flash-decode); recurrent state channels -> 'model'; batch -> data axes;
    any non-dividing axis degrades to replication."""
    base = key.split("/")[-1]
    logical = _CACHE_RULES.get(base, {}).get(len(shape))
    if logical is None:
        return P()
    bp = batch_pspec(mesh)
    out, used = [], set()
    for dim, name in zip(shape, logical):
        if name == "batch":
            axes = bp[0] if isinstance(bp[0], tuple) else (bp[0],)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % total == 0 and not used.intersection(axes):
                out.append(bp[0])
                used.update(axes)
            else:
                out.append(None)
        elif name in _LOGICAL_CACHE:
            axis = _LOGICAL_CACHE[name]
            if axis not in used and dim % mesh.shape[axis] == 0:
                out.append(axis)
                used.add(axis)
            else:
                out.append(None)
        else:
            out.append(None)
    return P(*out)


def cache_specs(model: Model, shape: ShapeSpec, mesh: Mesh) -> Dict[str, Any]:
    """ShapeDtypeStructs (sharded) for the serve cache at this shape."""
    window = serve_window(model.cfg, shape.name)
    shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, window=window))

    def attach(path_key: str, sds):
        ps = cache_pspec(path_key, sds.shape, mesh)
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=NamedSharding(mesh, ps))

    out: Dict[str, Any] = {}
    for k, v in shapes.items():
        if isinstance(v, dict):
            out[k] = {kk: attach(kk, vv) for kk, vv in v.items()}
        else:
            out[k] = attach(k, v)
    return out


def decode_token_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh):
    bp = batch_pspec(mesh)
    b = shape.global_batch
    bspec = bp if b % _data_size(mesh) == 0 else P()
    return _sds((b,), jnp.int32, mesh, bspec)
