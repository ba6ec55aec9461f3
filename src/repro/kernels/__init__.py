"""Pallas TPU kernels for the paper's compute hot-spots.

glr_step           fused streaming GLR detector step: carried prefix-sum
                   ring append + change-point test, no cumsum, no raw
                   history (Alg. 2 detector, the GLR-CUCB scan-body hot path)
glr_scan           GLR change-point statistic via full prefix recompute
                   (the legacy reference detector)
weighted_aggregate fused zeta-weighted masked client aggregation (Eq. 7)
client_gather      scheduled clients' rows read in place from a dataset
                   stored client-minor (the population trainer's gather)
flash_attention    blockwise GQA attention for prefill (dense/MoE/VLM archs)

Each kernel ships with a pure-jnp oracle in ref.py; ops.py holds the jit'd
public wrappers (interpret=True off-TPU).
"""
from repro.kernels import ops
