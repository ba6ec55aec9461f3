"""Federated data pipeline: per-client mini-batch streams.

Each client draws mini-batches from its own (non-IID) shard.  The loader
yields stacked ``(M, batch, ...)`` arrays so one FL round — including the
E local SGD epochs of every participating client — is a single jitted,
vmapped step.

For multi-seed Monte-Carlo FL (``repro.sim.simulate_fl_batch``),
``BatchedFederatedLoader`` runs B per-seed RNG streams in lockstep and
stacks their draws on a leading (B,) axis — slice b is bit-identical to
what a serial ``FederatedLoader(seed=seeds[b])`` would have produced, so
the vmapped and serial training paths see the same data.

The host-side loaders above precompute ``(R, M, ...)`` round data — fine
at M = tens of clients, impossible at the sparse substrate's N = 1e5+.
``client_batch_indices`` / ``gather_client_batches`` are the jittable
replacement: the full client datasets stay device-resident as (N, n, ...)
operands, and each round draws mini-batch *indices* only for the M
scheduled clients, keyed by ``fold_in(key, client_id)`` — a pure function
of (round key, client id), so the same client scheduled by any subset, at
any slot, sees the same batches (the dense-vs-sparse parity anchor of
``repro.fl.sparse``).
"""
from __future__ import annotations

from typing import Iterator, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import client_gather as gather_kernel
from repro.kernels import ops


def client_batch_indices(
    key: jax.Array,
    client_ids: jnp.ndarray,       # (M,) int32 — the scheduled clients
    n_examples: int,
    local_epochs: int,
    batch_size: int,
) -> jnp.ndarray:
    """Per-client mini-batch indices, (M, E, B) int32 in [0, n_examples).

    Client ``i``'s draw depends only on ``fold_in(key, i)`` — not on which
    other clients were scheduled or where ``i`` sits in ``client_ids`` — so
    a sparse M-client gather and a dense all-N precomputation produce
    bit-identical batches for every shared client.
    """

    def one(cid):
        return jax.random.randint(
            jax.random.fold_in(key, cid),
            (local_epochs, batch_size), 0, n_examples)

    return jax.vmap(one)(client_ids)


def gather_backend(data) -> str:
    """The ``repro.kernels.ops.client_gather`` backend that reads ``data``
    (an (N, n, d) dataset or (N, n) labels) without copying it.

    ``"pallas"`` where ``data`` lies on one TPU, stored client-minor: its
    layout's ``major_to_minor`` ends in the client axis 0 with the other
    axes in order, so that the transposed view (n, d, N) the kernel reads
    is the stored bytes.  A TPU lays out the population trainer's
    u8[100000, 64, 784] so (784 would pad to 896 on the lanes).  What
    shapes and dtypes the kernel reads, it says itself
    (``repro.kernels.client_gather.supports``).
    ``"jnp"`` everywhere else: another layout, a dataset sharded over
    devices, a CPU, a tracer, whose layout is not known, or an array the
    kernel does not read.
    """
    if (isinstance(data, jax.core.Tracer) or not isinstance(data, jax.Array)
            or not gather_kernel.supports(data.shape, data.dtype)
            or len(data.sharding.device_set) != 1
            or next(iter(data.sharding.device_set)).platform != "tpu"):
        return "jnp"
    client_minor = tuple(range(1, data.ndim)) + (0,)
    return ("pallas" if data.format.layout.major_to_minor == client_minor
            else "jnp")


def gather_backends(client_x, client_y) -> Tuple[str, str]:
    """``gather_backend`` of the datasets and of the labels: the static
    ``backends`` argument of ``gather_client_batches``."""
    return gather_backend(client_x), gather_backend(client_y)


def gather_client_batches(
    client_x: jnp.ndarray,         # (N, n, ...) device-resident datasets
    client_y: jnp.ndarray,         # (N, n)
    client_ids: jnp.ndarray,       # (M,) int32
    idx: jnp.ndarray,              # (M, E, B) from client_batch_indices
    backends: Tuple[str, str] = ("jnp", "jnp"),
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather ``(x (M, E, B, ...), y (M, E, B))`` for the scheduled clients.

    Only the M scheduled rows of the (N, n, ...) datasets are touched — the
    sparse substrate's per-round data cost is O(M · E · B), independent of
    the total client count N.

    Layout contract: ``backends`` names how the M rows of ``client_x`` and
    of ``client_y`` are read (``repro.kernels.ops.client_gather``), and the
    bytes returned are the same either way.  ``"jnp"`` is an XLA gather:
    on an array stored client-major it reads the rows in place, but on one
    stored client-minor, inside a loop, XLA first relayouts the whole array
    to client-major, a copy of every byte in every call.  ``"pallas"`` (or
    ``"pallas_interpret"``) reads the rows of a client-minor array in place
    with the ``client_gather`` kernel.  ``gather_backends`` picks them from
    the arrays' observed layout and placement; inside ``jit`` only the
    caller that held the concrete arrays can, so it passes them in.  The
    per-example gather then runs on the small (M, n, ...) rows.
    """

    def one(xi, yi, ix):
        return jnp.take(xi, ix, axis=0), jnp.take(yi, ix, axis=0)

    return jax.vmap(one)(
        ops.client_gather(client_x, client_ids, backends[0]),
        ops.client_gather(client_y, client_ids, backends[1]),
        idx)


class FederatedLoader:
    def __init__(
        self,
        client_x: np.ndarray,       # (M, n, ...)
        client_y: np.ndarray,       # (M, n)
        batch_size: int,
        local_epochs: int = 1,
        seed: int = 0,
    ):
        self.cx = client_x
        self.cy = client_y
        self.batch = batch_size
        self.e = local_epochs
        self.rng = np.random.default_rng(seed)
        self.m, self.n = client_y.shape

    def next_round(self) -> Tuple[np.ndarray, np.ndarray]:
        """(x (M, E, B, ...), y (M, E, B)) — E local steps per client."""
        idx = self.rng.integers(0, self.n, size=(self.m, self.e, self.batch))
        gather = np.arange(self.m)[:, None, None]
        return self.cx[gather, idx], self.cy[gather, idx]

    def next_rounds(self, r: int) -> Tuple[np.ndarray, np.ndarray]:
        """(x (R, M, E, B, ...), y (R, M, E, B)) — R rounds stacked for the
        scan-fused ``AsyncFLTrainer.run`` (same draws as R ``next_round``s)."""
        idx = self.rng.integers(0, self.n, size=(r, self.m, self.e, self.batch))
        gather = np.arange(self.m)[None, :, None, None]
        return self.cx[gather, idx], self.cy[gather, idx]

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        while True:
            yield self.next_round()


class BatchedFederatedLoader:
    """B per-seed ``FederatedLoader`` streams advancing in lockstep.

    The input format of the batched FL engine: ``next_rounds(r)`` returns
    ``(x (B, R, M, E, Bsz, ...), y (B, R, M, E, Bsz))`` where slice ``b``
    reproduces the *identical* RNG stream as a standalone
    ``FederatedLoader(..., seed=seeds[b])`` drawing ``r`` rounds — the
    guarantee that makes the vmapped ``simulate_fl_batch`` path
    deterministic with respect to the per-seed serial baseline (guarded by
    a regression test in ``tests/test_fl_round.py``).
    """

    def __init__(
        self,
        client_x: np.ndarray,       # (M, n, ...)
        client_y: np.ndarray,       # (M, n)
        batch_size: int,
        local_epochs: int = 1,
        seeds: Sequence[int] = (0,),
    ):
        self.loaders = [
            FederatedLoader(client_x, client_y, batch_size, local_epochs, seed=s)
            for s in seeds
        ]
        self.seeds = tuple(seeds)

    @property
    def n_seeds(self) -> int:
        return len(self.loaders)

    def next_round(self) -> Tuple[np.ndarray, np.ndarray]:
        """(x (B, M, E, Bsz, ...), y (B, M, E, Bsz)) — one round per seed."""
        xs, ys = zip(*(ld.next_round() for ld in self.loaders))
        return np.stack(xs), np.stack(ys)

    def next_rounds(self, r: int) -> Tuple[np.ndarray, np.ndarray]:
        """(x (B, R, M, E, Bsz, ...), y (B, R, M, E, Bsz)) — R rounds per seed."""
        xs, ys = zip(*(ld.next_rounds(r) for ld in self.loaders))
        return np.stack(xs), np.stack(ys)
