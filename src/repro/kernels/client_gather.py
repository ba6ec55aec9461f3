"""Pallas TPU kernel: gather scheduled clients' rows from a client-minor dataset.

The population trainer keeps every client's examples resident as one
(N, n, d) uint8 array.  On a TPU the default layout of such an array puts
the client axis N most minor (on the lanes) whenever that pads least:
d = 784 would pad to 896 on the lanes (14%), N = 100,000 pads to 100,096
(0.1%).  An XLA gather of M rows along that axis inside a loop makes XLA
relayout the whole dataset to client-major first: a copy of every byte in
every call.

This kernel reads the stored bytes instead.  Its input is the view
``xt = transpose(x, (1, 2, 0))``, shape (n, d, N), whose row-major layout
is exactly the client-minor storage, so the view is a bitcast.  The
client's n rows are read tn at a time, tn the largest divisor of n whose
tiles fit a fixed VMEM budget (``BLOCK_BUDGET_BYTES``), so the VMEM taken
does not grow with n.  For each selected client m and each row tile it
DMAs the (tn, d, 128) tile of the 128-lane block holding that client from
HBM into VMEM, double-buffered across steps, rotates the client's lane to
lane m mod 128 and selects it into the resident output tile: lane-wise
moves of whole 32-bit words (uint8 rows are read four to a word), so the
bytes come out exactly.  The output is client-minor too,
(n, d, 128 * ceil(M / 128)), and XLA transposes its M live lanes to
(M, n, d): a relayout of the M rows alone.

The last block may reach past N: a TPU stores the view in (8, 128) tiles,
so its lanes are padded to a multiple of 128 in HBM, and the block holding
client N - 1 lies inside that padding; its lanes past N are read and never
kept.  In interpret mode the view is padded so, explicitly.

Inputs
  ids:  (M,) int32 client ids, scalar-prefetched
  xt:   (n, d, N) uint8 or int32 view of the dataset (``pl.ANY``: in HBM)
Output
  (M, n, d): row m is client ids[m]'s (n, d) examples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the VMEM the blocks may take: two input tiles and the double-buffered
# output tile, (tn, d, 128) each, and four rows' worth of the lane moves'
# temporaries.  tn, the rows of a client read in one DMA, is the largest
# divisor of n that fits, so the VMEM taken does not grow with the
# examples a client holds: all 64 rows (6.5 MB a tile) at n=64, d=784
# uint8; 75 at n=600
BLOCK_BUDGET_BYTES = 32 * 1024 * 1024
# the budget and Mosaic's own scratch, over the default scoped VMEM limit
# of 16 MiB
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _row_bytes(d: int, dtype) -> int:
    """VMEM bytes of one (d, 128) row of a tile, with the tiling's padding
    of d to (8 * 4 / itemsize) sublanes."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-d // sub) * sub * LANES * item


def _rows_per_tile(n: int, d: int, dtype) -> int:
    """The largest divisor of n whose four tiles, with four rows of
    temporaries, fit ``BLOCK_BUDGET_BYTES`` (0 where one row does not)."""
    fit = (BLOCK_BUDGET_BYTES // _row_bytes(d, dtype) - 4) // 4
    return max((t for t in range(1, min(n, fit) + 1) if n % t == 0),
               default=0)


def supports(shape, dtype) -> bool:
    """Whether the kernel reads an array of ``shape`` and ``dtype``: (N, n, d)
    or (N, n); int32, or uint8 with rows of a multiple of 4 bytes (the lane
    moves act on 32-bit words); a tile of one row of a client fits
    ``BLOCK_BUDGET_BYTES`` of VMEM (d up to 32,768 uint8, 8,192 int32)."""
    if len(shape) not in (2, 3) or dtype not in (jnp.uint8, jnp.int32):
        return False
    n, d = (1, shape[1]) if len(shape) == 2 else shape[1:]
    if dtype == jnp.uint8 and d % 4:
        return False
    return _rows_per_tile(n, d, dtype) > 0


def _step(s, n_sel, n_tiles):
    """Grid step s -> (lane group g, row tile r, lane j) of the client
    ``g * 128 + j``.  Steps run group by group, then tile by tile, then
    lane by lane, so one output tile stays in VMEM over its group's lanes.
    The last group holds the M mod 128 clients left (or 128)."""
    last = (n_sel - 1) // LANES
    in_full = s < last * n_tiles * LANES
    group = jnp.where(in_full, LANES, n_sel - last * LANES)
    rest = jnp.where(in_full, s % (n_tiles * LANES), s - last * n_tiles * LANES)
    g = jnp.where(in_full, s // (n_tiles * LANES), last)
    return g, rest // group, rest % group


def _gather_kernel(ids_ref, xt_ref, out_ref, buf, sem, *, n_sel, n_tiles,
                   tn):
    s = pl.program_id(0)

    def dma(slot, step, op):
        """Start or wait for the copy of step ``step``'s tile into ``slot``:
        rows r * tn .. of the 128-lane block holding its client."""
        g, r, j = _step(step, n_sel, n_tiles)
        cid = ids_ref[g * LANES + j]
        rows = pl.ds(pl.multiple_of(r * tn, tn), tn)
        base = pl.multiple_of((cid // LANES) * LANES, LANES)
        c = pltpu.make_async_copy(xt_ref.at[rows, :, pl.ds(base, LANES)],
                                  buf.at[slot], sem.at[slot])
        c.start() if op == "start" else c.wait()

    slot = s % 2

    @pl.when(s == 0)
    def _():
        dma(0, s, "start")

    @pl.when(s + 1 < pl.num_programs(0))
    def _():
        dma(1 - slot, s + 1, "start")

    dma(slot, s, "wait")
    g, _, to = _step(s, n_sel, n_tiles)
    cid = ids_ref[g * LANES + to]
    shift = (to - cid % LANES + LANES) % LANES
    # the lane moves act on 32-bit words: four uint8 rows share one
    if out_ref.dtype.itemsize < 4:
        word = functools.partial(pltpu.bitcast, ty=jnp.uint32)
        back = functools.partial(pltpu.bitcast, ty=out_ref.dtype)
    else:
        word = back = lambda v: v

    def row(r, carry):
        x = word(buf[slot, r])                               # (d', 128)
        hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == to
        out_ref[r] = back(jnp.where(hit, pltpu.roll(x, shift, 1),
                                    word(out_ref[r])))
        return carry

    jax.lax.fori_loop(0, tn, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def client_gather(client_x: jnp.ndarray, ids: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    """Rows ``ids`` of the (N, n, d) or (N, n) ``client_x``, read in place.

    Shapes and dtypes as ``supports`` says.
    """
    if not supports(client_x.shape, client_x.dtype):
        raise ValueError(f"client_gather: cannot read {client_x.dtype}"
                         f"{list(client_x.shape)}; see supports()")
    if client_x.ndim == 2:                        # labels: one row of n
        n_clients, n = client_x.shape
        return client_gather(client_x.reshape(n_clients, 1, n), ids,
                             interpret=interpret).reshape(ids.shape[0], n)
    n_clients, n, d = client_x.shape
    xt = jnp.transpose(client_x, (1, 2, 0))                  # (n, d, N)
    if interpret:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, -n_clients % LANES)))
    m = ids.shape[0]
    tn = _rows_per_tile(n, d, client_x.dtype)
    n_tiles = n // tn
    kernel = functools.partial(_gather_kernel, n_sel=m, n_tiles=n_tiles,
                               tn=tn)

    def out_tile(s, ids):
        g, r, _ = _step(s, m, n_tiles)
        return r, 0, g

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles * m,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, d, LANES), out_tile),
            scratch_shapes=[pltpu.VMEM((2, tn, d, LANES), client_x.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d, -(-m // LANES) * LANES),
                                       client_x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="client_gather",
    )(ids.astype(jnp.int32), xt)
    return jnp.transpose(out[:, :, :m], (2, 0, 1))


@functools.lru_cache(maxsize=None)
def vmappable_client_gather(interpret: bool):
    """``client_gather`` whose ``vmap`` over the ids alone (the seed axis of
    the batched trainer, the dataset shared) is one kernel call over every
    seed's ids, never a broadcast of the dataset."""

    @jax.custom_batching.custom_vmap
    def gather(client_x, ids):
        return client_gather(client_x, ids, interpret=interpret)

    @gather.def_vmap
    def _rule(axis_size, in_batched, client_x, ids):
        if in_batched[0]:
            raise NotImplementedError(
                "client_gather: a dataset per vmapped seed; the kernel reads "
                "one shared dataset")
        rows = gather(client_x, ids.reshape(-1))
        return rows.reshape(ids.shape + rows.shape[1:]), True

    return gather
