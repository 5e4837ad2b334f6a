"""Pallas TPU kernels: dispatch-buffer scatter and its transpose gather,
as whole rows moved by index.

``dispatch_scatter`` builds the [E, C, H] expert dispatch buffer from the
flattened routed entries; ``combine_gather`` reads each (token, choice)'s
row back out of an [E, C, H] result buffer and applies its combine
weight.  The two are mutual transposes, which is what lets each serve as
the other's backward pass in kernels/dispatch.py — exactly how
``segment_centroid`` / ``residual_apply`` pair up for the LSH path.

Neither contracts anything: entry f's buffer row is ``id_f·C + pos_f``
of the flattened [E·C, H] buffer, and the kernels copy rows by DMA, so
their work is linear in the F entries (a one-hot MXU contraction would
be E·C·F·H).  The row numbers ride in SMEM as scalar-prefetch operands;
the large operand stays in HBM (``memory_space=pl.ANY``) and each row is
fetched with its own async copy into a VMEM landing slot:

  combine_gather    grid over tiles of entries.  Tile i+1's copies are
                    in flight while tile i is written (two landing
                    slots), and out[f] = w[f]·row.  An out-of-range
                    entry is never fetched and writes zero by control
                    flow, not by a multiply: 0·inf or 0·NaN of a stale
                    landing slot would be NaN.
  dispatch_scatter  entries are put in (row, entry) order by one stable
                    sort of F int32 keys.  The grid runs over [tile_c, H]
                    output blocks (``capacity_tile``); each owns one
                    contiguous run of the sorted entries, whose source
                    rows are fetched in double-buffered chunks and added
                    into the zeroed f32 block in entry order.  Duplicate
                    (id, pos) pairs therefore still sum, in the order the
                    one-hot contraction of the fused codec twin
                    (kernels/fused_wire.py) adds them; plans from
                    ``positions_in_expert`` never collide, so there every
                    add is an exact copy.  Rows no entry targets stay zero.

Alignment: Mosaic copies whole (8, 128) tiles of a tiled HBM array, so a
single row cannot be a DMA.  The operand is viewed as [rows/sub, sub, H]
— sub is 8 rows for 32-bit dtypes and 16 for bf16, whose tiles pack two
rows a sublane; the view is a free bitcast when sub divides C (gather)
or F (scatter) — the aligned sub-row block holding the row is copied,
and the row is picked out of VMEM: sub rows read per row used, still
linear in F.

Overflow-bin contract (shared with every registry op): an entry whose
expert id falls outside [0, E) or whose position falls outside [0, C)
contributes nothing to the scatter and gathers exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# A whole [C, H] f32 expert block at C=2048, H=1536 is 12 MiB, 24 MiB
# double-buffered: past v5e's 16 MiB default scoped VMEM.  Blocks over
# _BLOCK_BYTES therefore split the capacity axis into row tiles that
# divide C (multiples of 32 rows, the int8 sublane tile, so the fused
# kernels' quantized blocks stay aligned too).  At C=2048, H=1536 that is
# 512-row (3 MiB) blocks.
_BLOCK_BYTES = 4 << 20

# One landing slot holds a tile of fetched sub-row blocks: 1.5 MiB at
# H=1536 (32 entries of 48 KiB), two slots beside the scatter's
# double-buffered 3 MiB output block.
_LANDING_BYTES = 2 << 20


def capacity_tile(capacity: int, hidden: int) -> int:
    """Capacity rows per kernel block: all of C when the f32 [C, H] block
    fits _BLOCK_BYTES, else the largest 32-row multiple dividing C that
    does (all of C when none does)."""
    if capacity * hidden * 4 <= _BLOCK_BYTES:
        return capacity
    fits = [r for r in range(32, capacity, 32)
            if capacity % r == 0 and r * hidden * 4 <= _BLOCK_BYTES]
    return fits[-1] if fits else capacity


def _sublanes(dtype) -> int:
    """Rows of one (8, 128) HBM tile of ``dtype``: 8 for 32-bit, 16 for
    16-bit dtypes (two rows packed per sublane)."""
    return 32 // jnp.dtype(dtype).itemsize


def _entry_tile(sub: int, hidden: int, dtype, tile_t: int) -> int:
    """Entries per landing slot: the largest power of two from 8 up to
    ``tile_t`` whose [n, sub, H] slot fits _LANDING_BYTES."""
    per = sub * hidden * jnp.dtype(dtype).itemsize
    n = 8
    while 2 * n <= tile_t and 2 * n * per <= _LANDING_BYTES:
        n *= 2
    return n


def _row_blocks(x: jax.Array, sub: int) -> jax.Array:
    """[R, H] -> [ceil(R/sub), sub, H], the DMA-able view (padded where
    sub does not divide R: small shapes only)."""
    pad = (-x.shape[0]) % sub
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x.reshape(-1, sub, x.shape[1])


def _landing(n: int, sub: int, hidden: int, dtype):
    """Scratch: two landing slots, their DMA semaphores, and the f32
    block ``_pick_row`` widens 16-bit rows through."""
    return [pltpu.VMEM((2, n, sub, hidden), dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((8, hidden), jnp.float32)]


def _pick_row(block, r, sub, wide):
    """Row ``r`` of a landed [sub, H] block, as [1, H] f32.  Mosaic loads
    one 32-bit row at a dynamic sublane offset, but packed 16-bit rows
    only in 8-row groups: those are widened into ``wide`` first."""
    if sub == 8:
        return block[pl.ds(r, 1), :].astype(jnp.float32)
    wide[...] = block[pl.ds(pl.multiple_of(r // 8 * 8, 8), 8), :].astype(
        jnp.float32)
    return wide[pl.ds(r % 8, 1), :]


def _buffer_rows(expert_ids, pos, num_experts, capacity):
    """Entry -> row id·C + pos of the flattened [E·C, H] buffer; -1 for
    out-of-range entries (the overflow bin)."""
    ids = expert_ids.astype(jnp.int32)
    p = pos.astype(jnp.int32)
    ok = (ids >= 0) & (ids < num_experts) & (p >= 0) & (p < capacity)
    return jnp.where(ok, ids * capacity + p, -1)


def _scatter_kernel(bounds_ref, order_ref, rows_ref, src_hbm, out_ref,
                    land, sem, wide, *, n, sub, tile_c, n_c):
    # this output block holds buffer rows b·tile_c + [0, tile_c)
    b = pl.program_id(0) * n_c + pl.program_id(1)
    lo = bounds_ref[b]
    hi = bounds_ref[b + 1]
    chunks = (hi - lo + n - 1) // n
    out_ref[...] = jnp.zeros_like(out_ref)

    def each(k, act):
        """act(j, idx) for chunk k's sorted entries idx, j its slot."""
        first = lo + k * n

        def one(j, c):
            act(j, first + j)
            return c
        jax.lax.fori_loop(0, jnp.minimum(n, hi - first), one, 0)

    def copies(k, slot, act):
        each(k, lambda j, idx: act(pltpu.make_async_copy(
            src_hbm.at[order_ref[idx] // sub], land.at[slot, j],
            sem.at[slot])))

    pl.when(chunks > 0)(lambda: copies(0, 0, lambda c: c.start()))

    def chunk(k, carry):
        slot = k % 2
        pl.when(k + 1 < chunks)(
            lambda: copies(k + 1, 1 - slot, lambda c: c.start()))
        copies(k, slot, lambda c: c.wait())

        def add(j, idx):
            t = rows_ref[idx] - b * tile_c
            out_ref[0, pl.ds(t, 1), :] += _pick_row(
                land.at[slot, j], order_ref[idx] % sub, sub, wide)
        each(k, add)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=("num_experts", "capacity",
                                             "tile_t", "interpret"))
def dispatch_scatter_pallas(expert_ids: jax.Array, pos: jax.Array,
                            src: jax.Array, *, num_experts: int,
                            capacity: int, tile_t: int = 128,
                            interpret: bool) -> jax.Array:
    """expert_ids/pos: [F] int32; src: [F, H].  Returns [E, C, H] f32 with
    buf[e, c] = Σ_{f: id_f == e, pos_f == c} src[f], summed in entry
    order; out-of-range entries contribute nothing (overflow bin)."""
    F, H = src.shape
    R = num_experts * capacity
    rows = _buffer_rows(expert_ids, pos, num_experts, capacity)
    # out-of-range entries sort past every block's run
    rows, order = jax.lax.sort(
        (jnp.where(rows < 0, R, rows), jnp.arange(F, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    tile_c = capacity_tile(capacity, H)
    n_c = capacity // tile_c
    edges = jnp.arange(num_experts * n_c + 1, dtype=jnp.int32) * tile_c
    bounds = jnp.searchsorted(rows, edges,
                              method="compare_all").astype(jnp.int32)
    sub = _sublanes(src.dtype)
    n = _entry_tile(sub, H, src.dtype, tile_t)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, n=n, sub=sub, tile_c=tile_c,
                          n_c=n_c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(num_experts, n_c),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tile_c, H),
                                   lambda e, c, *_: (e, c, 0)),
            scratch_shapes=_landing(n, sub, H, src.dtype)),
        out_shape=jax.ShapeDtypeStruct((num_experts, capacity, H),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="dispatch_scatter_pallas",
        interpret=interpret,
    )(bounds, order, rows, _row_blocks(src, sub))


def _gather_kernel(rows_ref, w_ref, buf_hbm, out_ref, land, sem, wide, *,
                   n, sub):
    i = pl.program_id(0)
    slot = i % 2

    def each(step, act):
        """act(j, r) for tile ``step``'s in-range entries, r their row."""
        def one(j, c):
            r = rows_ref[step * n + j]
            pl.when(r >= 0)(lambda: act(j, r))
            return c
        jax.lax.fori_loop(0, n, one, 0)

    def copies(step, s, act):
        each(step, lambda j, r: act(pltpu.make_async_copy(
            buf_hbm.at[r // sub], land.at[s, j], sem.at[s])))

    pl.when(i == 0)(lambda: copies(0, 0, lambda c: c.start()))
    pl.when(i + 1 < pl.num_programs(0))(
        lambda: copies(i + 1, 1 - slot, lambda c: c.start()))
    copies(i, slot, lambda c: c.wait())
    out_ref[...] = jnp.zeros_like(out_ref)

    def row(j, r):
        out_ref[pl.ds(j, 1), :] = _pick_row(
            land.at[slot, j], r % sub, sub, wide) * w_ref[i * n + j]
    each(i, row)


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def combine_gather_pallas(expert_ids: jax.Array, pos: jax.Array,
                          buf: jax.Array, weights: jax.Array, *,
                          tile_t: int = 128,
                          interpret: bool) -> jax.Array:
    """expert_ids/pos: [F] int32; buf: [E, C, H]; weights: [F].
    Returns [F, H] f32 = weights[f] * buf[id_f, pos_f]; out-of-range
    entries gather zero (overflow bin)."""
    E, C, H = buf.shape
    F = expert_ids.shape[0]
    sub = _sublanes(buf.dtype)
    n = _entry_tile(sub, H, buf.dtype, tile_t)
    pad_f = (-F) % n
    rows = jnp.pad(_buffer_rows(expert_ids, pos, E, C), (0, pad_f),
                   constant_values=-1)
    w = jnp.pad(weights.astype(jnp.float32), (0, pad_f))
    Fp = F + pad_f
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n=n, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Fp // n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n, H), lambda i, *_: (i, 0)),
            scratch_shapes=_landing(n, sub, H, buf.dtype)),
        out_shape=jax.ShapeDtypeStruct((Fp, H), jnp.float32),
        # tile i starts tile i+1's copies: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="combine_gather_pallas",
        interpret=interpret,
    )(rows, w, _row_blocks(buf.reshape(E * C, H), sub))
    return out[:F]
