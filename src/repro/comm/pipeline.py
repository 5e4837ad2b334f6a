"""Chunk-pipelined all-to-all with compute overlap (Pipeline MoE,
arXiv 2304.11414).

The MoE exchange is  a2a -> expert MLP -> a2a  on a wire tensor
[R, e_local, c, H] whose slot axis (c) is embarrassingly chunkable: the
expert MLP is per-token, so slots can be transferred and processed in K
independent chunks.  ``pipelined_moe_exchange`` software-pipelines them
with a ``lax.fori_loop`` whose carry double-buffers the in-flight chunk:
iteration k issues the dispatch a2a for chunk k AND the MLP + combine a2a
for chunk k-1 with no data dependence between the two, so the scheduler
can overlap chunk-k transfer with chunk-(k-1) compute.

The per-chunk transport is pluggable (``transfer=``): the planner passes
a ``comm.wire.coded_transfer`` when a quantized wire format is active, so
each chunk is sliced from the FLOAT tensor and encoded in transit — the
int8/fp8 payload and its scales sidecar are chunked in lockstep by
construction (quantization is per-slot, so encode commutes with slot
slicing and chunked results stay bit-identical to the unchunked path).

A chunk count that does not divide the slot extent RAISES here: the
planner validates divisibility at plan time (core/moe.py pads the slot
count so configured overlap_chunks divide) and degrades to flat with a
logged reason otherwise, so reaching this module with an indivisible
chunking is a planning bug, not a runtime condition to paper over.

``pipelined_all_to_all_bf16`` is the bare chunked transfer (no compute):
pure data movement through ``all_to_all_bf16`` per chunk, hence
bit-identical to the flat a2a in values and gradients — that is what the
parity suite pins down; the fused exchange then only adds the per-chunk
MLP, whose chunked partial sums in the weight gradient are allclose (not
bitwise) to the unchunked einsum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm.collectives import all_to_all_bf16
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope


def _slice(x, i, size, axis):
    return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis)


def _update(buf, val, i, size, axis):
    return jax.lax.dynamic_update_slice_in_dim(buf, val, i * size, axis)


def _check_divides(chunks: int, extent: int) -> None:
    if chunks > 1 and extent % chunks:
        raise ValueError(
            f"overlap_chunks={chunks} does not divide the slot extent "
            f"{extent}; the planner must validate this at plan time "
            f"(degrade to flat / pad the slot count) — see comm/planner.py")


def pipelined_all_to_all_bf16(x, axis_name: str, split: int, concat: int,
                              chunks: int, *, chunk_axis: int = 2,
                              transfer=None):
    """Flat a2a transferred in ``chunks`` slices of ``chunk_axis`` (which
    must differ from split/concat and divide evenly — indivisible chunk
    counts raise).  Bit-identical to ``all_to_all_bf16`` — each chunk is
    the same bf16-pinned primitive — but exposes K independent transfers
    the scheduler can interleave with neighbouring compute.

    ``transfer`` overrides the per-chunk leg (split/concat are then
    ignored): the tuner probes the coded int8/fp8 chunked transfer
    through here with ``comm.wire.transfer_fn``, so the timed leg is the
    production one.  The output dtype follows the transfer's (a codec
    decodes to its compute dtype)."""
    if transfer is None:
        def transfer(v):
            return all_to_all_bf16(v, axis_name, split, concat)
    elif chunk_axis in (split, concat):
        raise ValueError("transfer override requires chunk_axis "
                         "disjoint from split/concat")
    extent = x.shape[chunk_axis]
    _check_divides(chunks, extent)
    if chunks <= 1 or chunk_axis in (split, concat):
        return transfer(x)
    size = extent // chunks
    # chunk 0 outside the loop: its output dtype seeds the buffer
    first = transfer(_slice(x, 0, size, chunk_axis))
    out = _update(jnp.zeros(x.shape, first.dtype), first, 0, size,
                  chunk_axis)

    def body(i, acc):
        got = transfer(_slice(x, i, size, chunk_axis))
        return _update(acc, got, i, size, chunk_axis)

    return jax.lax.fori_loop(1, chunks, body, out)


def pipelined_moe_exchange(send, compute_fn, axis_name: str, chunks: int,
                           *, chunk_axis: int = 2, transfer=None):
    """dispatch a2a -> compute_fn -> combine a2a, pipelined over slot
    chunks.  send: [R, e_local, c, H] float; compute_fn maps a received
    chunk [R, e_local, c/K, H] to the same shape (per-token expert MLP).

    ``transfer`` is one planned a2a leg (defaults to the flat bf16-pinned
    a2a); with a wire codec active it encodes/decodes each chunk in
    transit (comm/wire.transfer_fn), so compute_fn always sees the
    decoded compute dtype.

    Stage-(k) transfer and stage-(k-1) compute share a loop iteration
    without depending on each other — the double buffer is the loop carry
    holding the chunk received last iteration."""
    if transfer is None:
        def transfer(v):
            return all_to_all_bf16(v, axis_name, 0, 0)

    def dispatch(v):
        with phase_scope(obs_tracing.PH_DISPATCH):
            return transfer(v)

    def finish(chunk):
        out = compute_fn(chunk)
        with phase_scope(obs_tracing.PH_COMBINE):
            return transfer(out)

    extent = send.shape[chunk_axis]
    _check_divides(chunks, extent)
    if chunks <= 1:
        return finish(dispatch(send))
    size = extent // chunks

    recv0 = dispatch(_slice(send, 0, size, chunk_axis))

    def body(i, carry):
        out, prev = carry
        nxt = dispatch(_slice(send, i, size, chunk_axis))  # transfer chunk i
        done = finish(prev)                                # compute chunk i-1
        return _update(out, done, i - 1, size, chunk_axis), nxt

    out, last = jax.lax.fori_loop(
        1, chunks, body, (jnp.zeros(send.shape, recv0.dtype), recv0))
    return _update(out, finish(last), chunks - 1, size, chunk_axis)
