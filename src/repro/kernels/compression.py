"""Compressed client-update Pallas kernels (the delta codec plane).

Client updates dominate cross-device FL traffic, and the selection
metric the paper optimizes is only meaningful if aggregation cost
models that traffic. These kernels implement the two codecs of the
compressed update plane (fl.compression):

- ``topk_sparsify`` — per-row magnitude top-k with index+value packing:
  each flattened client delta keeps its k largest-|x| entries (signed
  values + lane indices). The selection is ``segmented_topk``'s
  threshold select (``row_topk``: 32 counting passes find the k-th
  largest ``|x|``, one more gathers and orders the survivors) over
  ``|x|``; ties break to the lowest lane, matching ``jax.lax.top_k``
  over ``|x|``.

- ``quantize_i8`` / ``dequantize_i8`` — per-chunk symmetric int8: each
  ``chunk``-wide slice of a row is scaled by ``amax/127`` (f32 scales,
  one per chunk) and rounded to int8. Blocks are 8 rows by
  ``128 * chunk`` lanes, so each block's scales form one lane-dense
  ``(8, 128)`` tile; the wrapper zero-pads rows and the parameter axis
  to whole blocks (padding quantizes to 0 and is sliced off), so no
  in-kernel tail masking is needed.

- ``fedavg_agg_quality_i8`` — the fused *compressed* sibling of
  ``fedavg_agg_quality``: one pass over the quantized payloads
  dequantizes each block into VMEM and emits the weighted aggregate Δ_t
  plus all per-client Gram terms of the quality cosine — the server
  never materializes the dequantized (K, P) matrix in HBM.

Like every kernel in this package, each has a jnp oracle in ``ref.py``
and is called through the dispatching wrappers in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .segmented_topk import _BLOCK, _ROWS, pad_rows, row_topk

_TILE_CHUNKS = 128   # chunks per lane tile: one lane-dense (8, 128) scale tile


def _first_block(i):
    """Block (0, 0) at every grid step. Block indices are int32: a
    Python 0 lowers to i64 under jax_enable_x64, which Mosaic rejects."""
    return jnp.int32(0), jnp.int32(0)


def _column_block(i):
    """Block (0, i): the i-th parameter block of every row."""
    return jnp.int32(0), i


def _chunk_tiles(rows: int, p: int, chunk: int):
    """Tiling of a (rows, p) payload into (8, tc * chunk) lane tiles.

    Returns ``(rp, nc, tc, nt)``: padded row count, chunks per row,
    chunks per tile and tiles per row. One tile spans the whole row
    when it has at most ``_TILE_CHUNKS`` chunks (full-array blocks are
    always legal); longer rows are zero-padded to whole tiles.
    """
    rp = -(-rows // _ROWS) * _ROWS
    nc = -(-p // chunk)
    tc = nc if nc <= _TILE_CHUNKS else _TILE_CHUNKS
    return rp, nc, tc, -(-nc // tc)


def _pad2(x, rows: int, cols: int):
    r, c = x.shape
    if (r, c) == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


# ---------------------------------------------------------------------------
# Magnitude top-k sparsification
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_sparsify(x, k: int, *, block: int = _BLOCK,
                  interpret: bool = False):
    """x: (K, P) flattened client deltas -> ``(values (K, k) f32,
    indices (K, k) int32)``: each row's k largest-magnitude entries
    (signed values), ordered by descending |value|, ties to the lowest
    lane — a stable descending sort of |x|, which is what
    ``jax.lax.top_k(|x|, k)`` returns on the CPU. (On a v5e,
    ``lax.top_k`` over ~1M lanes returns the same set in another order.)
    """
    K, _ = x.shape
    xf = x.astype(jnp.float32)
    _, idx = row_topk(pad_rows(jnp.abs(xf), 0.0), k, block=block,
                      interpret=interpret)
    idx = idx[:K]
    return jnp.take_along_axis(xf, idx, axis=1), idx


# ---------------------------------------------------------------------------
# Per-chunk symmetric int8 quantization
# ---------------------------------------------------------------------------

def _quantize_i8_kernel(x_ref, v_ref, s_ref, *, chunk: int, tc: int):
    lanes = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    scales = jnp.zeros(s_ref.shape, jnp.float32)             # (8, tc)
    for c in range(tc):
        cols = slice(c * chunk, (c + 1) * chunk)
        x = x_ref[:, cols]                                   # (8, chunk)
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        q = jnp.round(x / jnp.where(scale > 0.0, scale, 1.0))
        v_ref[:, cols] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
        scales = jnp.where(lanes == c, scale, scales)
    s_ref[...] = scales


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def quantize_i8(x, *, chunk: int = 256, interpret: bool = False):
    """x: (K, P) -> ``(values (K, P) int8, scales (K, ceil(P/chunk))
    f32)``. Symmetric per-chunk: scale = amax(|chunk|)/127; an all-zero
    chunk gets scale 0 and quantizes to 0. Zero padding (rows to 8, the
    parameter axis to whole tiles) quantizes to 0 and is sliced off.
    """
    K, P = x.shape
    rp, nc, tc, nt = _chunk_tiles(K, P, chunk)
    xp = _pad2(x.astype(jnp.float32), rp, nt * tc * chunk)
    vals, scales = pl.pallas_call(
        functools.partial(_quantize_i8_kernel, chunk=chunk, tc=tc),
        grid=(rp // _ROWS, nt),
        in_specs=[pl.BlockSpec((_ROWS, tc * chunk), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((_ROWS, tc * chunk), lambda i, j: (i, j)),
                   pl.BlockSpec((_ROWS, tc), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, jnp.int8),
                   jax.ShapeDtypeStruct((rp, nt * tc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xp)
    return vals[:K, :P], scales[:K, :nc]


def _dequantize_i8_kernel(v_ref, s_ref, o_ref, *, chunk: int, tc: int):
    scales = s_ref[...]                                      # (8, tc)
    for c in range(tc):
        cols = slice(c * chunk, (c + 1) * chunk)
        o_ref[:, cols] = (v_ref[:, cols].astype(jnp.float32)
                          * scales[:, c:c + 1])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def dequantize_i8(values, scales, *, chunk: int = 256,
                  interpret: bool = False):
    """Inverse of :func:`quantize_i8`: ``(K, P) int8 + (K, nc) f32 ->
    (K, P) f32`` with each chunk rescaled by its stored scale."""
    K, P = values.shape
    rp, nc, tc, nt = _chunk_tiles(K, P, chunk)
    vp = _pad2(values, rp, nt * tc * chunk)
    sp = _pad2(scales.astype(jnp.float32), rp, nt * tc)
    out = pl.pallas_call(
        functools.partial(_dequantize_i8_kernel, chunk=chunk, tc=tc),
        grid=(rp // _ROWS, nt),
        in_specs=[pl.BlockSpec((_ROWS, tc * chunk), lambda i, j: (i, j)),
                  pl.BlockSpec((_ROWS, tc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_ROWS, tc * chunk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(vp.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(vp, sp)
    return out[:K, :P]


# ---------------------------------------------------------------------------
# Fused compressed aggregation + quality
# ---------------------------------------------------------------------------

def _agg_quality_i8_kernel(w_ref, v_ref, s_ref, o_ref, dots_ref, sq_ref,
                           asq_ref, u_ref, *, chunk: int, tc: int):
    i = pl.program_id(0)
    # dequantize in VMEM: (rows, chunk) int8 * (rows, 1) chunk scale
    scales = s_ref[...]                                      # (rows, tc)
    for c in range(tc):
        cols = slice(c * chunk, (c + 1) * chunk)
        u_ref[:, cols] = (v_ref[:, cols].astype(jnp.float32)
                          * scales[:, c:c + 1])
    u = u_ref[...]                                           # (rows, tc*chunk)
    # f32 sums on the VPU, as in fedavg_agg_quality (an f32 dot would
    # multiply in bfloat16 on the MXU)
    agg = jnp.sum(w_ref[...] * u, axis=0, keepdims=True)     # (1, C)
    o_ref[...] = agg
    part_dots = jnp.sum(u * agg, axis=1, keepdims=True)      # (rows, 1)
    part_sq = jnp.sum(u * u, axis=1, keepdims=True)              # (rows, 1)
    part_asq = jnp.sum(agg * agg).reshape(1, 1)

    @pl.when(i == 0)
    def _init():
        dots_ref[...] = part_dots
        sq_ref[...] = part_sq
        asq_ref[...] = part_asq

    @pl.when(i > 0)
    def _accumulate():
        dots_ref[...] += part_dots
        sq_ref[...] += part_sq
        asq_ref[...] += part_asq


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fedavg_agg_quality_i8(values, scales, weights, *, chunk: int = 256,
                          interpret: bool = False):
    """Fused Δ_t + quality pass over *quantized* payloads.

    values: (K, P) int8, scales: (K, ceil(P/chunk)) f32, weights: (K,).
    Returns ``(agg (P,) f32, dots (K,), sq (K,), asq ())`` — exactly
    :func:`~repro.kernels.fedavg_agg.fedavg_agg_quality` applied to
    ``dequantize_i8(values, scales)``, but the dequantized (K, P)
    matrix never reaches HBM: each tile is dequantized into VMEM
    (zero-padding of the ragged tail dequantizes to 0 and cannot
    perturb the sums; padded rows carry weight 0).
    """
    K, P = values.shape
    rp, nc, tc, nt = _chunk_tiles(K, P, chunk)
    width = tc * chunk
    vp = _pad2(values, rp, nt * width)
    sp = _pad2(scales.astype(jnp.float32), rp, nt * tc)
    w2 = _pad2(weights.astype(jnp.float32).reshape(K, 1), rp, 1)
    agg, dots, sq, asq = pl.pallas_call(
        functools.partial(_agg_quality_i8_kernel, chunk=chunk, tc=tc),
        grid=(nt,),
        in_specs=[pl.BlockSpec((rp, 1), _first_block),
                  pl.BlockSpec((rp, width), _column_block),
                  pl.BlockSpec((rp, tc), _column_block)],
        out_specs=[pl.BlockSpec((1, width), _column_block),
                   pl.BlockSpec((rp, 1), _first_block),
                   pl.BlockSpec((rp, 1), _first_block),
                   pl.BlockSpec((1, 1), _first_block)],
        out_shape=[jax.ShapeDtypeStruct((1, nt * width), jnp.float32),
                   jax.ShapeDtypeStruct((rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rp, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w2, vp, sp)
    return agg[0, :P], dots[:K, 0], sq[:K, 0], asq[0, 0]
