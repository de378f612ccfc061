"""Segmented top-k Pallas kernel (hierarchical selection frontier, §VI-A
at fleet scale).

The million-client selection plane shards the pool into ``S`` segments
of ``C`` rows and replaces the full-pool argsort of the greedy knapsack
with a per-shard *frontier*: the top-``k`` score/cost ratios of every
shard, extracted in one pass over the sharded ratio matrix. The global
merge then runs the exact greedy over the ``S * k`` surviving
candidates on the host (``core.engine.hierarchical_greedy_knapsack``).

Kernel shape: the rows are padded to a multiple of 8 and the lanes are
cut into ``block``-wide tiles, so every block is ``(8, block)`` — the
TPU's native ``(8, 128)`` tiling. One grid step per tile extracts that
tile's top-``k`` by iterative max-extract: ``k`` vectorized max/mask
passes over the VMEM-resident tile, no sort network and no dynamic
stores (the running ``(8, k)`` value/lane frontiers are carried through
a ``fori_loop`` and written once). Ties break toward the lowest lane
(matching ``jax.lax.top_k`` and the host argsort's stable order).

When a row spans several tiles, the tiles' frontiers are laid side by
side in tile order and the same kernel runs again over them, until one
tile holds the row. This merge is exact, ties included: equal keys from
one tile leave it in ascending lane order and tiles are concatenated in
ascending lane order, so "lowest position" in the candidate row is
"lowest lane" in the original row. ``block`` grows to twice the
frontier when ``k`` is large, so each level at least halves the row.

Rows are padded with ``-inf``; a ``-inf`` frontier entry therefore
means "segment exhausted" and its index is meaningless.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8          # f32 sublanes per vreg: the row-tile height
_BLOCK = 16_384    # default lane-tile width (a (8, 16384) f32 tile = 512 KiB)


def _topk_tile_kernel(x_ref, vals_ref, idx_ref, *, k: int, width: int):
    row = x_ref[...]                                     # (8, width) f32
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (row.shape[0], k), 1)

    def body(i, carry):
        row, vals, idxs = carry
        m = jnp.max(row, axis=1, keepdims=True)          # (8, 1)
        # lowest lane attaining the max (stable tie-break)
        j = jnp.min(jnp.where(row == m, lanes, jnp.int32(width)), axis=1,
                    keepdims=True)
        vals = jnp.where(slots == i, m, vals)
        idxs = jnp.where(slots == i, j, idxs)
        row = jnp.where(lanes == j, -jnp.inf, row)
        return row, vals, idxs

    init = (row, jnp.full((row.shape[0], k), -jnp.inf, jnp.float32),
            jnp.zeros((row.shape[0], k), jnp.int32))
    # int32 bounds (and the int32 width above) keep every index int32
    # under jax_enable_x64
    _, vals, idxs = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), body,
                                      init)
    vals_ref[...] = vals
    idx_ref[...] = idxs + pl.program_id(1) * width       # tile -> row lane


def _frontier_block(i, j):
    # tile j of row block i; the int32 zero stays int32 under
    # jax_enable_x64 (a Python 0 lowers to i64, which Mosaic rejects)
    return j, i, jnp.int32(0)


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def row_topk(keys, k: int, *, block: int = _BLOCK, interpret: bool = False):
    """keys: (R, W) f32 with ``R % 8 == 0`` -> ``((R, k) keys, (R, k)
    int32 lanes)``, descending per row, ties to the lowest lane.

    Every tile and frontier is a whole number of 128-lane vregs: rows
    are ``-inf``-padded to whole tiles, and each tile yields
    ``_lanes(k)`` candidates (the extra ones are its next-best, so the
    merge stays exact); the final frontier is cut back to ``k``.
    """
    R, W = keys.shape
    k = int(min(k, W))
    bw = max(block, 2 * _lanes(k))
    nb = -(-W // bw)
    if nb == 1:
        bw = _lanes(W)
    if nb * bw != W:
        keys = jnp.pad(keys, ((0, 0), (0, nb * bw - W)),
                       constant_values=-jnp.inf)
    kt = min(_lanes(k), bw)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=kt, width=bw),
        grid=(R // _ROWS, nb),
        in_specs=[pl.BlockSpec((_ROWS, bw), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((None, _ROWS, kt), _frontier_block),
                   pl.BlockSpec((None, _ROWS, kt), _frontier_block)],
        out_shape=[jax.ShapeDtypeStruct((nb, R, kt), jnp.float32),
                   jax.ShapeDtypeStruct((nb, R, kt), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(keys)
    if nb == 1:
        return vals[0, :, :k], idx[0, :, :k]
    # tile frontiers side by side, in tile order, then merge them
    cand = jnp.transpose(vals, (1, 0, 2)).reshape(R, nb * kt)
    lanes = jnp.transpose(idx, (1, 0, 2)).reshape(R, nb * kt)
    vals, pos = row_topk(cand, k, block=block, interpret=interpret)
    return vals, jnp.take_along_axis(lanes, pos, axis=1)


def pad_rows(x, fill):
    """Pad the leading axis of a 2-D array up to a multiple of 8."""
    r = x.shape[0]
    rp = -(-r // _ROWS) * _ROWS
    if rp == r:
        return x
    return jnp.pad(x, ((0, rp - r), (0, 0)), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def segmented_topk(x, k: int, *, block: int = _BLOCK,
                   interpret: bool = False):
    """x: (S, C) per-segment rows -> ((S, k) values f32, (S, k) lane
    indices int32), descending per segment, ties to the lowest lane.
    Entries equal to ``-inf`` mean the segment ran out of finite rows.
    """
    S, _ = x.shape
    keys = pad_rows(x.astype(jnp.float32), -jnp.inf)
    vals, idx = row_topk(keys, k, block=block, interpret=interpret)
    return vals[:S], idx[:S]
