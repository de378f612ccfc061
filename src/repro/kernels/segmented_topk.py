"""Segmented top-k Pallas kernel (hierarchical selection frontier, §VI-A
at fleet scale).

The million-client selection plane shards the pool into ``S`` segments
of ``C`` rows and replaces the full-pool argsort of the greedy knapsack
with a per-shard *frontier*: the top-``k`` score/cost ratios of every
shard, selected in one kernel over the sharded ratio matrix. The global
merge then runs the exact greedy over the ``S * k`` surviving
candidates on the host (``core.engine.hierarchical_greedy_knapsack``).

Kernel shape: the rows are padded to a multiple of 8 and the lanes are
cut into ``block``-wide tiles, so every block is ``(8, block)`` — the
TPU's native ``(8, 128)`` tiling. Each row's top-``k`` is found by
*threshold select*, in work that grows with the row's width and hardly
with ``k``:

1. **Threshold.** Keys are compared through their order-preserving
   int32 image (the f32 bits, with the magnitude bits flipped where the
   sign is set: ``-0.0`` sorts just below ``+0.0``, as in
   ``jax.lax.top_k``). Bisection on that image, one bit a pass for 32
   passes, each pass one streaming count of ``key >= t`` over the row's
   tiles, finds the ``k``-th largest key ``tau`` and the count of keys
   strictly above it; ``k`` less that count is the tie quota.
2. **Gather.** One more pass takes, tile by tile in lane order, every
   key above ``tau`` and the lowest-lane keys equal to it until the
   quota is spent. A prefix count along the tile gives each survivor
   its place, a shift network (one shift a bit of the distance, lowest
   bit first) packs the survivors to the tile's front, and a per-row
   rotation appends them to an ``(8, frame)`` frontier in VMEM,
   ``frame`` the power of two at or above ``k``'s whole vregs.
3. **Order.** A bitonic network sorts the frontier by key descending,
   lane ascending, so ties break toward the lowest lane (matching
   ``jax.lax.top_k``), bit for bit.

All three run in one ``pallas_call`` over a ``(row blocks, 33, tiles)``
grid: 32 count passes, then the gather; the frontier is written once,
as a ``(1, rows, k)`` block. The gather's networks act on whole tiles
(each step one roll and a select over the tile); the sort is a loop
over vregs with its distances computed in the loop, so it traces to
the same few lines at every ``k``. Rows are padded with ``-inf``; a
row with fewer than ``k`` finite keys fills its frontier with ``-inf``
entries, which therefore mean "segment exhausted" (their lanes are the
lowest ``-inf`` lanes of the row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8          # f32 sublanes per vreg: the row-tile height
_LANES = 128       # lanes per vreg
_BLOCK = 16_384    # default lane-tile width (a (8, 16384) f32 tile = 512 KiB)
_BITS = 32         # count passes: one per bit of the int32 key image
_MIN = -(1 << 31)  # int32 sign bit; the image of no key sorts lower
_MAX = (1 << 31) - 1


def method(k: int, width: int) -> str:
    """The selection ``segmented_topk`` runs for a top-``k`` of rows
    ``width`` lanes wide. There is one, threshold select, whatever the
    sizes: the label is what the ``stage1.frontier`` span records."""
    del k, width
    return "threshold"


def _i32(v):
    # loop bounds, shifts and fills as int32: a Python int lowers to i64
    # under jax_enable_x64, which Mosaic rejects
    return jnp.int32(v)


def _image(x):
    """f32 -> int32 whose signed order is the floats' total order."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ _MAX, i)


def _value(s):
    """Inverse of :func:`_image` (the map is its own inverse)."""
    return jax.lax.bitcast_convert_type(jnp.where(s < 0, s ^ _MAX, s),
                                        jnp.float32)


def _vreg(v):
    """The lanes of vreg ``v`` of an ``(8, n)`` ref."""
    return pl.ds(pl.multiple_of(v * _LANES, _LANES), _LANES)


def _lanes_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)


def _loop(lo, hi, body):
    """``body(v)`` for ``v`` in ``[lo, hi)``, in order."""
    def step(v, c):
        body(v)
        return c
    jax.lax.fori_loop(_i32(lo), _i32(hi), step, _i32(0))


def _roll(x, shift: int):
    """``jnp.roll`` along the lanes by a static ``shift``."""
    return pltpu.roll(x, _i32(shift), 1)


def _prefix_sum(x, width: int):
    """Inclusive prefix sum along the lanes (Hillis-Steele)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    sh = 1
    while sh < width:
        x = x + jnp.where(lanes >= sh, _roll(x, sh), _i32(0))
        sh *= 2
    return x


def _pack_front(s, d, width: int):
    """Move every entry with ``d >= 0`` down by ``d`` lanes, one roll per
    bit of ``d`` from the lowest: survivors keep their order, so none
    ever lands on another. Entries with ``d < 0`` are dropped (their
    ``d`` comes out ``-1``)."""
    sh = 1
    while sh < width:
        s_in = _roll(s, width - sh)                    # lane l + sh
        d_in = _roll(d, width - sh)
        arrive = (d_in >= 0) & ((d_in & sh) != 0)
        stay = (d >= 0) & ((d & sh) == 0)
        s = jnp.where(arrive, s_in, s)
        d = jnp.where(arrive, d_in, jnp.where(stay, d, _i32(-1)))
        sh *= 2
    return s, d


def _before(a_s, a_l, b_s, b_l):
    """Whether entry a precedes entry b: larger key, then lower lane."""
    return (a_s > b_s) | ((a_s == b_s) & (a_l < b_l))


def _sort(fs, fl, frame: int):
    """Bitonic sort of the ``(8, frame)`` frontier refs (keys, lanes),
    each row on its own, into key-descending, lane-ascending order. A
    block of ``size`` entries whose index has the ``size`` bit set is
    ordered the other way (``down`` flips both keys' bits)."""
    nv = frame // _LANES
    lanes = _lanes_iota()

    def across(size, dist):
        # compare-exchange between vregs ``dist / 128`` apart
        span = dist >> 7

        def pair(m):
            lo = (m // span) * 2 * span + m % span
            hi = lo + span
            down = -(((lo * _LANES) & size) != 0).astype(jnp.int32)
            ls, ll = fs[:, _vreg(lo)], fl[:, _vreg(lo)]
            hs, hl = fs[:, _vreg(hi)], fl[:, _vreg(hi)]
            swap = _before(hs ^ down, hl ^ down, ls ^ down, ll ^ down)
            fs[:, _vreg(lo)] = jnp.where(swap, hs, ls)
            fl[:, _vreg(lo)] = jnp.where(swap, hl, ll)
            fs[:, _vreg(hi)] = jnp.where(swap, ls, hs)
            fl[:, _vreg(hi)] = jnp.where(swap, ll, hl)
        _loop(0, nv // 2, pair)

    def within(size, first):
        # every step of the level inside each vreg: dist first .. 1
        steps = _i32(32) - jax.lax.clz(first)          # log2(first) + 1

        def vreg(v):
            down = -(((v * _LANES + lanes) & size) != 0).astype(jnp.int32)

            def step(u, c):
                vs, vl = c
                dist = first >> u
                upper = (lanes & dist) != 0
                ps = jnp.where(upper, pltpu.roll(vs, dist, 1),
                               pltpu.roll(vs, _LANES - dist, 1))
                pl_ = jnp.where(upper, pltpu.roll(vl, dist, 1),
                                pltpu.roll(vl, _LANES - dist, 1))
                own_first = _before(vs ^ down, vl ^ down, ps ^ down,
                                    pl_ ^ down)
                # the lower entry of a pair keeps whichever comes first
                take = ~(own_first ^ upper)
                return jnp.where(take, ps, vs), jnp.where(take, pl_, vl)
            vs, vl = jax.lax.fori_loop(_i32(0), steps, step,
                                       (fs[:, _vreg(v)], fl[:, _vreg(v)]))
            fs[:, _vreg(v)], fl[:, _vreg(v)] = vs, vl
        _loop(0, nv, vreg)

    def level(lv, c):
        size = _i32(1) << lv

        def cross(u, c):
            across(size, size >> (u + 1))
            return c
        # distances of a vreg or more, then the rest inside each vreg
        jax.lax.fori_loop(_i32(0), jnp.maximum(lv - 7, 0), cross, _i32(0))
        within(size, jnp.minimum(size >> 1, _LANES // 2))
        return c
    jax.lax.fori_loop(_i32(1), _i32(frame.bit_length()), level, _i32(0))


def _select_kernel(x_ref, vals_ref, idx_ref, t_ref, cnt_ref, above_ref,
                   seen_ref, filled_ref, acc_s, acc_l, *, k: int, kt: int,
                   width: int, nb: int, frame: int):
    p = pl.program_id(1)                 # count passes, then the gather
    j = pl.program_id(2)                 # lane tile
    s = _image(x_ref[...])               # (8, width)

    @pl.when(p < _BITS)
    def _count():
        @pl.when((p == 0) & (j == 0))
        def _():
            t_ref[...] = jnp.zeros_like(t_ref)
            above_ref[...] = jnp.zeros_like(above_ref)

        @pl.when(j == 0)
        def _():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        # t is built as an unsigned pattern, highest bit first: the
        # candidate sets this pass's bit; compare signed images
        cand = t_ref[...] | (_i32(1) << (_BITS - 1 - p))
        ge = (s >= (cand ^ _MIN)[:, :1]).astype(jnp.int32)
        cnt_ref[...] += jnp.sum(ge, axis=1, keepdims=True, dtype=jnp.int32)

        @pl.when(j == nb - 1)
        def _():
            n = cnt_ref[...]
            keep = n >= k
            t_ref[...] = jnp.where(keep, cand, t_ref[...])
            # the last refused candidate is tau + 1: n counts keys > tau
            above_ref[...] = jnp.where(keep, above_ref[...], n)

    @pl.when(p == _BITS)
    def _gather():
        @pl.when(j == 0)
        def _():
            seen_ref[...] = jnp.zeros_like(seen_ref)
            filled_ref[...] = jnp.zeros_like(filled_ref)
            acc_s[...] = jnp.full(acc_s.shape, _MIN, jnp.int32)
            acc_l[...] = jnp.full(acc_l.shape, _MAX, jnp.int32)

        tau = (t_ref[...] ^ _MIN)[:, :1]
        quota = jnp.maximum(k - above_ref[...] - seen_ref[...], 0)[:, :1]
        gt, eq = s > tau, s == tau
        # both counts in one scan: keys above tau low, ties high
        packed = gt.astype(jnp.int32) | (eq.astype(jnp.int32) << 16)
        incl = _prefix_sum(packed, width)
        excl = incl - packed
        gt_before, eq_before = excl & 0xFFFF, excl >> 16
        lanes = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = gt | (eq & (eq_before < quota))
        # each survivor's distance d to its place at the tile's front
        d = jnp.where(keep, lanes - gt_before
                      - jnp.minimum(eq_before, quota), _i32(-1))
        fs, d = _pack_front(s, d, width)
        fl = jnp.where(d >= 0, lanes + d + j * width, _i32(-1))
        # into the frontier's frame (a tile keeps at most k survivors)
        if width >= frame:
            fs, fl = fs[:, :frame], fl[:, :frame]
        else:
            fs = jnp.concatenate(
                [fs, jnp.full((_ROWS, frame - width), _MIN, jnp.int32)], 1)
            fl = jnp.concatenate(
                [fl, jnp.full((_ROWS, frame - width), -1, jnp.int32)], 1)
        filled = filled_ref[:, :1]
        sh = 1
        while sh < frame:                # rotate up by `filled`, per row
            moved = (filled & sh) != 0
            fs = jnp.where(moved, _roll(fs, sh), fs)
            fl = jnp.where(moved, _roll(fl, sh), fl)
            sh *= 2
        acc_s[...] = jnp.where(fl >= 0, fs, acc_s[...])
        acc_l[...] = jnp.where(fl >= 0, fl, acc_l[...])
        tot = incl[:, width - 1:]
        seen_ref[...] += tot >> 16
        filled_ref[...] += (tot & 0xFFFF) + jnp.minimum(tot >> 16, quota)

        @pl.when(j == nb - 1)
        def _():
            _sort(acc_s, acc_l, frame)

            def out(v):
                vals_ref[:, _vreg(v)] = _value(acc_s[:, _vreg(v)])
                idx_ref[:, _vreg(v)] = acc_l[:, _vreg(v)]
            _loop(0, kt // _LANES, out)


def _frontier_block(i, p, j):
    # the whole frontier of row block i at every step; the int32 zero
    # stays int32 under jax_enable_x64 (a Python 0 lowers to i64, which
    # Mosaic rejects)
    return jnp.int32(0), i, jnp.int32(0)


def _lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def row_topk(keys, k: int, *, block: int = _BLOCK, interpret: bool = False):
    """keys: (R, W) f32 with ``R % 8 == 0`` -> ``((R, k) keys, (R, k)
    int32 lanes)``, descending per row, ties to the lowest lane.

    Rows are ``-inf``-padded to whole ``block``-wide tiles (one tile of
    ``W`` rounded up to whole vregs where that is narrower). The kernel
    writes ``_lanes(k)`` entries a row, cut back to ``k`` here.
    """
    R, W = keys.shape
    k = int(min(k, W))
    if block % _LANES or block > 1 << 15:   # counts are packed in 16 bits
        raise ValueError(f"block {block} is not whole vregs up to 32768")
    bw = min(block, _lanes(W))
    nb = -(-W // bw)
    if nb * bw != W:
        keys = jnp.pad(keys, ((0, 0), (0, nb * bw - W)),
                       constant_values=-jnp.inf)
    kt = _lanes(k)
    frame = 1 << (kt - 1).bit_length()
    small = pltpu.VMEM((_ROWS, _LANES), jnp.int32)
    vmem = functools.partial(pltpu.VMEM, dtype=jnp.int32)
    vals, idx = pl.pallas_call(
        functools.partial(_select_kernel, k=k, kt=kt, width=bw, nb=nb,
                          frame=frame),
        grid=(R // _ROWS, _BITS + 1, nb),
        in_specs=[pl.BlockSpec((_ROWS, bw), lambda i, p, j: (i, j))],
        out_specs=[pl.BlockSpec((None, _ROWS, kt), _frontier_block),
                   pl.BlockSpec((None, _ROWS, kt), _frontier_block)],
        out_shape=[jax.ShapeDtypeStruct((1, R, kt), jnp.float32),
                   jax.ShapeDtypeStruct((1, R, kt), jnp.int32)],
        scratch_shapes=[small, small, small, small, small,
                        vmem((_ROWS, frame)), vmem((_ROWS, frame))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(keys)
    return vals[0, :, :k], idx[0, :, :k]


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
