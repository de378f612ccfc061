"""Blocked flash attention for TPU (Pallas): causal / sliding-window /
GQA, online softmax, f32 accumulation in VMEM scratch.

Layout: q (B,H,Sq,hd), k/v (B,G,Sk,hd). Grid = (B, H, Sq/bq, Sk/bk) with
the KV-block dimension innermost ("arbitrary" semantics => sequential),
so the (m, l, acc) scratch carries across KV blocks of one Q block and
is flushed to HBM on the last one. Block shapes default to MXU-aligned
(128, 128); hd rides along unblocked (<= 256 for all assigned archs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, bq: int, bk: int,
                 seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # zero padded K rows (S % bk != 0): garbage values must not reach the
    # PV matmul (0 * garbage = NaN hazards).
    kvalid = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < seq_k

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
    k = jnp.where(kvalid, k_ref[0, 0].astype(jnp.float32), 0.0)  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    # absolute positions (right-aligned when Sq < Sk, e.g. decode)
    offset = seq_k - seq_q
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_k                                 # tail padding
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (everything -inf): keep exp at 0
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = jnp.where(kvalid, v_ref[0, 0].astype(jnp.float32), 0.0)  # (bk, hd)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B,H,Sq,hd); k/v: (B,G,Sk,hd). Returns (B,H,Sq,hd)."""
    B, H, Sq, hd = q.shape
    G, Sk = k.shape[1], k.shape[2]
    if H % G:
        raise ValueError(f"H={H} not a multiple of G={G}")
    rep = H // G
    scale = float(hd ** -0.5) if scale is None else float(scale)

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    nq = pl.cdiv(Sq, bq)
    nk = pl.cdiv(Sk, bk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, seq_q=Sq, seq_k=Sk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, _rep=rep: (b, h // _rep, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, _rep=rep: (b, h // _rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)

