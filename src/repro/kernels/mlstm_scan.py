"""Chunkwise-parallel gated linear attention (mLSTM / SSD) Pallas kernel.

TPU adaptation of the GPU selective-scan: intra-chunk work is two small
MXU matmuls (QKᵀ and PV) with log-space gate weights; the inter-chunk
state (dk x dv per head) lives in VMEM scratch and is carried across the
innermost (sequential) grid dimension — no HBM round-trip per chunk.

Matches ``kernels.ref.mlstm_scan_ref`` (== models.ssm oracle) for both
the normalized (mLSTM) and unnormalized (SSD / mamba-2) variants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(q_ref, k_ref, v_ref, f_ref, i_ref, o_ref,
                s_scr, n_scr, m_scr, *, chunk: int, normalize: bool,
                seq: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    # padded-tail handling: zero K/V rows (0*garbage = NaN hazard) and
    # neutralize the gates (f=1, i=0 in log space)
    tpos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk,), 0)
    valid = tpos < seq
    vcol = valid[:, None]

    q = q_ref[0].astype(jnp.float32)                   # (C, dk)
    k = jnp.where(vcol, k_ref[0].astype(jnp.float32), 0.0)
    v = jnp.where(vcol, v_ref[0].astype(jnp.float32), 0.0)  # (C, dv)
    fj = f_ref[0].astype(jnp.float32)                  # (C,)
    ij = i_ref[0].astype(jnp.float32)

    fj = jnp.where(valid, fj, 0.0)
    neg_big = jnp.float32(-1e30)
    ij = jnp.where(valid, ij, neg_big)

    g = jnp.cumsum(fj)                                  # (C,) inclusive
    G = g[-1]
    m_prev = m_scr[0, 0]

    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    inter = g + m_prev                                  # (C,)
    intra = g[:, None] - g[None, :] + ij[None, :]       # (C, C)
    intra = jnp.where(causal, intra, neg_big)
    if normalize:
        M = jnp.maximum(inter, intra.max(axis=-1))      # (C,)
    else:
        M = jnp.zeros_like(inter)
    w_inter = jnp.exp(inter - M)
    w_intra = jnp.exp(intra - M[:, None])
    w_intra = jnp.where(causal, w_intra, 0.0)

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C, C)
    scores = qk * w_intra
    y = jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    y += w_inter[:, None] * jax.lax.dot(q, s_scr[...],
                                        preferred_element_type=jnp.float32)
    if normalize:
        nrm = scores.sum(axis=-1) + w_inter * (q @ n_scr[...][:, 0])
        denom = jnp.maximum(jnp.abs(nrm), jnp.exp(-M))
        y = y / denom[:, None]
    o_ref[0] = y.astype(o_ref.dtype)

    # ---- state update ----
    m_new = jnp.maximum(G + m_prev, (G - g + ij).max())
    if not normalize:
        m_new = jnp.zeros_like(m_new)
    decay = jnp.exp(G + m_prev - m_new)
    w_k = jnp.exp(G - g + ij - m_new)                   # (C,)
    s_scr[...] = decay * s_scr[...] + jax.lax.dot_general(
        k * w_k[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (dk, dv)
    n_scr[...] = decay * n_scr[...] + (
        (k * w_k[:, None]).sum(axis=0))[:, None]        # (dk, 1)
    m_scr[...] = jnp.full_like(m_scr, m_new)


@functools.partial(jax.jit, static_argnames=("chunk", "normalize",
                                             "interpret"))
def mlstm_scan(q, k, v, log_f, log_i=None, *, chunk: int = 64,
               normalize: bool = True, interpret: bool = False):
    """q,k: (B,H,S,dk), v: (B,H,S,dv), log_f/log_i: (B,H,S).

    Returns (B,H,S,dv). log_i=None => SSD mode (zeros, unnormalized
    callers pass normalize=False)."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if log_i is None:
        log_i = jnp.zeros_like(log_f)
    C = min(chunk, S)
    NC = pl.cdiv(S, C)
    BH = B * H
    rs = lambda x: x.reshape(BH, S, *x.shape[3:])
    qf, kf, vf = rs(q), rs(k), rs(v)
    ff, iff = log_f.reshape(BH, S), log_i.reshape(BH, S)

    out = pl.pallas_call(
        functools.partial(_gla_kernel, chunk=C, normalize=normalize, seq=S),
        grid=(BH, NC),
        in_specs=[
            pl.BlockSpec((1, C, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C), lambda b, c: (b, c)),
            pl.BlockSpec((1, C), lambda b, c: (b, c)),
        ],
        out_specs=pl.BlockSpec((1, C, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((dk, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, ff, iff)
    return out.reshape(B, H, S, dv)
