"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` matches the corresponding kernel's semantics exactly and
is used (a) by tests/test_kernels_*.py for allclose sweeps across
shapes/dtypes and (b) as the CPU fallback path in ops.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -2.0 ** 30
# f32 products in the FedAvg oracles: a TPU's default matmul precision
# multiplies f32 in bfloat16 (on the CPU the two are the same)
_F32 = jax.lax.Precision.HIGHEST


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """q: (B,H,Sq,hd), k/v: (B,G,Sk,hd) with H % G == 0.

    Returns (B,H,Sq,hd). Softmax in f32, output cast back to q.dtype.
    """
    B, H, Sq, hd = q.shape
    G, Sk = k.shape[1], k.shape[2]
    rep = H // G
    scale = hd ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, G, rep, Sq, hd) * scale
    s = jnp.einsum("bgrqh,bgkh->bgrqk", qf, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos + (Sk - Sq)   # right-aligned when Sq < Sk
    if window > 0:
        mask &= kpos > qpos + (Sk - Sq) - window
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bgkh->bgrqh", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    """x: (..., D), scale: (D,)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * scale


def swiglu_ref(x, w_gate, w_up):
    """x: (M, D), w_gate/w_up: (D, F) -> (M, F): silu(x@Wg) * (x@Wu)."""
    g = x.astype(jnp.float32) @ w_gate.astype(jnp.float32)
    u = x.astype(jnp.float32) @ w_up.astype(jnp.float32)
    return (jax.nn.silu(g) * u).astype(x.dtype)


def fedavg_agg_ref(updates, weights):
    """updates: (K, P) per-client updates, weights: (K,) p_k.

    The paper's aggregation Δ_t = Σ_k p_k Δ_t^(k), f32 accumulation.
    """
    acc = jnp.einsum("kp,k->p", updates.astype(jnp.float32),
                     weights.astype(jnp.float32), precision=_F32)
    return acc.astype(updates.dtype)


def fedavg_agg_quality_ref(updates, weights):
    """Fused aggregation + quality oracle (kernels.fedavg_agg).

    updates: (K, P), weights: (K,). Returns (agg, dots, sq, asq) with
    agg = Σ_k p_k u_k in updates.dtype, dots_k = ⟨u_k, agg⟩ (f32 agg),
    sq_k = ‖u_k‖², asq = ‖agg‖² — everything accumulated in f32.
    """
    u = updates.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    agg = jnp.einsum("k,kp->p", w, u, precision=_F32)
    dots = jnp.matmul(u, agg, precision=_F32)
    sq = jnp.sum(u * u, axis=1)
    asq = jnp.dot(agg, agg, precision=_F32)
    return agg.astype(updates.dtype), dots, sq, asq


def mlstm_scan_ref(q, k, v, log_f, log_i, *, chunk: int = 64,
                   normalize: bool = True):
    """Chunkwise gated linear attention oracle.

    q,k: (B,H,S,dk), v: (B,H,S,dv), gates: (B,H,S). Returns (B,H,S,dv).
    Delegates to models.ssm.gated_linear_attention (itself validated
    against the step recurrence in tests/test_models_core.py).
    """
    from repro.models.ssm import gated_linear_attention
    to_bshd = lambda x: jnp.moveaxis(x, 1, 2)
    out, _ = gated_linear_attention(
        to_bshd(q), to_bshd(k), to_bshd(v),
        jnp.moveaxis(log_f, 1, 2),
        None if log_i is None else jnp.moveaxis(log_i, 1, 2),
        chunk=chunk, normalize=normalize)
    return jnp.moveaxis(out, 1, 2).astype(v.dtype)


def segmented_topk_ref(x, k: int):
    """Segmented top-k oracle: x (S, C) -> ((S, k) f32 values,
    (S, k) int32 lane indices), descending per segment. Ties break to
    the lowest lane (``lax.top_k`` semantics, which order ``-0.0``
    below ``+0.0``). ``-inf`` values mark exhausted segments; they sit
    at the segment's lowest ``-inf`` lanes."""
    k = int(min(k, x.shape[-1]))
    vals, idx = jax.lax.top_k(x.astype(jnp.float32), k)
    return vals, idx.astype(jnp.int32)


def topk_sparsify_ref(x, k: int):
    """Magnitude top-k oracle: x (K, P) -> ``(values (K, k) f32,
    indices (K, k) int32)``. Selection is ``lax.top_k(|x|, k)`` (stable
    — ties to the lowest index); values are the *signed* originals at
    the selected indices, ordered by descending magnitude. Exact on the
    CPU; on a v5e, ``lax.top_k`` over ~1M lanes returns the same set in
    another order, so compare there with a stable host sort."""
    k = int(min(k, x.shape[-1]))
    xf = x.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(xf), k)
    vals = jnp.take_along_axis(xf, idx, axis=1)
    return vals, idx.astype(jnp.int32)


def _chunked(x, chunk: int):
    """(K, P) f32 -> (K, nc, chunk) with a zero-padded ragged tail."""
    K, P = x.shape
    nc = -(-P // chunk)
    xp = jnp.pad(x, ((0, 0), (0, nc * chunk - P)))
    return xp.reshape(K, nc, chunk), nc


def quantize_i8_ref(x, chunk: int = 256):
    """Per-chunk symmetric int8 oracle: x (K, P) ->
    ``(values (K, P) int8, scales (K, ceil(P/chunk)) f32)`` with
    scale = amax(|chunk|)/127 (0 for an all-zero chunk) and
    values = round(x/scale) clipped to ±127."""
    K, P = x.shape
    xc, nc = _chunked(x.astype(jnp.float32), chunk)
    scales = jnp.max(jnp.abs(xc), axis=2) / 127.0              # (K, nc)
    safe = jnp.where(scales > 0.0, scales, 1.0)[:, :, None]
    q = jnp.where(scales[:, :, None] > 0.0, jnp.round(xc / safe), 0.0)
    vals = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return vals.reshape(K, -1)[:, :P], scales


def dequantize_i8_ref(values, scales, chunk: int = 256):
    """Inverse oracle: (K, P) int8 + (K, nc) f32 -> (K, P) f32."""
    K, P = values.shape
    vc, nc = _chunked(values.astype(jnp.float32), chunk)
    return (vc * scales[:, :, None]).reshape(K, -1)[:, :P]


def fedavg_agg_quality_i8_ref(values, scales, weights, chunk: int = 256):
    """Compressed fused aggregation oracle: dequantize, then the exact
    ``fedavg_agg_quality_ref`` pass (f32 throughout)."""
    u = dequantize_i8_ref(values, scales, chunk)
    agg, dots, sq, asq = fedavg_agg_quality_ref(u, weights)
    return agg.astype(jnp.float32), dots, sq, asq


def mkp_utility_ref(values, weights, residual, selectable, eps: float = 1e-12):
    """Toyoda pseudo-utility oracle: values (n,), weights (n, m),
    residual (m,), selectable (n,) -> (n,) f32, −inf where infeasible."""
    v = values.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    r = residual.astype(jnp.float32)
    scarcity = 1.0 / jnp.maximum(r, eps)
    penalty = w @ scarcity
    fits = jnp.all(w <= r + eps, axis=1) & (selectable.astype(jnp.float32) > 0)
    util = v / jnp.maximum(penalty, eps)
    return jnp.where(fits, util, -jnp.inf)
