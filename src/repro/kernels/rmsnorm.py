"""Fused RMSNorm Pallas kernel: one HBM->VMEM pass per row block,
f32 variance accumulation, fused scale multiply."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                  # (bm, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., D); scale: (D,). Rows are processed in VMEM blocks."""
    orig_shape = x.shape
    D = x.shape[-1]
    xm = x.reshape(-1, D)
    M = xm.shape[0]
    bm = min(block_rows, M)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pl.cdiv(M, bm),),
        in_specs=[pl.BlockSpec((bm, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xm, scale)
    return out.reshape(orig_shape)
