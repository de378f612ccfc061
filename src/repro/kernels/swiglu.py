"""Fused SwiGLU Pallas kernel: silu(x @ Wg) * (x @ Wu) with both partial
products accumulated in VMEM scratch over K blocks — the activations
never round-trip to HBM between the two GEMMs and the gating."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _swiglu_kernel(x_ref, wg_ref, wu_ref, o_ref, accg, accu, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        accg[...] = jnp.zeros_like(accg)
        accu[...] = jnp.zeros_like(accu)

    x = x_ref[...].astype(jnp.float32)                  # (bm, bk)
    accg[...] += jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)
    accu[...] += jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        g = accg[...]
        o_ref[...] = (g / (1.0 + jnp.exp(-g)) * accu[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def swiglu(x, w_gate, w_up, *, block_m: int = 256, block_n: int = 256,
           block_k: int = 512, interpret: bool = False):
    """x: (..., D); w_gate/w_up: (D, F). Returns (..., F)."""
    orig = x.shape
    D = x.shape[-1]
    F = w_gate.shape[1]
    xm = x.reshape(-1, D)
    M = xm.shape[0]
    bm, bn, bk = min(block_m, M), min(block_n, F), min(block_k, D)
    nk = pl.cdiv(D, bk)
    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, nk=nk),
        grid=(pl.cdiv(M, bm), pl.cdiv(F, bn), nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xm, w_gate, w_up)
    return out.reshape(*orig[:-1], F)
