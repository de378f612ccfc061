"""Toyoda pseudo-utility update Pallas kernel (MKP inner loop, §VI-B).

Each greedy pick of the MKP scheduler rescores every candidate item
against the residual knapsack capacities:

    scarcity_k = 1 / residual_k
    util_j     = v_j / Σ_k w_jk · scarcity_k     (−inf if j can't fit)

For an ``(n_items, n_knapsacks)`` weight matrix this is a bandwidth-bound
row reduction; the kernel tiles the item axis into VMEM-sized blocks,
keeps the (small) knapsack axis whole, and fuses the fit mask, the
scarcity-weighted penalty and the final select into one pass. The
residual vector is a broadcast (1, m) block shared by every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12


def _mkp_utility_kernel(v_ref, sel_ref, w_ref, r_ref, o_ref):
    w = w_ref[...]                                   # (bn, m) f32
    resid = r_ref[...]                               # (1, m)  f32
    v = v_ref[...]                                   # (bn,)   f32
    sel = sel_ref[...]                               # (bn,)   f32 0/1
    scarcity = 1.0 / jnp.maximum(resid, _EPS)        # (1, m)
    penalty = jnp.sum(w * scarcity, axis=1)          # (bn,)
    # an f32 min, not jnp.all: under jax_enable_x64 a bool reduction
    # lowers to an f64 one, which Mosaic rejects
    fits = (jnp.min((w <= resid + _EPS).astype(jnp.float32), axis=1) > 0.0) \
        & (sel > 0.0)
    util = v / jnp.maximum(penalty, _EPS)
    o_ref[...] = jnp.where(fits, util, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def mkp_utility(values, weights, residual, selectable, *,
                block_n: int = 4096, interpret: bool = False):
    """values: (n,), weights: (n, m), residual: (m,), selectable: (n,).

    Returns (n,) float32 utilities, −inf where the item is unselectable
    or does not fit the residual capacities.
    """
    n, m = weights.shape
    bn = min(block_n, n)
    v = values.astype(jnp.float32)
    sel = selectable.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    r = residual.astype(jnp.float32).reshape(1, m)
    return pl.pallas_call(
        _mkp_utility_kernel,
        grid=(pl.cdiv(n, bn),),
        # block indices are int32: a Python 0 lowers to i64 under
        # jax_enable_x64, which Mosaic rejects
        in_specs=[pl.BlockSpec((bn,), lambda i: (i,)),
                  pl.BlockSpec((bn,), lambda i: (i,)),
                  pl.BlockSpec((bn, m), lambda i: (i, jnp.int32(0))),
                  pl.BlockSpec((1, m),
                               lambda i: (jnp.int32(0), jnp.int32(0)))],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(v, sel, w, r)
