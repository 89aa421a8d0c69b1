"""OP_reuse element-wise kernel — TaylorSeer forecast over cached blocks.

The paper's cache-then-reuse branch performs "lightweight element-wise
operations (e.g., summation and multiplication in TaylorSeer)".  On TPU we
run it as a standalone VPU kernel over the CACHED blocks only (scalar-
prefetched id list), overlapping with the MXU-bound sparse attention kernel
at the XLA schedule level (DESIGN §2.3).

    out[block b] = Σ_d  coef[d] · derivs[d, block b]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["taylor_reuse_kernel"]


def _kernel(ids_ref, coef_ref, derivs_ref, base_ref, out_ref, *, order1: int):
    acc = coef_ref[0, 0] * derivs_ref[0, 0].astype(jnp.float32)
    for d in range(1, order1):
        acc += coef_ref[0, d] * derivs_ref[d, 0].astype(jnp.float32)
    out_ref[0] = acc.astype(out_ref.dtype)


def taylor_reuse_kernel(
    derivs: jax.Array,      # (D+1, BH, N, d) finite-difference stack
    coef: jax.Array,        # (1, D+1) f32 reuse coefficients (SMEM 2D)
    base: jax.Array,        # (BH, N, d) written-through baseline (aliased)
    ids: jax.Array,         # (BH, Cc) int32 cached block ids
    *,
    block: int,
    interpret: bool = False,
) -> jax.Array:
    order1, bhs, n, d = derivs.shape
    cc = ids.shape[1]
    assert n % block == 0

    def d_map(bh, c, ids_ref, coef_ref):
        return (0, bh, ids_ref[bh, c], 0)

    def o_map(bh, c, ids_ref, coef_ref):
        return (bh, ids_ref[bh, c], 0)

    return pl.pallas_call(
        functools.partial(_kernel, order1=order1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bhs, cc),
            in_specs=[
                pl.BlockSpec((order1, 1, block, d), d_map),
                pl.BlockSpec((1, block, d), o_map),
            ],
            out_specs=pl.BlockSpec((1, block, d), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_taylor_reuse",
    )(ids, coef, derivs, base)
