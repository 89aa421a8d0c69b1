"""FlashOmni GEMM-Q — spatial-axis sparse projection (paper §3.5, Obs. 2).

At *Dispatch* steps, row blocks whose attention output is fully cached never
need their query projection.  The GPU kernel decodes ``S_c`` per CTA and
early-exits; the TPU adaptation gathers the LIVE row blocks through a
scalar-prefetched index map, so dead rows cost neither MXU cycles nor DMA
(DESIGN §2.4).

The output is **compact** ``(Cr·bm, F)`` — live blocks in slot order.  The
FlashOmni attention CSR kernel consumes Q by live-slot index, so the compact
layout chains into attention without a scatter (layout fusion).  Use
:func:`repro.kernels.ops.scatter_rows` when the full-shape tensor is needed.

Batching is part of the KERNEL GRID: pass ``x`` as ``(B, N, K)`` with
``row_ids`` ``(B, Cr)`` and the grid grows a leading batch dimension —
one ``pallas_call`` covers the whole batch (no Python per-sample relaunch;
the scalar-prefetched ids are flattened ``(B·Cr,)`` and indexed by
``b·Cr + c``).  The unbatched ``(N, K)`` / ``(Cr,)`` signature still works.

Occupancy guard (``row_cnt``, ISSUE 8): GEMM-Q has no per-row reduction
occupancy to bucket — its reduction axis is the DENSE model dim ``K``, and
its spatial sparsity is already the compact ``Cr`` capacity (the paper's
1:1 density:speedup line).  What remains is the GPU kernel's ``S_c``
early-exit analogue: capacity-padding slots (``c ≥ row_cnt``) duplicate
the last live row id, and an unguarded kernel pays full MXU work to
compute a duplicate that every consumer masks off.  With ``row_cnt`` the
kernel skips the MXU on padded slots (the input re-DMA of the duplicated
block is elided by Mosaic) and stores deterministic ZEROS there — the
compact tail is defined output, not duplicated garbage.  The GEMM-Q grid
shares the attention kernel's Update-time sort: ``active_indices``
already orders live rows first, which IS the degenerate one-bucket
layout over the dense-``K`` reduction, so no second sort exists anywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_q_sparse_kernel"]


def _kernel(row_ids_ref, row_cnt_ref, x_ref, w_ref, o_ref, acc_ref, *,
            n_k: int):
    bi, c, ki = pl.program_id(0), pl.program_id(1), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Padding slots (c >= row_cnt) skip the MXU entirely — their
    # accumulator stays zero, so the compact tail stores deterministic
    # zeros instead of a duplicate of the last live block.
    @pl.when(c < row_cnt_ref[bi])
    def _accum():
        acc_ref[...] += jax.lax.dot(
            x_ref[0].astype(jnp.float32),
            w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def gemm_q_sparse_kernel(
    x: jax.Array,          # (B, N, K) or (N, K)
    w: jax.Array,          # (K, F)
    row_ids: jax.Array,    # (B, Cr) or (Cr,) int32 live row-block ids
    *,
    block_rows: int,       # bm — MUST equal the symbol granularity divisor
    block_k: int = 512,
    block_f: int = 512,
    interpret: bool = False,
    row_cnt: Optional[jax.Array] = None,   # (B,) or () live-slot counts
) -> jax.Array:
    squeeze = x.ndim == 2
    if squeeze:
        x, row_ids = x[None], row_ids[None]
        if row_cnt is not None:
            row_cnt = jnp.asarray(row_cnt).reshape(1)
    b, n, kdim = x.shape
    f = w.shape[1]
    assert n % block_rows == 0
    assert row_ids.shape[0] == b
    block_k = min(block_k, kdim)
    block_f = min(block_f, f)
    assert kdim % block_k == 0 and f % block_f == 0
    cr = row_ids.shape[-1]
    n_k = kdim // block_k
    grid = (b, cr, f // block_f, n_k)
    if row_cnt is None:
        # No occupancy info: treat every slot as live (legacy duplicated-
        # tail behavior would differ — with the guard always on, padded
        # slots compute the duplicate like before the guard existed; all
        # callers in-tree pass the real counts).
        row_cnt = jnp.full((b,), cr, jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_rows, block_k),
                             lambda bi, c, fi, ki, ids, cnt: (bi, ids[bi * cr + c], ki)),
                pl.BlockSpec((block_k, block_f),
                             lambda bi, c, fi, ki, ids, cnt: (ki, fi)),
            ],
            out_specs=pl.BlockSpec((1, block_rows, block_f),
                                   lambda bi, c, fi, ki, ids, cnt: (bi, c, fi)),
            scratch_shapes=[pltpu.VMEM((block_rows, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, cr * block_rows, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_gemm_q",
    )(row_ids.reshape(-1), row_cnt.reshape(-1).astype(jnp.int32), x, w)
    return out[0] if squeeze else out
