"""FlashOmni sparse GEMMs — XLA structural path (paper §3.5, Obs. 2/3, Eq. 3-4).

GEMM-Q (query projection, spatial-axis sparsity)
    RMSNorm and RoPE are token-local, so if block ``i``'s attention output
    is cached for every head, its query projection row-block is dead code.
    The structural path gathers the live row blocks (capacity padded),
    projects only those, and scatters into a zero output.

GEMM-O (output projection, reduction-axis sparsity)
    ``Out_i = Σ_h O_i^h W_h``; heads cached for block ``i`` contribute the
    pre-computed bias  B_c[i] = Σ_{h∉H_i} Õ_i^h W_h  (refreshed at Update).
    Because OP_reuse is element-wise linear (TaylorSeer), forecasting
    commutes with the projection (Eq. 4), so at Dispatch the bias is simply
    Taylor-forecast in *output* space and added to the live-head partial
    GEMM.  Rows whose heads are ALL cached skip the GEMM entirely
    (spatial gather, as in GEMM-Q); intra-row head sparsity is masked in
    this XLA path and structurally skipped in the Pallas kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attention import scatter_blocks
from repro.core.symbols import active_indices

__all__ = [
    "gemm_q_sparse",
    "gemm_q_from_plan",
    "gemm_o_update_bias",
    "gemm_o_sparse",
    "gemm_o_from_plan",
    "rows_any_head_live",
]


def _gather_rows(xb: jax.Array, ids: jax.Array) -> jax.Array:
    # Size-1 trailing index dims: whole-block copies (attention._gather_blocks).
    return jnp.take_along_axis(xb, ids[..., None, None], axis=-3)


def gemm_q_from_plan(
    x: jax.Array,
    w: jax.Array,
    ids: jax.Array,
    cnt: jax.Array,
    *,
    block: int,
    bias: Optional[jax.Array] = None,
    compact: bool = False,
) -> jax.Array:
    """Row-block-sparse ``x @ w`` over PRECOMPUTED live-row indices.

    ``ids``/``cnt`` from :func:`repro.core.symbols.active_indices` (or a
    :class:`~repro.core.plan.DispatchPlan`).  When ``compact`` the gathered
    projection is returned in slot order, shape (..., cap·block, d_out),
    without the scatter (the Pallas layout-fusion contract); otherwise it
    is scattered to full shape with zeros on cached rows.
    """
    n, d_in = x.shape[-2], x.shape[-1]
    t = n // block
    xb = x.reshape(*x.shape[:-2], t, block, d_in)
    xg = _gather_rows(xb, ids)                                  # (..., cap, block, d_in)
    yg = jnp.einsum("...cbd,df->...cbf", xg, w)
    if bias is not None:
        yg = yg + bias
    if compact:
        return yg.reshape(*x.shape[:-2], ids.shape[-1] * block, w.shape[-1])
    outb = jnp.zeros((*x.shape[:-2], t, block, w.shape[-1]), yg.dtype)
    outb = scatter_blocks(outb, ids, cnt, yg)
    return outb.reshape(*x.shape[:-1], w.shape[-1])


def gemm_q_sparse(
    x: jax.Array,
    w: jax.Array,
    m_rows: jax.Array,
    *,
    block: int,
    cap: int,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Row-block-sparse ``x @ w`` (mask-level entry; decodes indices).

    x: (..., N, d_in); w: (d_in, d_out); m_rows: (..., T) with T = N//block,
    True = row block is live.  Cached row blocks produce zeros (their Q is
    never consumed — their attention output comes from cache).
    """
    ids, cnt = active_indices(m_rows, cap)
    return gemm_q_from_plan(x, w, ids, cnt, block=block, bias=bias)


def rows_any_head_live(m_ch: jax.Array) -> jax.Array:
    """(..., T, H) per-(block, head) compute mask -> (..., T) block-live mask."""
    return jnp.any(m_ch, axis=-1)


def gemm_o_update_bias(
    o_heads: jax.Array,
    w: jax.Array,
    m_ch: jax.Array,
    *,
    block: int,
) -> jax.Array:
    """Update-step stage 1: cache bias ``B_c = Σ_{h∉H_i} O_i^h W_h``.

    o_heads: (..., N, H, dh); w: (H, dh, d_out); m_ch: (..., T, H).
    Returns (..., N, d_out) — zero on rows whose every head is live.
    """
    n = o_heads.shape[-3]
    t = n // block
    cached = ~m_ch                                              # heads NOT recomputed
    per_tok = jnp.repeat(cached, block, axis=-2)[..., :n, :]    # (..., N, H)
    contrib = jnp.einsum("...nhd,hdf->...nhf", o_heads, w)
    return jnp.sum(jnp.where(per_tok[..., None], contrib, 0), axis=-2)


def gemm_o_from_plan(
    o_heads: jax.Array,
    w: jax.Array,
    head_mask: jax.Array,
    ids: jax.Array,
    cnt: jax.Array,
    bias_forecast: jax.Array,
    *,
    block: int,
) -> jax.Array:
    """Dispatch-step GEMM-O over PRECOMPUTED indices.

    o_heads: (..., N, H, dh); w: (H, dh, d_out); ``ids``/``cnt`` are the
    live-row list and ``head_mask`` (..., cap, H) the per-live-row live-head
    mask — both straight from a :class:`~repro.core.plan.DispatchPlan`.

    Under ``kv_buckets > 1`` the plan's ``head_mask`` already carries the
    bucket-induced head clamp (folded back at Update time by
    ``plan.gmo_layout``), so this path consumes the same truncated head
    lists as the bucketed Pallas kernel — the ISSUE-8 no-carve-outs
    bit-consistency invariant needs no bucket awareness here.
    """
    n, h, dh = o_heads.shape[-3], o_heads.shape[-2], o_heads.shape[-1]
    t = n // block
    d_out = w.shape[-1]
    ob = o_heads.reshape(*o_heads.shape[:-3], t, block, h, dh)
    og = jnp.take_along_axis(ob, ids[..., None, None, None],
                             axis=-4)                           # (..., cap, block, H, dh)
    og = jnp.where(head_mask[..., None, :, None], og, 0)        # mask cached heads
    yg = jnp.einsum("...cbhd,hdf->...cbf", og, w)
    outb = jnp.zeros((*o_heads.shape[:-3], t, block, d_out), yg.dtype)
    outb = scatter_blocks(outb, ids, cnt, yg)
    out = outb.reshape(*o_heads.shape[:-3], n, d_out)
    return out + bias_forecast


def gemm_o_sparse(
    o_heads: jax.Array,
    w: jax.Array,
    m_ch: jax.Array,
    bias_forecast: jax.Array,
    *,
    block: int,
    cap: int,
) -> jax.Array:
    """Dispatch-step GEMM-O (mask-level entry; decodes indices per call).

    o_heads: (..., N, H, dh); w: (H, dh, d_out); m_ch: (..., T, H);
    bias_forecast = OP_reuse(B_c): (..., N, d_out).
    Fully cached row blocks cost zero GEMM FLOPs (spatial gather).
    """
    live_rows = rows_any_head_live(m_ch)                        # (..., T)
    ids, cnt = active_indices(live_rows, cap)
    mh = jnp.take_along_axis(m_ch, ids[..., None], axis=-2)     # (..., cap, H)
    return gemm_o_from_plan(o_heads, w, mh, ids, cnt, bias_forecast,
                            block=block)
