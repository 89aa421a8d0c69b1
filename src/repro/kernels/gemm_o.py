"""FlashOmni GEMM-O — reduction-axis sparse output projection (paper §3.5,
Obs. 3, Eq. 3/4).

``Out_i = Σ_{h∈H_i} O_i^h W_h + OP_reuse(B_c)_i``: per live row block, only
the live heads are reduced; the cached heads' contribution arrives through
the Taylor-forecast bias ``B_c``.  The paper relaunches the kernel for its
two stages on GPU; on TPU both collapse into ONE kernel because the bias is
simply the accumulator's initial value (DESIGN §2.4).

Structure: grid ``(B, Cr, F_tiles, Hc)``, with per-row live-head CSR lists
in scalar memory (flattened over the batch, indexed ``b·Cr + c``) — batch
is a GRID dimension, so one ``pallas_call`` covers every sample (no Python
per-sample relaunch; unbatched inputs still accepted).  The bias tensor is
aliased to the output, so row blocks that are never visited (fully cached
rows) keep their forecast value — Eq. 4's "cache-then-reuse branch
terminates immediately" for free.

Occupancy-bucketed variant (:func:`gemm_o_sparse_bucketed_kernel`, the
paper's GEMM-O 2.5–3.8× territory): the uniform grid pays ``Hc`` (the max
live-head count) for EVERY row slot even when most rows keep 1–2 live
heads — the common case under per-head sparsity patterns.  The bucketed
grid is ``(B, F_tiles, S)`` with ``S = Σ rows_b·width_b`` over a
halving-depth ``bucket_geometry(Cr, H, 1, kv_buckets)``: row slots are
sorted by live-head count at Update time (``DispatchPlan.gmo_*``,
:func:`repro.core.plan.gmo_layout`) so a 1-head row occupies a 1-deep
reduction slot.  At ``B = 3`` buckets the grid shrinks to
``3/7 ≈ 0.43×`` the uniform slot count — a static bound.  Both variants
preserve the bias-as-accumulator-init trick and the padded-slot no-store
invariant; any bucket-induced head clamp is folded back into the plan's
``head_cnt`` lists, so bucketed and uniform outputs are bit-identical.

Tile shapes (``block_f``, and ``block_k``/``block_f`` for GEMM-Q) come
from the calibration table in :mod:`repro.kernels.tuning` — a JSON file
keyed per kernel kind and per bucket width class, populated by
``benchmarks/autotune.py`` and consulted by :mod:`repro.kernels.ops` /
:class:`repro.core.backend.PallasBackend`.  The checked-in default table
reproduces the hand-picked ``512`` tiles, so behavior without a sweep is
unchanged.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_o_sparse_kernel", "gemm_o_sparse_bucketed_kernel"]


def _kernel(row_ids_ref, head_ids_ref, head_cnt_ref,
            o_ref, w_ref, bias_ref, out_ref, acc_ref, *, cr: int, hc: int):
    bi, c, hh = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    slot = bi * cr + c

    @pl.when(hh == 0)
    def _init():
        acc_ref[...] = bias_ref[0].astype(jnp.float32)  # B_c as accumulator init

    @pl.when(hh < head_cnt_ref[slot])
    def _accum():
        acc_ref[...] += jax.lax.dot(
            o_ref[0, 0].astype(jnp.float32),
            w_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    # Padding slots (head_cnt == 0) duplicate the last live row id; they
    # must not store: with the bias-aliased output, re-initializing from
    # ``bias_ref`` would erase (interpret) or re-accumulate (TPU re-fetch
    # across f-tiles) the live slot's already-written result.
    @pl.when((hh == hc - 1) & (head_cnt_ref[slot] > 0))
    def _done():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def gemm_o_sparse_kernel(
    o_heads: jax.Array,    # (B, H, N, dh) or (H, N, dh) attention outputs
    w: jax.Array,          # (H, dh, F) output projection, per-head
    bias: jax.Array,       # (B, N, F) or (N, F) OP_reuse(B_c) — aliased to out
    row_ids: jax.Array,    # (B, Cr) or (Cr,) live row-block ids
    head_ids: jax.Array,   # (B, Cr, Hc) or (Cr, Hc) live head ids per row
    head_cnt: jax.Array,   # (B, Cr) or (Cr,)
    *,
    block_rows: int,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    squeeze = o_heads.ndim == 3
    if squeeze:
        o_heads, bias = o_heads[None], bias[None]
        row_ids, head_ids, head_cnt = row_ids[None], head_ids[None], head_cnt[None]
    b, h, n, dh = o_heads.shape
    f = w.shape[-1]
    assert n % block_rows == 0
    block_f = min(block_f, f)
    assert f % block_f == 0
    _, cr, hc = head_ids.shape
    grid = (b, cr, f // block_f, hc)
    flat_rows = row_ids.reshape(-1)
    flat_heads = head_ids.reshape(-1)
    flat_cnt = head_cnt.reshape(-1)

    def o_map(bi, c, fi, hh, rids, hids, hcnt):
        slot = bi * cr + c
        hh_c = jnp.maximum(jnp.minimum(hh, hcnt[slot] - 1), 0)
        return (bi, hids[slot * hc + hh_c], rids[slot], 0)

    def w_map(bi, c, fi, hh, rids, hids, hcnt):
        slot = bi * cr + c
        hh_c = jnp.maximum(jnp.minimum(hh, hcnt[slot] - 1), 0)
        return (hids[slot * hc + hh_c], 0, fi)

    def bias_map(bi, c, fi, hh, rids, hids, hcnt):
        return (bi, rids[bi * cr + c], fi)

    out = pl.pallas_call(
        functools.partial(_kernel, cr=cr, hc=hc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_rows, dh), o_map),
                pl.BlockSpec((1, dh, block_f), w_map),
                pl.BlockSpec((1, block_rows, block_f), bias_map),
            ],
            out_specs=pl.BlockSpec((1, block_rows, block_f), bias_map),
            scratch_shapes=[pltpu.VMEM((block_rows, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(bias.shape, bias.dtype),
        input_output_aliases={5: 0},                         # bias -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_gemm_o",
    )(flat_rows, flat_heads, flat_cnt, o_heads, w, bias)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Occupancy-bucketed variant — two-level (bucket × row-slot × Hc_b) grid
# ---------------------------------------------------------------------------

def _bucketed_kernel(srow_ref, jof_ref, soff_ref, slast_ref,
                     rows_ref, src_ref, hid_ref, cnt_ref,
                     o_ref, w_ref, bias_ref, out_ref, acc_ref):
    bi, s = pl.program_id(0), pl.program_id(2)
    r = srow_ref[s]

    @pl.when(jof_ref[s] == 0)
    def _init():
        acc_ref[...] = bias_ref[0].astype(jnp.float32)  # B_c as accumulator init

    @pl.when(jof_ref[s] < cnt_ref[bi, r])
    def _accum():
        acc_ref[...] += jax.lax.dot(
            o_ref[0, 0].astype(jnp.float32),
            w_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    # Store at the LAST slot of the row's bucket width (not at head_cnt-1:
    # the accumulation already finished, trailing slots are no-ops), and
    # only for slots with live heads — dead row slots write nothing, so
    # the bias-aliased output keeps their forecast value (they also map to
    # the trash block, see the wrapper).
    @pl.when((slast_ref[s] == 1) & (cnt_ref[bi, r] > 0))
    def _done():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def gemm_o_sparse_bucketed_kernel(
    o_heads: jax.Array,       # (B, H, N, dh) or (H, N, dh) attention outputs
    w: jax.Array,             # (H, dh, F) output projection, per-head
    bias: jax.Array,          # (B, N, F) or (N, F) OP_reuse(B_c) — aliased
    gmo_rows: jax.Array,      # (B, Cr) or (Cr,) write row id (dead → N//bm)
    gmo_src: jax.Array,       # (B, Cr) or (Cr,) read row id (dead → 0)
    gmo_head_ids: jax.Array,  # (B, S) or (S,) per-slot head id
    gmo_head_cnt: jax.Array,  # (B, Cr) or (Cr,) clamped live-head count
    geometry,                 # ((rows, width), ...) — bucket_geometry output
    *,
    block_rows: int,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Bucketed GEMM-O (see module docstring).

    Grid is ``(B, F_tiles, S)`` with ``S = Σ rows_b·width_b`` — the
    two-level bucket × row-slot × per-bucket-Hc structure flattened so
    consecutive grid steps walk one row's head reduction start-to-finish.
    The plan layout (``gmo_*``, sorted at Update time) is consumed
    verbatim; Dispatch jaxprs stay sort-free.  Dead row slots read row 0 /
    a clamped head (resident-block re-DMA, elided by Mosaic) and store to
    a one-block trash row appended past ``N``, sliced off after the call.
    """
    from repro.core.plan import bucket_slot_layout

    squeeze = o_heads.ndim == 3
    if squeeze:
        o_heads, bias = o_heads[None], bias[None]
        gmo_rows, gmo_src = gmo_rows[None], gmo_src[None]
        gmo_head_ids, gmo_head_cnt = gmo_head_ids[None], gmo_head_cnt[None]
    b, h, n, dh = o_heads.shape
    f = w.shape[-1]
    assert n % block_rows == 0
    block_f = min(block_f, f)
    assert f % block_f == 0
    cr = gmo_rows.shape[-1]
    srow, jof, soff, slast = bucket_slot_layout(geometry)
    s_total = int(srow.shape[0])
    assert int(sum(r for r, _ in geometry)) == cr, (geometry, cr)
    grid = (b, f // block_f, s_total)

    # One trash row block past the real tokens: dead row slots (head_cnt
    # == 0) write nothing, but their out block still flushes whatever the
    # revisited buffer holds — point it at the pad and slice it off.
    pad = jnp.zeros((b, block_rows, f), bias.dtype)
    bias_pad = jnp.concatenate([bias, pad], axis=1)

    def o_map(bi, fi, s, srow_r, jof_r, soff_r, slast_r, rows_r, src_r,
              hid_r, cnt_r):
        r = srow_r[s]
        jj = jnp.maximum(jnp.minimum(jof_r[s], cnt_r[bi, r] - 1), 0)
        return (bi, hid_r[bi, soff_r[s] + jj], src_r[bi, r], 0)

    def w_map(bi, fi, s, srow_r, jof_r, soff_r, slast_r, rows_r, src_r,
              hid_r, cnt_r):
        r = srow_r[s]
        jj = jnp.maximum(jnp.minimum(jof_r[s], cnt_r[bi, r] - 1), 0)
        return (hid_r[bi, soff_r[s] + jj], 0, fi)

    def bias_map(bi, fi, s, srow_r, jof_r, soff_r, slast_r, rows_r, src_r,
                 hid_r, cnt_r):
        return (bi, rows_r[bi, srow_r[s]], fi)

    out = pl.pallas_call(
        _bucketed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_rows, dh), o_map),
                pl.BlockSpec((1, dh, block_f), w_map),
                pl.BlockSpec((1, block_rows, block_f), bias_map),
            ],
            out_specs=pl.BlockSpec((1, block_rows, block_f), bias_map),
            scratch_shapes=[pltpu.VMEM((block_rows, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(bias_pad.shape, bias.dtype),
        # NB: alias indices count the scalar-prefetch operands too.
        input_output_aliases={10: 0},                        # bias_pad -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_gemm_o_bucketed",
    )(jnp.asarray(srow), jnp.asarray(jof), jnp.asarray(soff),
      jnp.asarray(slast), gmo_rows, gmo_src, gmo_head_ids, gmo_head_cnt,
      o_heads, w, bias_pad)
    out = out[:, :n]
    return out[0] if squeeze else out
