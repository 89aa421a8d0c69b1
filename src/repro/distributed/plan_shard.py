"""Plan-sharded mesh dispatch: per-shard CSR partitions + a plan-aware
collective schedule (ROADMAP direction 1 tentpole).

The paper's flagship Hunyuan cell (33K tokens) sits at a sequence length
where every production DiT engine goes multi-device (xDiT's USP: Ulysses
head-all-to-all + ring attention).  Torch engines ship DENSE collectives —
each shard all-gathers the full remote K/V regardless of sparsity.  Our
:class:`~repro.core.plan.DispatchPlan` already knows which KV blocks are
live per row, so the collectives here ship **only live blocks**: the
communication volume scales with density, extending the paper's
near-linear sparsity:speedup ratio across the network, not just the FLOPs.

Mesh model
----------
A ``(data, seq)`` mesh (:func:`repro.launch.mesh.make_engine_mesh`).  The
batch axis shards over ``data``.  The second axis runs one of two modes
(``EngineConfig.mesh_axis``):

* ``"head"`` — heads shard over ``seq``.  Attention is embarrassingly
  parallel per head; no collectives.  (Occupancy buckets fold the head
  axis into layout rows, so ``kv_buckets > 1`` is rejected here.)
  Bit parity holds on the Pallas backend (the kernel's flash accumulation
  order per (b, h) grid cell is shape-independent); the XLA backend is
  numerically equal but NOT bitwise — shrinking the head batch lets the
  compiler reassociate its reductions (observed max |Δ| ≈ 2e-8) — so the
  head-mode parity test pins Pallas bitwise and XLA to allclose.
* ``"seq"``  — tokens shard over ``seq``: K/V and the attention output
  live block-contiguously on their owner shard, Q stays replicated (it is
  already density-compacted, so its volume scales with sparsity).  This
  is the interesting mode; everything below describes it.

The plan-aware collective schedule
----------------------------------
All schedule tensors are computed at **Update** time inside
:func:`~repro.core.plan.build_dispatch_plan` (via :func:`partition_plan`)
and carried in the plan's ``shd_*`` fields — a Dispatch step's jaxpr stays
sort-free and consumes them verbatim, exactly like every other plan field.
Per (batch, head, destination shard ``p``):

1. **Row partition** — live q blocks are owned by ``q_id // q_bps``
   (``q_bps = T_q / P`` blocks per shard).  ``shd_q_ids`` / ``shd_q_src``
   / ``shd_q_slots`` / ``shd_q_cnt`` list shard ``p``'s live rows in the
   local / full / compact layouts (capacity ``min(cap_q, q_bps)``; the
   partition of a capacity-clamped set never truncates).
2. **Union + pair clamp** — the union of the rows' (truncation-folded) KV
   lists, split by owner shard ``s``, forms contiguous ascending runs.
   Each remote run is capped at ``pair_cap ≈ ⌈slack · cap_kv / P⌉``
   (``EngineConfig.mesh_pair_slack``); overflow is dropped lowest-need
   first and **folded back into ``kv_row_ids``/``kv_row_cnt``** before
   the bucket layout runs — the PR-4/PR-6 shared-truncation invariant, so
   the single-device oracle consumes the identical lists and sharded
   output stays bit-identical with no carve-outs.  Local blocks never
   ship (``pair_cap`` does not bound the ``s == p`` run).
3. **Exchange step list** — ``shd_send_ids[s, p]`` is the ascending list
   of local block indices shard ``s`` contributes to shard ``p``'s union:
   ONE ``jax.lax.all_to_all`` of ``(P, pair_cap)`` block payloads per
   K and V moves every pair's run (a ring ``ppermute`` schedule would
   move the same bytes in ``P−1`` steps; the single a2a keeps the
   Dispatch jaxpr's collective count static.  On TPU jaxlib ≥ 0.5 the
   ``jax.lax.ragged_all_to_all`` primitive could ship the exact per-pair
   counts with no ``pair_cap`` padding — noted as the upgrade path).
4. **Receive placement** — union slots are ascending, so each source's
   run is contiguous: ``shd_gather_idx`` maps union slot → index into
   ``concat([local K/V blocks, a2a payload])``, a single static gather.
   The gathered union (+ one zero pad block, so the buffer strictly
   exceeds the row-list capacity and the XLA backend takes the per-row
   CSR path) is the shard's KV buffer; ``shd_kv_row_ids`` are the rows'
   lists remapped to buffer slots, order-preserving, so the flash
   accumulation order — and therefore the bits — match the single-device
   kernel.

Communication accounting: the a2a payload is ``P · pair_cap`` blocks per
shard vs ``T_kv`` for the dense all-gather — at 25% density and default
slack the plan-aware exchange moves < 0.5× the dense bytes (CI-gated via
``launch/dryrun.py --sharded-gate``, which counts collective bytes in the
lowered HLO).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.symbols import active_indices, clamp_mask_topk, slot_positions

__all__ = [
    "ShardGeometry",
    "shard_geometry",
    "mesh_keep_rows",
    "partition_plan",
    "exchange_blocks",
    "dense_exchange_blocks",
    "mesh_attention",
]


class ShardGeometry(NamedTuple):
    """Static shapes of the per-shard partition (a function of the spec)."""

    mesh_sp: int    # P — shards on the seq axis
    q_bps: int      # q blocks per shard        (T_q / P)
    kv_bps: int     # kv blocks per shard       (T_kv / P)
    cap_q: int      # per-shard live-row capacity  min(cap_q, q_bps)
    cap_kv: int     # per-shard KV-union capacity  kv_bps + (P−1)·pair_cap
    pair_cap: int   # per-(src, dst) shipped-block capacity

    @property
    def buf_blocks(self) -> int:
        """KV buffer blocks per shard: local slice + full a2a payload."""
        return self.kv_bps + self.mesh_sp * self.pair_cap


def shard_geometry(spec, t_q: int, t_kv: int, mesh_sp: int,
                   pair_slack: float = 1.5) -> ShardGeometry:
    """Derive the static partition geometry; raises on indivisible grids."""
    if mesh_sp < 1:
        raise ValueError(f"mesh_sp must be >= 1, got {mesh_sp}")
    if t_q % mesh_sp or t_kv % mesh_sp:
        raise ValueError(
            f"seq mesh needs the block grid divisible by the shard count: "
            f"T_q={t_q}, T_kv={t_kv}, mesh_sp={mesh_sp}")
    q_bps = t_q // mesh_sp
    kv_bps = t_kv // mesh_sp
    # pair_cap scales with cap_kv (≈ density · T_kv): the wire volume is
    # where the sparsity:communication scaling comes from.  kv_bps is the
    # never-truncates safe bound (a remote slice has only kv_bps blocks).
    pair_cap = min(kv_bps, max(1, math.ceil(pair_slack * spec.cap_kv / mesh_sp)))
    # With slack ≥ 1 the union capacity admits every row list:
    # kv_bps + (P−1)·pair_cap ≥ cap_kv, so active_indices never truncates
    # the per-shard union — the pair clamp is the ONLY mesh truncation.
    cap_kv = min(t_kv, kv_bps + (mesh_sp - 1) * pair_cap)
    return ShardGeometry(mesh_sp=mesh_sp, q_bps=q_bps, kv_bps=kv_bps,
                         cap_q=min(spec.cap_q, q_bps), cap_kv=cap_kv,
                         pair_cap=pair_cap)


def exchange_blocks(geom: ShardGeometry) -> int:
    """a2a payload blocks received per shard (K or V; incl. the unused
    self slot — honest wire accounting, the diagonal pads the payload)."""
    return geom.mesh_sp * geom.pair_cap


def dense_exchange_blocks(t_kv: int) -> int:
    """Dense baseline: all-gather result blocks per shard (K or V)."""
    return t_kv


def _owner(ids: jax.Array, blocks_per_shard: int, mesh_sp: int) -> jax.Array:
    return jnp.clip(ids // blocks_per_shard, 0, mesh_sp - 1)


def mesh_keep_rows(rows: jax.Array, q_ids: jax.Array, q_cnt: jax.Array,
                   geom: ShardGeometry) -> jax.Array:
    """Fold the per-(dst, src) ``pair_cap`` clamp back into the row masks.

    ``rows``: (B, H, Cq, T_kv) bool per-live-row block mask (padding slots
    duplicate the last live row, matching ``active_indices`` semantics).
    For every destination shard, each remote source slice of its KV union
    is capped at ``pair_cap`` blocks, dropping the blocks needed by the
    fewest rows first (the same need-ranked rule as the union clamp in
    :func:`~repro.core.attention.attention_plan_indices`).  The clamp is
    applied to the ROWS — shared truncation: every backend, sharded or
    not, consumes the folded lists.  With ``pair_cap`` at its safe bound
    (``kv_bps``) this is the identity.
    """
    p_ = geom.mesh_sp
    cq = q_ids.shape[-1]
    own = _owner(q_ids, geom.q_bps, p_)                          # (B,H,Cq)
    valid = jnp.arange(cq, dtype=jnp.int32) < q_cnt[..., None]
    ownh = jax.nn.one_hot(jnp.where(valid, own, p_), p_ + 1,
                          dtype=jnp.int32)[..., :p_]             # (B,H,Cq,P)
    need = jnp.einsum("...cp,...ct->...pt", ownh,
                      rows.astype(jnp.int32))                    # (B,H,P,T_kv)
    um = need > 0
    shp = um.shape[:-1]
    um_r = um.reshape(*shp, p_, geom.kv_bps)                     # (...,Pd,Ps,kbps)
    keep_r = clamp_mask_topk(um_r, need.reshape(um_r.shape), geom.pair_cap)
    # The local slice never ships — it is exempt from the pair clamp.
    eye = jnp.eye(p_, dtype=bool)[:, :, None]
    keep_r = jnp.where(eye, um_r, keep_r)
    keep = keep_r.reshape(*shp, p_ * geom.kv_bps)                # (B,H,P,T_kv)
    keep_q = jnp.take_along_axis(
        keep, jnp.broadcast_to(own[..., None], rows.shape), axis=-2)
    return rows & keep_q


def partition_plan(q_ids: jax.Array, q_cnt: jax.Array, q_slots: jax.Array,
                   kv_row_ids: jax.Array, kv_row_cnt: jax.Array,
                   t_kv: int, geom: ShardGeometry) -> dict:
    """Emit the per-shard CSR partition + collective schedule (``shd_*``).

    Inputs are the plan's (truncation-final) attention index fields —
    runs at Update time only, AFTER :func:`mesh_keep_rows` and the bucket
    layout folded their truncations into ``kv_row_cnt``, so every per-pair
    run is already within ``pair_cap`` and nothing here can truncate.
    """
    p_ = geom.mesh_sp
    b_, h_, cq = q_ids.shape
    ck0 = kv_row_ids.shape[-1]
    own = _owner(q_ids, geom.q_bps, p_)
    valid = jnp.arange(cq, dtype=jnp.int32) < q_cnt[..., None]
    # --- row partition: shard p's live rows, in global slot order ---
    pmask = (own[..., None, :] == jnp.arange(p_, dtype=jnp.int32)[:, None]) \
        & valid[..., None, :]                                    # (B,H,P,Cq)
    sel, shd_q_cnt = active_indices(pmask, geom.cap_q)           # (B,H,P,Cqs)
    bc = lambda a: jnp.broadcast_to(a[..., None, :], (b_, h_, p_, cq))
    gsel = lambda a: jnp.take_along_axis(bc(a), sel, axis=-1)
    shd_q_src = gsel(q_ids)
    shd_q_slots = gsel(q_slots)
    shd_q_ids = jnp.clip(
        shd_q_src - jnp.arange(p_, dtype=jnp.int32)[:, None] * geom.q_bps,
        0, geom.q_bps - 1)
    rl = jnp.take_along_axis(
        jnp.broadcast_to(kv_row_ids[..., None, :, :], (b_, h_, p_, cq, ck0)),
        sel[..., None], axis=-2)                                 # (B,H,P,Cqs,Ck0)
    rc = gsel(kv_row_cnt)                                        # (B,H,P,Cqs)
    # --- per-shard KV union (membership scatter; ascending ids) ---
    svalid = jnp.arange(geom.cap_q, dtype=jnp.int32) < shd_q_cnt[..., None]
    jlive = (jnp.arange(ck0, dtype=jnp.int32) < rc[..., None]) \
        & svalid[..., None]
    ids_m = jnp.where(jlive, rl, t_kv).reshape(b_, h_, p_, -1)
    um = jnp.put_along_axis(
        jnp.zeros((b_, h_, p_, t_kv + 1), jnp.int32), ids_m,
        jnp.ones_like(ids_m), axis=-1, inplace=False)[..., :t_kv] > 0
    shd_kv_ids, shd_kv_cnt = active_indices(um, geom.cap_kv)     # (B,H,P,Cks)
    # --- remap row lists to union-buffer slots (order-preserving) ---
    slot_of = slot_positions(shd_kv_ids, shd_kv_cnt, t_kv)       # (B,H,P,t_kv)
    shd_kv_row_ids = jnp.take_along_axis(
        slot_of, rl.reshape(b_, h_, p_, -1), axis=-1).reshape(rl.shape)
    # --- receive placement: union slot -> concat([local, a2a payload]) ---
    sown = _owner(shd_kv_ids, geom.kv_bps, p_)                   # (B,H,P,Cks)
    cvalid = jnp.arange(geom.cap_kv, dtype=jnp.int32) < shd_kv_cnt[..., None]
    ownh = jax.nn.one_hot(jnp.where(cvalid, sown, p_), p_ + 1,
                          dtype=jnp.int32)[..., :p_]             # (B,H,P,Cks,P)
    cnt_src = jnp.einsum("...cs->...s", ownh)                    # (B,H,Pd,Ps)
    starts = jnp.cumsum(cnt_src, axis=-1) - cnt_src              # exclusive
    pos = jnp.arange(geom.cap_kv, dtype=jnp.int32) \
        - jnp.take_along_axis(starts, sown, axis=-1)             # run position
    pself = jnp.arange(p_, dtype=jnp.int32)[:, None]
    shd_gather_idx = jnp.clip(
        jnp.where(sown == pself, shd_kv_ids - pself * geom.kv_bps,
                  geom.kv_bps + sown * geom.pair_cap + pos),
        0, geom.buf_blocks - 1)
    # --- send tables: ascending local ids per (src, dst) pair run ---
    um_r = um.reshape(b_, h_, p_, p_, geom.kv_bps) \
        & ~jnp.eye(p_, dtype=bool)[:, :, None]                   # no self-ship
    send_ids_d, send_cnt_d = active_indices(um_r, geom.pair_cap)
    return dict(
        shd_q_ids=shd_q_ids, shd_q_src=shd_q_src, shd_q_slots=shd_q_slots,
        shd_q_cnt=shd_q_cnt, shd_kv_ids=shd_kv_ids, shd_kv_cnt=shd_kv_cnt,
        shd_kv_row_ids=shd_kv_row_ids, shd_kv_row_cnt=rc,
        shd_gather_idx=shd_gather_idx,
        shd_send_ids=jnp.swapaxes(send_ids_d, 2, 3),             # (B,H,Psrc,Pdst,pc)
        shd_send_cnt=jnp.swapaxes(send_cnt_d, 2, 3))


# ---------------------------------------------------------------------------
# Dispatch-time sharded attention (shard_map over the engine mesh).
# ---------------------------------------------------------------------------

def _dummy_plan_tail(b_l: int, dtype=jnp.int32) -> dict:
    """GEMM-side plan fields the attention backends never read."""
    z = jnp.zeros((b_l, 1), dtype)
    return dict(row_ids=z, row_cnt=jnp.zeros((b_l,), dtype),
                head_ids=jnp.zeros((b_l, 1, 1), dtype),
                head_cnt=z, head_mask=jnp.zeros((b_l, 1, 1), bool),
                m_ch=jnp.zeros((b_l, 1, 1), bool),
                row_score=jnp.zeros((b_l, 1), jnp.float32))


def mesh_attention(inner, cfg, q, k, v, o_reuse, plan, spec, *,
                   scale: Optional[float] = None,
                   compact_q: bool = False) -> jax.Array:
    """shard_map-wrapped sparse attention over the ``(data, seq)`` mesh.

    ``inner`` is the single-device backend (XLA or Pallas) — the SAME
    per-row CSR code path runs inside each shard over the gathered KV
    buffer, with the row lists at their original capacity width, which is
    what makes sharded output bit-identical to the single-device oracle.
    GEMM-Q/GEMM-O stay outside the shard_map (batch-sharded / GSPMD-
    propagated); only attention exchanges KV.
    """
    from repro.core.attention import SparseAttentionSpec
    from repro.core.plan import DispatchPlan
    from repro.launch.mesh import make_engine_mesh

    plan = plan.widen()
    b, h, n_q, dh = q.shape
    n = o_reuse.shape[-2]
    if b % cfg.mesh_dp:
        raise ValueError(f"batch {b} not divisible by mesh_dp={cfg.mesh_dp}")
    if cfg.mesh_axis == "head":
        return _head_sharded(inner, cfg, q, k, v, o_reuse, plan, spec,
                             scale=scale, compact_q=compact_q)
    if plan.shd_q_ids is None:
        raise ValueError("seq-mode mesh dispatch needs a plan built with "
                         "mesh_sp > 1 (shd_* fields missing)")
    mesh = make_engine_mesh(cfg.mesh_dp, cfg.mesh_sp)
    p_ = cfg.mesh_sp
    bk = spec.block_kv
    kv_bps = (n // bk) // p_
    pair_cap = plan.shd_send_ids.shape[-1]
    ck_s = plan.shd_kv_ids.shape[-1]
    cq_s = plan.shd_q_ids.shape[-1]
    ck0 = plan.shd_kv_row_ids.shape[-1]
    # cap_kv keeps the ORIGINAL row-list width ck0 (≤ union capacity by
    # the slack ≥ 1 guarantee), so the inner per-row math — gather widths,
    # live mask, softmax reduction — has the exact shapes of the single-
    # device oracle.  The buffer carries ck_s + 1 blocks (one zero pad),
    # strictly more than ck0, so the XLA path takes the per-row CSR branch.
    inner_spec = SparseAttentionSpec(block_q=spec.block_q, block_kv=bk,
                                     cap_q=cq_s, cap_kv=ck0, kv_buckets=1)

    def body(qf, kl, vl, ol, qi, qs, qc, ri, rc, gi, si):
        b_l = ol.shape[0]
        sq = lambda a: a[:, :, 0]                      # squeeze the P axis
        kb = kl.reshape(b_l, h, kv_bps, bk, dh)
        vb = vl.reshape(b_l, h, kv_bps, bk, dh)
        send = sq(si).reshape(b_l, h, p_ * pair_cap)

        def gather(blocks, ids):
            return jnp.take_along_axis(blocks, ids[..., None, None], axis=2)

        def a2a(x):
            x = x.reshape(b_l, h, p_, pair_cap, bk, dh)
            y = jax.lax.all_to_all(x, "seq", split_axis=2, concat_axis=2)
            return y.reshape(b_l, h, p_ * pair_cap, bk, dh)

        pad = jnp.zeros((b_l, h, 1, bk, dh), kl.dtype)

        def buffer(blocks):
            buf = jnp.concatenate([blocks, a2a(gather(blocks, send))], axis=2)
            union = gather(buf, sq(gi))
            return jnp.concatenate([union, pad], axis=2) \
                .reshape(b_l, h, (ck_s + 1) * bk, dh)

        kx, vx = buffer(kb), buffer(vb)
        pv = DispatchPlan(
            q_ids=sq(qi), q_cnt=sq(qc), q_slots=sq(qs),
            kv_ids=jnp.zeros((b_l, h, 1), jnp.int32),
            kv_cnt=jnp.zeros((b_l, h), jnp.int32),
            pair_live=jnp.zeros((b_l, h, cq_s, 1), bool),
            kv_row_ids=sq(ri), kv_row_cnt=sq(rc), **_dummy_plan_tail(b_l))
        # compact_q=True always: the read layout (full or compact) is baked
        # into q_slots above; q_ids stay the shard-LOCAL output blocks.
        return inner.attention(qf, kx, vx, ol, pv, inner_spec, scale=scale,
                               compact_q=True)

    d, s = "data", "seq"
    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(d, None, None, None),                 # q (replicated on seq)
                  P(d, None, s, None), P(d, None, s, None),
                  P(d, None, s, None),                    # k, v, o_reuse on N
                  P(d, None, s, None), P(d, None, s, None), P(d, None, s),
                  P(d, None, s, None, None), P(d, None, s, None),
                  P(d, None, s, None), P(d, None, s, None, None)),
        out_specs=P(d, None, s, None), check_rep=False)
    src = plan.shd_q_slots if compact_q else plan.shd_q_src
    return f(q, k, v, o_reuse, plan.shd_q_ids, src, plan.shd_q_cnt,
             plan.shd_kv_row_ids, plan.shd_kv_row_cnt, plan.shd_gather_idx,
             plan.shd_send_ids)


def _head_sharded(inner, cfg, q, k, v, o_reuse, plan, spec, *,
                  scale, compact_q):
    """Head-parallel mode: shard H over ``seq``; no collectives at all."""
    from repro.core.plan import DispatchPlan
    from repro.launch.mesh import make_engine_mesh

    h = q.shape[1]
    if h % cfg.mesh_sp:
        raise ValueError(f"heads {h} not divisible by mesh_sp={cfg.mesh_sp}")
    if spec.kv_buckets > 1:
        raise ValueError("mesh_axis='head' cannot shard the bucketed layout "
                         "(bucket rows fold the head axis); use mesh_axis="
                         "'seq' or kv_buckets=1")
    mesh = make_engine_mesh(cfg.mesh_dp, cfg.mesh_sp)

    def body(qh, kh, vh, oh, qi, qc, qs, ki, kc, pl, ri, rc):
        pv = DispatchPlan(q_ids=qi, q_cnt=qc, q_slots=qs, kv_ids=ki,
                          kv_cnt=kc, pair_live=pl, kv_row_ids=ri,
                          kv_row_cnt=rc, **_dummy_plan_tail(qh.shape[0]))
        return inner.attention(qh, kh, vh, oh, pv, spec, scale=scale,
                               compact_q=compact_q)

    d, s = "data", "seq"
    h4 = P(d, s, None, None)
    f = shard_map(
        body, mesh=mesh,
        in_specs=(h4, h4, h4, h4,
                  P(d, s, None), P(d, s), P(d, s, None),
                  P(d, s, None), P(d, s), P(d, s, None, None),
                  P(d, s, None, None), P(d, s, None)),
        out_specs=h4, check_rep=False)
    return f(q, k, v, o_reuse, plan.q_ids, plan.q_cnt, plan.q_slots,
             plan.kv_ids, plan.kv_cnt, plan.pair_live,
             plan.kv_row_ids, plan.kv_row_cnt)
