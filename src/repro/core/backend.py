"""Backend routing for the Update–Dispatch engine (paper Fig. 4 "engine").

One logical Dispatch step = GEMM-Q → sparse attention → GEMM-O, all driven
by a precomputed :class:`~repro.core.plan.DispatchPlan`.  Two
interchangeable implementations sit behind a common interface:

  * :class:`XlaBackend`   — the pjit/XLA structural path (capacity-padded
    gathers + one-hot scatters).  Multi-pod / GSPMD friendly; the dry-run
    and roofline tooling lower this one.
  * :class:`PallasBackend` — the paper-faithful Pallas TPU kernels
    (``flashomni_attention_csr`` + ``gemm_q_sparse_kernel`` +
    ``gemm_o_sparse_kernel``), chained through the COMPACT GEMM-Q layout:
    the ``(Cr·bm, F)`` live-row projection feeds the CSR attention kernel
    directly via ``plan.q_slots`` — no scatter between the two kernels.
    Batch is part of every kernel's GRID (attention folds it into the
    flattened ``B·H`` leading axis; the GEMMs carry a leading batch grid
    dimension over per-sample scalar-prefetched index lists), so one
    ``pallas_call`` covers the whole batch.  Off-TPU the kernels run with
    ``interpret=True`` so tests and CI exercise the exact same code path.

Selection lives on ``EngineConfig.backend``: ``"xla"`` | ``"pallas"`` |
``"auto"`` (Pallas on real TPUs, XLA elsewhere).

Truncation semantics are SHARED: when ``cap_kv`` can truncate a head's
KV-block list (``cap_kv < T_kv``) the XLA path switches from the per-head
union layout to the same per-row CSR lists the Pallas kernel consumes
(``plan.kv_row_ids``/``kv_row_cnt``), so both backends truncate each
row's KV list identically — parity holds under truncation, not just when
the capacity admits the full union (see ``tests/test_backend.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sparse_gemm
from repro.core.attention import (SparseAttentionSpec, dense_attention,
                                  sparse_attention_from_plan)
from repro.core.plan import DispatchPlan

__all__ = ["XlaBackend", "PallasBackend", "MeshBackend", "get_backend",
           "available_backends"]


class XlaBackend:
    """Structural-sparse XLA path over precomputed plan indices."""

    name = "xla"
    compact_q = False

    def dense_attention(self, q, k, v):
        """Full softmax attention (Update and engine-off steps)."""
        return dense_attention(q, k, v)

    def gemm_q(self, x: jax.Array, w: jax.Array, plan: DispatchPlan, *,
               block: int) -> jax.Array:
        """(B, N, d_in) @ (d_in, F) -> (B, N, F), zeros on cached rows."""
        plan = plan.widen()
        return sparse_gemm.gemm_q_from_plan(
            x, w, plan.row_ids, plan.row_cnt, block=block)

    def attention(self, q, k, v, o_reuse, plan: DispatchPlan,
                  spec: SparseAttentionSpec, *, scale: Optional[float] = None,
                  compact_q: bool = False) -> jax.Array:
        """q (B,H,N_q,dh) [compact when ``compact_q``], k/v/o_reuse full.

        The per-row CSR lists are passed alongside the union layout;
        ``sparse_attention_from_plan`` consumes them whenever ``cap_kv``
        can truncate, matching the Pallas kernel's per-row truncation."""
        plan = plan.widen()
        return sparse_attention_from_plan(
            q, k, v, o_reuse, plan.q_ids, plan.q_cnt, plan.kv_ids,
            plan.kv_cnt, plan.pair_live, spec, scale=scale,
            q_src_ids=plan.q_slots if compact_q else None,
            kv_row_ids=plan.kv_row_ids, kv_row_cnt=plan.kv_row_cnt,
            # Mesh-folded plans carry the pair clamp in kv_row_cnt only;
            # the union layout (which ignores it) must never be taken even
            # when cap_kv admits the full union — this is how the single-
            # device oracle consumes a mesh plan bit-identically.
            force_per_row=plan.shd_q_ids is not None)

    def gemm_o(self, o_tok, w, plan: DispatchPlan, bias: jax.Array, *,
               block: int,
               spec: Optional[SparseAttentionSpec] = None) -> jax.Array:
        """o_tok (B,N,H,dh), w (H,dh,F), bias (B,N,F) -> (B,N,F).

        ``plan.head_mask`` already carries any bucket-induced head clamp
        (folded back at Update time, see ``plan.gmo_layout``), so this
        path needs no bucket awareness to stay bit-consistent with the
        bucketed kernel."""
        plan = plan.widen()
        return sparse_gemm.gemm_o_from_plan(
            o_tok, w, plan.head_mask, plan.row_ids, plan.row_cnt, bias,
            block=block)


class PallasBackend:
    """Pallas kernel path (CSR attention + sparse GEMMs, layout-fused)."""

    name = "pallas"
    compact_q = True

    def __init__(self, interpret: Optional[bool] = None):
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret

    def dense_attention(self, q, k, v):
        """Full softmax attention by the blockwise kernel: no (N, N) score
        array, and at flux width on one v5e 2.06 ms a block against XLA's
        4.51 ms with the scores materialized."""
        from repro.kernels.dense_attention import flashomni_dense_attention
        return flashomni_dense_attention(q, k, v, interpret=self.interpret)

    def gemm_q(self, x: jax.Array, w: jax.Array, plan: DispatchPlan, *,
               block: int) -> jax.Array:
        """COMPACT (B, Cr·block, F) projection of the live row blocks.

        Batch is a kernel-grid dimension — ONE ``pallas_call`` covers the
        whole batch (ROADMAP item: no Python unroll over B)."""
        plan = plan.widen()
        from repro.kernels.gemm_q import gemm_q_sparse_kernel
        from repro.kernels.tuning import kernel_tiles
        tiles = kernel_tiles("gemm_q", x.shape[-1])
        return gemm_q_sparse_kernel(x, w, plan.row_ids, block_rows=block,
                                    block_k=tiles.get("block_k", 512),
                                    block_f=tiles.get("block_f", 512),
                                    row_cnt=plan.row_cnt,
                                    interpret=self.interpret)

    def attention(self, q, k, v, o_reuse, plan: DispatchPlan,
                  spec: SparseAttentionSpec, *, scale: Optional[float] = None,
                  compact_q: bool = False) -> jax.Array:
        plan = plan.widen()   # Pallas index maps require int32 scalar ids
        from repro.kernels.flashomni_attention import flashomni_attention_csr
        b, h, n_q, dh = q.shape
        n = o_reuse.shape[-2]
        flat = lambda a: a.reshape(b * h, *a.shape[2:])
        if spec.kv_buckets > 1 and plan.bkt_head is not None:
            # Occupancy-bucketed two-level grid: the layout rows fold the
            # head axis, so the plan's (B, R)/(B, S) fields stay unflattened.
            from repro.core.plan import bucket_geometry
            from repro.kernels.flashomni_attention import (
                flashomni_attention_csr_bucketed,
            )
            geometry = bucket_geometry(spec.cap_q, spec.cap_kv, h,
                                       spec.kv_buckets)
            out = flashomni_attention_csr_bucketed(
                flat(q), flat(k), flat(v), flat(o_reuse),
                plan.bkt_head, plan.bkt_q_ids,
                plan.bkt_q_slots if compact_q else plan.bkt_q_src,
                plan.bkt_kv_ids, plan.bkt_kv_cnt, geometry,
                heads=h, block_q=spec.block_q, block_kv=spec.block_kv,
                scale=scale, interpret=self.interpret)
            # No any_live guard needed: dead layout rows write only to the
            # trash pad; cached rows keep their aliased o_reuse values.
            return out.reshape(b, h, n, dh)
        out = flashomni_attention_csr(
            flat(q), flat(k), flat(v), flat(o_reuse),
            flat(plan.q_ids), flat(plan.kv_row_ids), flat(plan.kv_row_cnt),
            flat(plan.q_cnt), block_q=spec.block_q, block_kv=spec.block_kv,
            scale=scale, interpret=self.interpret,
            q_src_ids=flat(plan.q_slots) if compact_q else None)
        # Degenerate all-cached guard (paper A.1.1 S_q degradation): with
        # zero live rows the kernel leaves the duplicated slot-0 block
        # undefined; keep the pure-reuse tensor for those (b, h).
        any_live = (flat(plan.q_cnt) > 0)[:, None, None]
        out = jnp.where(any_live, out, flat(o_reuse))
        return out.reshape(b, h, n, dh)

    def gemm_o(self, o_tok, w, plan: DispatchPlan, bias: jax.Array, *,
               block: int,
               spec: Optional[SparseAttentionSpec] = None) -> jax.Array:
        """Batched in the kernel grid, like :meth:`gemm_q`.

        With ``spec.kv_buckets > 1`` and a plan carrying the ``gmo_*``
        layout, routes to the occupancy-bucketed two-level grid — the
        geometry is re-derived statically from the spec exactly as the
        plan build derived it, and the plan's ``head_cnt``/``head_mask``
        already fold the bucket clamp, so uniform vs bucketed stays
        bit-identical."""
        plan = plan.widen()
        from repro.kernels.tuning import kernel_tiles
        h = w.shape[0]
        tiles = kernel_tiles("gemm_o", h)
        block_f = tiles.get("block_f", 512)
        if spec is not None and spec.kv_buckets > 1 \
                and plan.gmo_rows is not None:
            from repro.core.plan import bucket_geometry
            from repro.kernels.gemm_o import gemm_o_sparse_bucketed_kernel
            cr = plan.row_ids.shape[-1]
            geometry = bucket_geometry(cr, h, 1, spec.kv_buckets)
            return gemm_o_sparse_bucketed_kernel(
                o_tok.transpose(0, 2, 1, 3), w, bias, plan.gmo_rows,
                plan.gmo_src, plan.gmo_head_ids, plan.gmo_head_cnt,
                geometry, block_rows=block, block_f=block_f,
                interpret=self.interpret)
        from repro.kernels.gemm_o import gemm_o_sparse_kernel
        return gemm_o_sparse_kernel(
            o_tok.transpose(0, 2, 1, 3), w, bias, plan.row_ids,
            plan.head_ids, plan.head_cnt, block_rows=block, block_f=block_f,
            interpret=self.interpret)


class MeshBackend:
    """Mesh-sharded dispatch: the inner backend runs per shard under a
    ``shard_map`` over the (data, seq) engine mesh, exchanging only the
    plan-live KV blocks (``distributed/plan_shard.py``).  Only attention
    needs explicit collectives.  XLA GEMM-Q/GEMM-O are left to GSPMD via
    the state specs; Pallas ones run per data shard under a ``shard_map``
    (weights replicated, batch on ``data``), because GSPMD cannot
    partition a compiled TPU kernel."""

    def __init__(self, inner, cfg):
        self.inner = inner
        self.cfg = cfg
        self.name = f"mesh-{inner.name}"
        self.compact_q = inner.compact_q

    def _per_data_shard(self, fn, x, w, plan, *rest):
        """``fn(x, w, plan, *rest)`` per data shard: every operand but the
        weights ``w`` leads with the batch axis."""
        if self.inner.name != "pallas":
            return fn(x, w, plan, *rest)
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_engine_mesh
        batch = P("data")
        specs = (batch, P(), jax.tree.map(lambda _: batch, plan),
                 *(batch for _ in rest))
        return shard_map(
            fn, mesh=make_engine_mesh(self.cfg.mesh_dp, self.cfg.mesh_sp),
            in_specs=specs, out_specs=batch, check_rep=False)(
                x, w, plan, *rest)

    def dense_attention(self, q, k, v):
        """Full softmax attention per shard: on the seq axis each chip takes
        its own query rows against every key, K and V gathered once
        (``fo.gather``); on the head axis each chip takes its heads."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_engine_mesh
        if self.cfg.mesh_axis == "head":
            spec = P("data", "seq", None, None)
            body = self.inner.dense_attention
        else:
            spec = P("data", None, "seq", None)

            def body(ql, kl, vl):
                with jax.named_scope("fo.gather"):
                    kf, vf = (jax.lax.all_gather(a, "seq", axis=2, tiled=True)
                              for a in (kl, vl))
                return self.inner.dense_attention(ql, kf, vf)
        return shard_map(
            body, mesh=make_engine_mesh(self.cfg.mesh_dp, self.cfg.mesh_sp),
            in_specs=(spec, spec, spec), out_specs=spec, check_rep=False)(
                q, k, v)

    def gemm_q(self, x, w, plan, *, block):
        return self._per_data_shard(
            lambda x, w, plan: self.inner.gemm_q(x, w, plan, block=block),
            x, w, plan)

    def attention(self, q, k, v, o_reuse, plan: DispatchPlan,
                  spec: SparseAttentionSpec, *, scale: Optional[float] = None,
                  compact_q: bool = False):
        from repro.distributed.plan_shard import mesh_attention
        return mesh_attention(self.inner, self.cfg, q, k, v, o_reuse, plan,
                              spec, scale=scale, compact_q=compact_q)

    def gemm_o(self, o_tok, w, plan, bias, *, block, spec=None):
        return self._per_data_shard(
            lambda o, w, plan, bias: self.inner.gemm_o(
                o, w, plan, bias, block=block, spec=spec),
            o_tok, w, plan, bias)


_XLA = XlaBackend()


def available_backends() -> tuple[str, ...]:
    return ("xla", "pallas", "auto")


def get_backend(cfg):
    """Resolve ``EngineConfig.backend`` to a backend instance.

    ``cfg.mesh_sp > 1`` wraps the resolved backend in :class:`MeshBackend`
    — the same Update→Dispatch flow, with attention running sharded."""
    name = cfg.backend
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "xla"
    if name == "xla":
        inner = _XLA
    elif name == "pallas":
        inner = PallasBackend(interpret=getattr(cfg, "interpret", None))
    else:
        raise ValueError(
            f"unknown engine backend {cfg.backend!r}; expected one of "
            f"{available_backends()}")
    if getattr(cfg, "mesh_sp", 1) > 1:
        return MeshBackend(inner, cfg)
    return inner
