"""Occupancy-bucketed CSR attention grid tests (ISSUE 6 acceptance).

  * static bucket geometry: halving widths, equal slot area per bucket,
    rows partition exactly, slot total ≤ 0.5× the uniform grid at B = 3;
  * interpret-mode BIT parity: the bucketed two-level-grid kernel equals
    one online-softmax update a tile, in ascending id order, over the
    (bucket-truncated) per-row counts it shares with the uniform CSR
    kernel — the PR-4 shared-truncation invariant extended to buckets, no
    carve-outs — and agrees with the uniform kernel, which fuses its
    updates by groups, to f32 rounding; on skewed/bimodal plans including
    the adversarial one full-capacity row among empties;
  * oracle parity: on plans where no bucket truncates, the bucketed
    kernel matches ``masked_block_attention`` within 1e-6;
  * XLA parity: ``XlaBackend`` consumes the bucketed plan's scattered-back
    ``kv_row_cnt`` and agrees with the kernel;
  * strategy emissions (``multi-granularity``, ``hunyuan-1.5x``) run the
    full Update→Dispatch round-trip under ``kv_buckets=3`` on both
    backends, and ``plan_from_state`` rebuilds the bucketed plan fields
    bit-exactly (deterministic Update-time ``lax.sort`` assignment);
  * ``widen()`` round-trips the int16-compacted bucket id fields;
  * serving: two near-miss ``shape_key``s fold into ONE bucketed lane
    partition (≤ 4 executables) with per-request outputs bit-identical to
    sequential runs of the same padded requests, sliced back.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _csr_oracle import _tile_by_tile
from repro.configs.registry import get_smoke
from repro.core import (AttnParams, EngineConfig, MaskConfig, dispatch_layer,
                        init_layer_state, plan_from_state, update_layer)
from repro.core.attention import masked_block_attention
from repro.core.backend import PallasBackend, XlaBackend
from repro.core.masks import MaskConfig
from repro.core.plan import (bucket_geometry, bucket_grid_slots,
                             bucket_slot_layout, build_dispatch_plan)
from repro.launch.batching import ContinuousBatcher, Request, run_sequential
from repro.models import dit

N_TEXT = 64


# ---------------------------------------------------------------------------
# Static geometry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap_q,cap_kv,heads,nb", [
    (8, 8, 4, 3), (8, 16, 4, 3), (16, 32, 8, 3), (5, 7, 3, 3), (8, 16, 4, 2),
])
def test_bucket_geometry_partitions_rows(cap_q, cap_kv, heads, nb):
    geo = bucket_geometry(cap_q, cap_kv, heads, nb)
    rows = [r for r, _ in geo]
    widths = [w for _, w in geo]
    assert sum(rows) == heads * cap_q
    assert all(r >= 1 for r in rows)
    # Halving widths, widest first.
    assert widths == [-(-cap_kv // (1 << i)) for i in range(len(geo))]
    # Per-slot decode arrays cover every slot exactly once, in row order.
    srow, j_of, soff, slast = bucket_slot_layout(geo)
    assert len(srow) == bucket_grid_slots(geo)
    assert int(slast.sum()) == heads * cap_q     # one finalize per row
    np.testing.assert_array_equal(np.sort(np.unique(srow)),
                                  np.arange(heads * cap_q))


def test_bucket_geometry_three_buckets_halve_grid():
    """B = 3 equal-area buckets give a 3/7 ≈ 0.43 slot ratio — the ≥ 2×
    grid-slot cut the ISSUE acceptance requires, by construction."""
    for cap_q, cap_kv, heads in [(8, 8, 4), (8, 16, 4), (16, 64, 8)]:
        geo = bucket_geometry(cap_q, cap_kv, heads, 3)
        assert bucket_grid_slots(geo) * 2 <= heads * cap_q * cap_kv


def test_bucket_geometry_degenerate_single_bucket():
    geo = bucket_geometry(8, 16, 4, 1)
    assert geo == ((32, 16),)
    assert bucket_grid_slots(geo) == 32 * 16


# ---------------------------------------------------------------------------
# Kernel parity on skewed plans
# ---------------------------------------------------------------------------

def _cfgs(kv_buckets=3, **kw):
    mk = dict(pool=32, block_q=16, block_kv=16, interval=4, order=1,
              warmup_steps=1)
    cfg_b = EngineConfig(mask=MaskConfig(**mk), cap_q_frac=1.0,
                         cap_kv_frac=1.0, cache_dtype=jnp.float32,
                         kv_buckets=kv_buckets, **kw)
    cfg_u = dataclasses.replace(cfg_b, kv_buckets=1)
    return cfg_b, cfg_u


def _qkvo(seed, b, h, n, dh):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, h, n, dh)),
            jax.random.normal(ks[1], (b, h, n, dh)),
            jax.random.normal(ks[2], (b, h, n, dh)),
            jax.random.normal(ks[3], (b, h, n, dh)))


def _parity(m_c, m_s, *, seed=0, n=256, dh=32):
    """Bucketed kernel vs one tile an update in ascending order (BIT
    equal) vs the uniform kernel on the shared truncated counts (to f32
    rounding: it fuses its walk's updates by groups) vs XLA on the
    bucketed plan (allclose)."""
    b, h, t = m_c.shape
    cfg_b, cfg_u = _cfgs()
    q, k, v, o_reuse = _qkvo(seed, b, h, n, dh)
    plan_b = build_dispatch_plan(m_c, m_s, cfg_b, n)
    plan_u = build_dispatch_plan(m_c, m_s, cfg_u, n)
    spec_b, spec_u = cfg_b.caps(n), cfg_u.caps(n)
    pb = PallasBackend(interpret=True)
    out_bkt = pb.attention(q, k, v, o_reuse, plan_b, spec_b)
    # Same truncated per-row counts through the UNIFORM layout: the
    # shared-truncation invariant makes the two compute the same tiles.
    shared = plan_u._replace(kv_row_cnt=plan_b.kv_row_cnt).widen()
    flat = lambda a: np.asarray(a).reshape(b * h, *a.shape[2:])
    want = _tile_by_tile(flat(q), flat(k), flat(v), flat(o_reuse),
                         flat(shared.q_ids), flat(shared.kv_row_ids),
                         flat(shared.kv_row_cnt), flat(shared.q_cnt),
                         spec_u.block_q, flat(shared.q_ids))
    np.testing.assert_array_equal(flat(out_bkt), want)
    out_uni = pb.attention(q, k, v, o_reuse, shared, spec_u)
    np.testing.assert_allclose(np.asarray(out_bkt), np.asarray(out_uni),
                               atol=2e-5, rtol=2e-5)
    out_xla = XlaBackend().attention(q, k, v, o_reuse, plan_b, spec_b)
    np.testing.assert_allclose(np.asarray(out_bkt), np.asarray(out_xla),
                               atol=2e-5, rtol=2e-5)
    return out_bkt, plan_b, plan_u


def test_bucketed_bimodal_across_heads_bit_parity():
    """Hunyuan-like skew: two dense heads, two diagonal-only heads."""
    b, h, t = 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    m_c = jax.random.bernoulli(ks[0], 0.7, (b, h, t))
    m_s = jax.random.bernoulli(ks[1], 0.8, (b, h, t, t))
    diag = jnp.eye(t, dtype=bool)
    m_s = m_s.at[:, 2:].set(jnp.broadcast_to(diag, (b, 2, t, t)))
    m_s = m_s.at[..., 0].set(True)
    _parity(m_c, m_s, seed=2)


def test_bucketed_adversarial_one_full_row_oracle():
    """One full-capacity row among (near-)empty rows: the single wide row
    must land in the wide bucket — no truncation — so the bucketed kernel
    matches the dense oracle within 1e-6 on top of the bit parity."""
    b, h, t = 1, 4, 8
    diag = jnp.eye(t, dtype=bool)
    m_s = jnp.broadcast_to(diag, (b, h, t, t))
    m_s = m_s.at[0, 1, 3].set(True)            # the one full-width row
    m_s = m_s.at[..., 0].set(True)
    m_c = jnp.ones((b, h, t), bool)
    m_c = m_c.at[0, 0, 4:].set(False)          # plus some cached rows
    out_bkt, plan_b, plan_u = _parity(m_c, m_s, seed=3)
    q, k, v, o_reuse = _qkvo(3, b, h, t * 32, 32)
    # No bucket truncated: the scattered-back counts equal the uniform
    # plan's (block_kv-granularity) per-row counts.
    np.testing.assert_array_equal(np.asarray(plan_b.kv_row_cnt),
                                  np.asarray(plan_u.kv_row_cnt))
    # The masks are pool-granularity (pool = 32); the oracle consumes them
    # at that block size — identical semantics to the kernel's 16-block
    # expansion of the same cells.
    want = masked_block_attention(q, k, v, m_c, m_s, o_reuse,
                                  block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(out_bkt), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_bucketed_truncation_is_shared():
    """Overloaded wide rows DO truncate (more full rows than wide slots);
    the truncated counts are scattered back so the tile-by-tile parity
    still holds bit-for-bit and the uniform-kernel and XLA parity within
    tolerance."""
    b, h, t = 1, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 1)
    m_s = jax.random.bernoulli(ks[0], 0.9, (b, h, t, t))
    m_s = m_s.at[..., 0].set(True)             # most rows near-full
    m_c = jnp.ones((b, h, t), bool)
    _, plan_b, plan_u = _parity(m_c, m_s, seed=4)
    assert int(jnp.sum(plan_u.kv_row_cnt - plan_b.kv_row_cnt)) > 0, \
        "plan should truncate on this workload"


# ---------------------------------------------------------------------------
# Strategy emissions under kv_buckets: full engine round-trip + rebuild
# ---------------------------------------------------------------------------

def _engine_setup(strategy, backend, kv_buckets=3):
    key = jax.random.PRNGKey(0)
    B, H, N, dm, dh = 1, 4, 256, 64, 32
    cfg = EngineConfig(
        mask=MaskConfig(pool=32, block_q=16, block_kv=16, interval=4,
                        order=1, warmup_steps=1, tau_kv=0.15, tau_q=0.5),
        cap_q_frac=1.0, cap_kv_frac=1.0, cache_dtype=jnp.float32,
        backend=backend, strategy=strategy, kv_buckets=kv_buckets,
        interpret=True if backend == "pallas" else None)
    ks = jax.random.split(key, 8)
    p = AttnParams(
        wq=jax.random.normal(ks[0], (dm, H * dh)) * 0.05,
        wk=jax.random.normal(ks[1], (dm, H * dh)) * 0.05,
        wv=jax.random.normal(ks[2], (dm, H * dh)) * 0.05,
        wo=jax.random.normal(ks[3], (H * dh, dm)) * 0.05,
        q_scale=jnp.ones(dh), k_scale=jnp.ones(dh))
    x = jax.random.normal(ks[4], (B, N, dm))
    state = init_layer_state(B, H, N, dm, dh, cfg)
    return cfg, p, x, state, H, N


@pytest.mark.parametrize("strategy", ["multi-granularity", "hunyuan-1.5x"])
def test_strategy_emissions_bucketed_roundtrip(strategy):
    cfg, p, x, state, H, N = _engine_setup(strategy, "pallas")
    out_u, st = update_layer(p, x, state, cfg, n_text=N_TEXT, heads=H)
    assert st.plan.bkt_head is not None
    x2 = x + 0.01 * jax.random.normal(jax.random.PRNGKey(5), x.shape)
    out_d, st2 = dispatch_layer(p, x2, st, cfg, n_text=N_TEXT, heads=H)
    assert bool(jnp.isfinite(out_d).all())

    # Same strategy + inputs through the XLA backend: dispatch parity.
    cfg_x, px, xx, sx, _, _ = _engine_setup(strategy, "xla")
    _, st_x = update_layer(px, xx, sx, cfg_x, n_text=N_TEXT, heads=H)
    out_x, _ = dispatch_layer(px, x2, st_x, cfg_x, n_text=N_TEXT, heads=H)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_x),
                               atol=2e-5, rtol=2e-5)

    # plan_from_state rebuilds the bucketed fields bit-exactly (the
    # Update-time lax.sort assignment is deterministic, pid tie-broken).
    rebuilt = plan_from_state(st2, cfg, N)
    for a, b in zip(jax.tree.leaves(rebuilt), jax.tree.leaves(st2.plan)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_widen_covers_bucket_fields():
    b, h, t = 1, 4, 8
    m_c = jnp.ones((b, h, t), bool)
    m_s = jnp.broadcast_to(jnp.eye(t, dtype=bool), (b, h, t, t))
    m_s = m_s.at[..., 0].set(True)
    cfg_b, _ = _cfgs()
    plan = build_dispatch_plan(m_c, m_s, cfg_b, t * 32)
    narrow = ("q_ids", "q_slots", "kv_ids", "kv_row_ids", "row_ids",
              "bkt_head", "bkt_q_ids", "bkt_q_src", "bkt_q_slots",
              "bkt_kv_ids")
    for f in narrow:
        assert getattr(plan, f).dtype == jnp.int16, f
    wide = plan.widen()
    for f in narrow:
        assert getattr(wide, f).dtype == jnp.int32, f
        np.testing.assert_array_equal(np.asarray(getattr(wide, f)),
                                      np.asarray(getattr(plan, f)))
    # Idempotent on an already-wide plan.
    assert wide.widen() is wide


# ---------------------------------------------------------------------------
# Shape-bucketed serving lanes
# ---------------------------------------------------------------------------

def _ecfg():
    return EngineConfig(mask=MaskConfig(
        tau_q=0.5, tau_kv=0.15, interval=4, order=1, degrade=0.0,
        block_q=16, block_kv=16, pool=16, warmup_steps=2),
        cache_dtype=jnp.float32, cap_q_frac=1.0, cap_kv_frac=1.0)


def _shape_request(cfg, i, nv, steps=6):
    kx, kt = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(100), i))
    return Request(rid=i, x0=jax.random.normal(kx, (1, nv, cfg.patch_dim)),
                   text_emb=jax.random.normal(
                       kt, (1, cfg.n_text_tokens, cfg.d_model)),
                   num_steps=steps)


def test_shape_buckets_fold_near_miss_lanes():
    """N_v ∈ {64, 48} requests: unbucketed they partition into two lane
    shapes; with ``shape_buckets=(64,)`` they fold into ONE partition
    inside the ≤ 4 executable budget, each request's output bit-identical
    to a sequential run of the same zero-padded request, sliced back."""
    cfg = get_smoke("flux-mmdit")
    ecfg = _ecfg()
    params = dit.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [_shape_request(cfg, 0, 64), _shape_request(cfg, 1, 48),
            _shape_request(cfg, 2, 64), _shape_request(cfg, 3, 48)]

    # Baseline: exact shape keys split the queue into two partitions.
    base = ContinuousBatcher(params, cfg, ecfg, lanes=2, max_steps=6)
    base.submit_all(reqs)
    base.run()
    assert base.stats["shape_partitions"] == 2

    bat = ContinuousBatcher(params, cfg, ecfg, lanes=2, max_steps=6,
                            shape_buckets=(64,))
    bat.submit_all(reqs)
    results = bat.run()
    assert bat.stats["shape_partitions"] == 1
    assert 1 <= bat.stats["executables"] <= 4
    # The near-miss key is recorded as folding into the canonical lane.
    folded = {orig[0][1]: canon[0][1]
              for orig, canon in bat.stats["shape_buckets"].items()}
    assert folded == {64: 64, 48: 64}

    # Parity contract: sequential runs of the PADDED requests, sliced
    # back to each request's own N_v.
    padded = [Request(rid=r.rid,
                      x0=jnp.pad(r.x0, ((0, 0), (0, 64 - r.x0.shape[1]),
                                        (0, 0))),
                      text_emb=r.text_emb, num_steps=r.num_steps)
              for r in reqs]
    seq = run_sequential(params, cfg, ecfg, padded)
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(results[r.rid]["out"]),
            np.asarray(seq[r.rid]["out"][:, :r.x0.shape[1]]),
            err_msg=f"bucketed lane {r.rid} diverged from padded sequential")
