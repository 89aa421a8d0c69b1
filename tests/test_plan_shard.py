"""Plan-sharded mesh dispatch: partition invariants, single device.

Everything here runs on ONE CPU device — :func:`partition_plan` and
:func:`mesh_keep_rows` are pure jnp and execute at Update time regardless
of the mesh, so the per-shard CSR partition and the collective schedule
tables can be checked without any forced-device subprocess.  The
end-to-end 8-device bit-parity cases live in ``tests/test_distributed.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platforms", "cpu")

from repro.core.engine import (AttnParams, EngineConfig, init_layer_state,
                               plan_from_state, update_layer)
from repro.core.masks import MaskConfig
from repro.core.plan import build_dispatch_plan
from repro.distributed.plan_shard import (ShardGeometry, dense_exchange_blocks,
                                          exchange_blocks, mesh_attention,
                                          shard_geometry)

MASK = MaskConfig(tau_q=0.5, tau_kv=0.15, interval=4, order=1, degrade=0.3,
                  block_q=16, block_kv=16, pool=32, warmup_steps=2)
B, H, N, DM, DH = 2, 4, 256, 64, 16


def _masks(key=0, b=B, h=H, n=N):
    t = MASK.n_blocks(n)
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    m_c = jax.random.bernoulli(ks[0], 0.7, (b, h, t))
    m_s = jax.random.bernoulli(ks[1], 0.5, (b, h, t, t))
    m_s = m_s.at[..., 0].set(True)      # every row reads block 0 (non-empty)
    m_c = m_c.at[..., 0].set(True)
    return m_c, m_s


def test_shard_geometry_math():
    cfg = EngineConfig(mask=MASK)
    spec = cfg.caps(N)
    g = shard_geometry(spec, 16, 16, 4, pair_slack=1.5)
    assert g.q_bps == 4 and g.kv_bps == 4
    assert g.cap_q == min(spec.cap_q, 4)
    assert g.pair_cap == min(4, max(1, -(-int(1.5 * spec.cap_kv) // 4)))
    assert g.cap_kv == min(16, g.kv_bps + 3 * g.pair_cap)
    assert g.buf_blocks == g.kv_bps + 4 * g.pair_cap
    # slack >= 1 guarantees the per-shard union admits any full row list
    assert g.cap_kv >= min(spec.cap_kv, 16)
    assert exchange_blocks(g) == 4 * g.pair_cap
    assert dense_exchange_blocks(16) == 16
    with pytest.raises(ValueError, match="divisible"):
        shard_geometry(spec, 15, 16, 4)
    with pytest.raises(ValueError, match="mesh_sp"):
        shard_geometry(spec, 16, 16, 0)


def test_identity_fold_is_noop():
    """pair_cap at its safe bound (kv_bps): the mesh fold keeps every
    block, so the base plan fields match the non-mesh plan bit-for-bit."""
    m_c, m_s = _masks()
    cfg0 = EngineConfig(mask=MASK)
    # slack large enough that pair_cap == kv_bps
    cfgm = dataclasses.replace(cfg0, mesh_dp=1, mesh_sp=2,
                               mesh_pair_slack=64.0)
    p0 = build_dispatch_plan(m_c, m_s, cfg0, N)
    pm = build_dispatch_plan(m_c, m_s, cfgm, N)
    g = shard_geometry(cfg0.caps(N), MASK.n_blocks(N) * 2,
                       MASK.n_blocks(N) * 2, 2, 64.0)
    assert g.pair_cap == g.kv_bps
    for f in ("q_ids", "q_cnt", "q_slots", "kv_ids", "kv_cnt", "pair_live",
              "kv_row_ids", "kv_row_cnt", "row_ids", "row_cnt"):
        np.testing.assert_array_equal(
            np.asarray(getattr(p0, f)), np.asarray(getattr(pm, f)), err_msg=f)
    assert p0.shd_q_ids is None and pm.shd_q_ids is not None


def test_partition_invariants():
    """Row partition, union reconstruction from the send/gather tables,
    order-preserving row-list remap, and capacity bounds — all in numpy."""
    sp = 4
    m_c, m_s = _masks()
    cfgm = EngineConfig(mask=MASK, mesh_dp=1, mesh_sp=sp)
    cfg0 = EngineConfig(mask=MASK)
    spec = cfgm.caps(N)
    t = MASK.n_blocks(N) * (MASK.pool // MASK.block_kv)
    g = shard_geometry(spec, t, t, sp, cfgm.mesh_pair_slack)
    pm = build_dispatch_plan(m_c, m_s, cfgm, N).widen()
    p0 = build_dispatch_plan(m_c, m_s, cfg0, N).widen()

    q_ids = np.asarray(pm.q_ids); q_cnt = np.asarray(pm.q_cnt)
    rl = np.asarray(pm.kv_row_ids); rc = np.asarray(pm.kv_row_cnt)
    sq_ids = np.asarray(pm.shd_q_ids); sq_src = np.asarray(pm.shd_q_src)
    sq_cnt = np.asarray(pm.shd_q_cnt)
    skv = np.asarray(pm.shd_kv_ids); skv_cnt = np.asarray(pm.shd_kv_cnt)
    srl = np.asarray(pm.shd_kv_row_ids); src_ = np.asarray(pm.shd_kv_row_cnt)
    gi = np.asarray(pm.shd_gather_idx)
    send = np.asarray(pm.shd_send_ids); send_cnt = np.asarray(pm.shd_send_cnt)

    # capacity bounds
    assert (sq_cnt <= g.cap_q).all() and (skv_cnt <= g.cap_kv).all()
    assert (send_cnt <= g.pair_cap).all()
    # mesh fold only shrinks the row lists (shared truncation)
    assert (rc <= np.asarray(p0.kv_row_cnt)).all()

    for b in range(B):
        for h in range(H):
            live = set(q_ids[b, h, :q_cnt[b, h]].tolist())
            shard_rows = []
            for p in range(sp):
                cnt = sq_cnt[b, h, p]
                rows = sq_src[b, h, p, :cnt].tolist()
                shard_rows += rows
                # local ids are the global ids offset into the shard slice
                np.testing.assert_array_equal(
                    sq_ids[b, h, p, :cnt],
                    sq_src[b, h, p, :cnt] - p * g.q_bps)
                # union reconstruction: gather idx -> global block id
                for c in range(skv_cnt[b, h, p]):
                    gidx = gi[b, h, p, c]
                    if gidx < g.kv_bps:
                        glob = p * g.kv_bps + gidx
                    else:
                        s = (gidx - g.kv_bps) // g.pair_cap
                        j = (gidx - g.kv_bps) % g.pair_cap
                        assert j < send_cnt[b, h, s, p], (b, h, p, c)
                        glob = s * g.kv_bps + send[b, h, s, p, j]
                    assert glob == skv[b, h, p, c], (b, h, p, c)
                # remapped row lists resolve to the folded global lists,
                # order-preserving
                for i in range(cnt):
                    gslot = int(np.where(
                        q_ids[b, h] == sq_src[b, h, p, i])[0][0])
                    nkv = src_[b, h, p, i]
                    assert nkv == rc[b, h, gslot]
                    np.testing.assert_array_equal(
                        skv[b, h, p][srl[b, h, p, i, :nkv]],
                        rl[b, h, gslot, :nkv])
            # row partition covers the live set exactly, no duplicates
            assert sorted(shard_rows) == sorted(live)
            # ascending unions (contiguous per-source runs)
            for p in range(sp):
                u = skv[b, h, p, :skv_cnt[b, h, p]]
                assert (np.diff(u) > 0).all()


def test_plan_from_state_rebuild_bit_exact(monkeypatch):
    """``plan_from_state`` rebuilds the shd_* partition fields
    bit-exactly from the packed symbols under a mesh config.  On one
    device Update's attention runs unsharded: its per-shard form needs the
    mesh's devices, and the plan is what this test checks."""
    from repro.core.backend import MeshBackend
    monkeypatch.setattr(MeshBackend, "dense_attention",
                        lambda self, q, k, v: self.inner.dense_attention(
                            q, k, v))
    cfgm = EngineConfig(mask=MASK, mesh_dp=1, mesh_sp=2)
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    params = AttnParams(
        wq=jax.random.normal(ks[0], (DM, H * DH)) * 0.05,
        wk=jax.random.normal(ks[1], (DM, H * DH)) * 0.05,
        wv=jax.random.normal(ks[2], (DM, H * DH)) * 0.05,
        wo=jax.random.normal(ks[3], (H * DH, DM)) * 0.05,
        q_scale=jnp.ones((DH,)), k_scale=jnp.ones((DH,)))
    x = jax.random.normal(ks[4], (B, N, DM), jnp.float32)
    st0 = init_layer_state(B, H, N, DM, DH, cfgm)
    _, st = update_layer(params, x, st0, cfgm, heads=H)
    rebuilt = plan_from_state(st, cfgm, N)
    assert st.plan.shd_q_ids is not None
    for f in st.plan._fields:
        a, b = getattr(st.plan, f), getattr(rebuilt, f)
        if a is None:
            assert b is None, f
            continue
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f)


def test_mesh_attention_validation_errors():
    from repro.core.backend import XlaBackend
    m_c, m_s = _masks()
    cfg0 = EngineConfig(mask=MASK)
    plan = build_dispatch_plan(m_c, m_s, cfg0, N)
    spec = cfg0.caps(N)
    z = jnp.zeros((B, H, N, DH))
    xla = XlaBackend()
    # seq mode rejects a plan built without the shd_* partition
    cfg_seq = dataclasses.replace(cfg0, mesh_dp=1, mesh_sp=2)
    with pytest.raises(ValueError, match="shd_"):
        mesh_attention(xla, cfg_seq, z, z, z, z, plan, spec)
    # head mode rejects indivisible heads and the bucketed layout
    cfg_head = dataclasses.replace(cfg0, mesh_dp=1, mesh_sp=3,
                                   mesh_axis="head")
    with pytest.raises(ValueError, match="heads"):
        mesh_attention(xla, cfg_head, z, z, z, z, plan, spec)
    cfg_head2 = dataclasses.replace(cfg0, mesh_dp=1, mesh_sp=2,
                                    mesh_axis="head")
    spec_b = spec._replace(kv_buckets=3)
    with pytest.raises(ValueError, match="bucketed"):
        mesh_attention(xla, cfg_head2, z, z, z, z, plan, spec_b)
    # batch must divide over the data axis
    cfg_dp = dataclasses.replace(cfg0, mesh_dp=3, mesh_sp=1)
    with pytest.raises(ValueError, match="batch"):
        mesh_attention(xla, cfg_dp, z, z, z, z, plan, spec)


def test_make_engine_mesh_requires_devices():
    from repro.launch.mesh import make_engine_mesh
    with pytest.raises(ValueError, match="devices"):
        make_engine_mesh(1, 8 * len(jax.devices()))


def test_mesh_shape_for_derives_from_device_count():
    from repro.launch.mesh import mesh_shape_for
    assert mesh_shape_for(512, (16, 16)) == (16, 16)     # cap saturates
    assert mesh_shape_for(32, (16, 16)) == (2, 16)       # model axis filled first
    assert mesh_shape_for(1024, (2, 16, 16)) == (2, 16, 16)
    assert mesh_shape_for(8, (16, 16)) == (1, 8)         # model axis first
    assert mesh_shape_for(6, (16, 16)) == (1, 4)         # floor pow2
    assert mesh_shape_for(1, (16, 16)) == (1, 1)
    with pytest.raises(ValueError, match="power"):
        mesh_shape_for(8, (3, 16))
    with pytest.raises(ValueError, match="device"):
        mesh_shape_for(0, (16, 16))


def test_collective_bytes_extended_ops():
    """The dry-run byte counter must know every exchange op the sharded
    dispatch can lower to — a stale list makes the CI gate read 0 bytes."""
    from repro.launch.dryrun import collective_bytes
    hlo = "\n".join([
        "%r = f32[8,16]{1,0} ragged-all-to-all(%a, %b, %c), replica_groups={}",
        "%s = f32[4,4]{1,0} all-to-all(%d), replica_groups={{0,1}}",
        "%t = bf16[32]{0} collective-broadcast(%e)",
        "%u = f32[2,2]{1,0} collective-permute-start(%f)",
    ])
    coll = collective_bytes(hlo)
    assert coll["ragged-all-to-all"] == 8 * 16 * 4
    assert coll["all-to-all"] == 4 * 4 * 4          # not swallowed by ragged
    assert coll["collective-broadcast"] == 32 * 2
    assert coll["collective-permute"] == 2 * 2 * 4  # -start variant counted
    assert coll["ragged-all-to-all_count"] == 1
