"""MMDiT — the paper's own model family (FLUX / HunyuanVideo style).

Single-stream DiT blocks over the concatenated [text; vision] token
sequence with adaLN-Zero timestep modulation; joint attention runs through
the FlashOmni Update–Dispatch engine (``repro.core.engine``).  The text
encoder and VAE/patchifier are STUBS per the task spec — inputs are
precomputed text embeddings and latent-patch embeddings.

``denoise_step`` traces one engine phase (``mode`` = "update" /
"dispatch" / "dense"); the pipeline's single-scan sampler ``lax.switch``es
between the three trace bodies on a :class:`~repro.core.schedule.
SparsitySchedule` mode array — one compiled executable for the whole loop.

Engine states are stacked (L, ...) and scanned with the blocks, so the HLO
stays one-block-sized at any depth — including per-layer strategy tables,
which ride the scan as a TRACED strategy-id row (``lax.switch`` over the
schedule's active strategy set inside the block body; nothing unrolls).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import engine as E
from repro.core.engine import AttnParams, EngineConfig, LayerState
from repro.models import layers as L

__all__ = ["init_params", "param_specs", "init_engine_states",
           "engine_state_specs", "denoise_step", "timestep_embedding",
           "train_loss"]


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0):
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _init_block(cfg: ArchConfig, key):
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.hd
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * hd)) * s,
        "wk": jax.random.normal(ks[1], (d, h * hd)) * s,
        "wv": jax.random.normal(ks[2], (d, h * hd)) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d)) * s,
        "q_scale": jnp.ones((hd,)),
        "k_scale": jnp.ones((hd,)),
        "mlp_wi": jax.random.normal(ks[4], (d, cfg.d_ff)) * s,
        "mlp_wo": jax.random.normal(ks[5], (cfg.d_ff, d)) * (cfg.d_ff ** -0.5),
        "adaln": jax.random.normal(ks[6], (d, 6 * d)) * 0.02,
        "adaln_b": jnp.zeros((6 * d,)),
    }


def _block_specs():
    n = (None,)
    return {"wq": (*n, "fsdp", "tp"), "wk": (*n, "fsdp", "tp"),
            "wv": (*n, "fsdp", "tp"), "wo": (*n, "tp", "fsdp"),
            "q_scale": (*n, None), "k_scale": (*n, None),
            "mlp_wi": (*n, "fsdp", "tp"), "mlp_wo": (*n, "tp", "fsdp"),
            "adaln": (*n, "fsdp", None), "adaln_b": (*n, None)}


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> Any:
    """Seeded random weights, every leaf stored in ``dtype``.

    Layer ``i`` draws from ``fold_in(kb, i)``; the blocks are drawn under
    ``vmap`` so the (L, ...) stack is built in one piece, never as a list
    of per-block arrays.  Called under ``jax.jit`` with a bf16 ``dtype``
    (``launch/serve.init_weights``) the f32 draws stay inside the fused
    program and only the bf16 stack reaches device memory.
    """
    kb, kt, kf, kp = jax.random.split(key, 4)
    d = cfg.d_model
    layer_keys = jax.vmap(lambda i: jax.random.fold_in(kb, i))(
        jnp.arange(cfg.n_layers))
    params = {
        "blocks": jax.vmap(lambda k: _init_block(cfg, k))(layer_keys),
        "t_mlp1": jax.random.normal(kt, (256, d)) * 0.02,
        "t_mlp2": jax.random.normal(jax.random.fold_in(kt, 1), (d, d)) * 0.02,
        "final_mod": jax.random.normal(kf, (d, 2 * d)) * 0.02,
        "final_proj": jax.random.normal(kp, (d, cfg.patch_dim)) * 0.02,
        "final_norm": jnp.ones((d,)),
    }
    return jax.tree.map(lambda x: x.astype(dtype), params)


def param_specs(cfg: ArchConfig) -> Any:
    return {"blocks": _block_specs(),
            "t_mlp1": (None, "fsdp"), "t_mlp2": ("fsdp", "tp"),
            "final_mod": ("fsdp", None), "final_proj": ("fsdp", None),
            "final_norm": (None,)}


def init_engine_states(cfg: ArchConfig, ecfg: EngineConfig, batch: int,
                       n_tokens: int) -> LayerState:
    one = E.init_layer_state(batch, cfg.n_heads, n_tokens, cfg.d_model, cfg.hd, ecfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one)


def engine_state_specs(cfg: ArchConfig, ecfg: EngineConfig) -> LayerState:
    if ecfg.cache_mode == "bias":
        taylor_feat = (None, None, "dp", "sp", "tp")   # (L, D+1, B, N, dm)
    else:
        taylor_feat = (None, None, "dp", None, "sp", None)
    from repro.core.plan import DispatchPlan
    from repro.core.taylorseer import TaylorState
    # Packed symbols are tiny (uint8); replicate the head dim (24 heads do
    # not divide the 16-wide model axis).  The DispatchPlan index arrays are
    # likewise small (int32 at block/pool granularity) and capacity-shaped;
    # shard them on batch only so scalar-prefetch gathers stay local.
    plan = DispatchPlan(
        q_ids=(None, "dp", None, None),
        q_cnt=(None, "dp", None),
        q_slots=(None, "dp", None, None),
        kv_ids=(None, "dp", None, None),
        kv_cnt=(None, "dp", None),
        pair_live=(None, "dp", None, None, None),
        kv_row_ids=(None, "dp", None, None, None),
        kv_row_cnt=(None, "dp", None, None),
        row_ids=(None, "dp", None),
        row_cnt=(None, "dp"),
        head_ids=(None, "dp", None, None),
        head_cnt=(None, "dp", None),
        head_mask=(None, "dp", None, None),
        m_ch=(None, "dp", None, None),
        row_score=(None, "dp", None),
        occ_hist=(None, "dp", None),
    )
    if ecfg.resolved_kv_buckets() > 1:
        # Optional bucketed-layout fields become pytree leaves only when
        # the config emits them — the spec tree must match leaf-for-leaf.
        # NB: resolved_kv_buckets, not kv_buckets — the 0 = auto sentinel
        # must resolve to the same depth the plan build sees via caps().
        plan = plan._replace(
            bkt_head=(None, "dp", None), bkt_q_ids=(None, "dp", None),
            bkt_q_src=(None, "dp", None), bkt_q_slots=(None, "dp", None),
            bkt_kv_ids=(None, "dp", None), bkt_kv_cnt=(None, "dp", None),
            gmo_rows=(None, "dp", None), gmo_src=(None, "dp", None),
            gmo_head_ids=(None, "dp", None), gmo_head_cnt=(None, "dp", None))
    if ecfg.mesh_sp > 1 and ecfg.mesh_axis == "seq":
        # Plan-sharded mesh partition (distributed/plan_shard.py): batch-
        # sharded like every other plan field; the destination-shard axis
        # is consumed by the dispatch shard_map, not by GSPMD.
        p3 = (None, "dp", None, None)
        p4 = (None, "dp", None, None, None)
        plan = plan._replace(
            shd_q_ids=p4, shd_q_src=p4, shd_q_slots=p4, shd_q_cnt=p3,
            shd_kv_ids=p4, shd_kv_cnt=p3,
            shd_kv_row_ids=(None, "dp", None, None, None, None),
            shd_kv_row_cnt=p4, shd_gather_idx=p4,
            shd_send_ids=(None, "dp", None, None, None, None),
            shd_send_cnt=p4)
    return LayerState(
        s_c=(None, "dp", None, None),
        s_s=(None, "dp", None, None),
        taylor=TaylorState(derivs=taylor_feat, n_updates=(None,)),
        k_since=(None,),
        plan=plan,
    )


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None]) + shift[:, None]


def _canonicalize_layer_strategies(layer_strategies, ecfg, n_layers):
    """Per-layer spec table -> (static strategy set, traced int32 id row)."""
    from repro.core.schedule import strategy_table
    strategies, ids = strategy_table(layer_strategies, ecfg, n_layers)
    return strategies, jnp.asarray(ids)


def _block(cfg: ArchConfig, ecfg: EngineConfig, p, state, x, t_emb, *, mode: str,
           n_text: int, strategy=None, layer_idx=None, strategy_id=None,
           strategies=None, step_idx=None, num_steps=None):
    dtype = x.dtype
    with jax.named_scope("fo.mlp"):
        mod = (jax.nn.silu(t_emb) @ p["adaln"].astype(dtype)
               + p["adaln_b"].astype(dtype))
        sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
        xa = _modulate(L.rms_norm(x, jnp.ones((cfg.d_model,)), cfg.norm_eps),
                       sh_a, sc_a)
    attn_p = AttnParams(wq=p["wq"].astype(dtype), wk=p["wk"].astype(dtype),
                        wv=p["wv"].astype(dtype), wo=p["wo"].astype(dtype),
                        q_scale=p["q_scale"], k_scale=p["k_scale"])
    if mode == "update":
        o, new_state = E.update_layer(attn_p, xa, state, ecfg, n_text=n_text,
                                      heads=cfg.n_heads, strategy=strategy,
                                      layer_idx=layer_idx,
                                      strategy_id=strategy_id,
                                      strategies=strategies,
                                      step_idx=step_idx, num_steps=num_steps)
    elif mode == "dispatch":
        o, new_state = E.dispatch_layer(attn_p, xa, state, ecfg, n_text=n_text,
                                        heads=cfg.n_heads)
    else:  # "dense": engine off (baseline / training)
        from repro.core.backend import get_backend
        with jax.named_scope("fo.qkv"):
            q, k = E._qk(attn_p, xa, cfg.n_heads, None)
            v = E._project_heads(xa, attn_p.wv, cfg.n_heads)
        with jax.named_scope("fo.attention"):
            oh = get_backend(ecfg).dense_attention(q, k, v)
        with jax.named_scope("fo.o_proj"):
            o = oh.transpose(0, 2, 1, 3).reshape(*xa.shape[:2], -1) @ attn_p.wo
        new_state = state
    from repro.distributed.ctx import constrain
    with jax.named_scope("fo.mlp"):
        x = constrain(x + g_a[:, None] * o.astype(dtype), "dp", "sp", None)
        xm = _modulate(L.rms_norm(x, jnp.ones((cfg.d_model,)), cfg.norm_eps),
                       sh_m, sc_m)
        y = constrain(jax.nn.gelu(xm @ p["mlp_wi"].astype(dtype)),
                      "dp", "sp", "tp")
        y = constrain(y @ p["mlp_wo"].astype(dtype), "dp", "sp", None)
        return x + g_m[:, None] * y, new_state


def denoise_step(params, cfg: ArchConfig, ecfg: EngineConfig, states: LayerState,
                 x_vision: jax.Array, text_emb: jax.Array, t: jax.Array,
                 *, mode: str, dtype=jnp.bfloat16, layer_strategies=None,
                 strategies=None, strategy_row=None, step_idx=None,
                 num_steps=None):
    """One diffusion step: predicts the velocity field for ``x_vision``.

    x_vision (B, N_v, d_model) latent patch embeddings; text_emb (B, N_t, d);
    t (B,) diffusion time in [0, 1].  Returns (velocity, new_states).

    Per-layer sparse-symbol producers ride the scanned block body as
    TRACED data (no unrolling — the HLO stays one-block-sized at any
    depth):

      * ``strategies`` + ``strategy_row`` — a schedule's static active set
        and one traced ``(n_layers,)`` int32 id row (a
        ``SparsitySchedule.strategy_ids`` step slice); each scanned block
        ``lax.switch``es its emitter on its row entry.
      * ``layer_strategies`` — convenience per-layer table (registry names
        / strategy objects, ``None`` entries fall back to
        ``ecfg.strategy``); canonicalized into the pair above here.

    ``step_idx`` (traced scalar) and ``num_steps`` (a static int under
    ``pipeline.sample``, or a traced per-lane int32 scalar under the
    batched serving ticks — lanes mix step counts) flow into the
    :class:`~repro.core.strategy.StrategyContext` for schedule-varying
    producers; the scanned layer index is always threaded as the traced
    ``ctx.layer_idx``.

    Under the grouped serving tick the whole step body is ``jax.vmap``ed
    over the lane axis, so ``strategy_row`` may arrive BATCHED (one id row
    per lane): the block scan still threads one row entry per layer, and
    ``emit_switch`` lowers the now-batched ``lax.switch`` to an all-branch
    select — bit-exact per lane, whatever mix of rows the group carries.
    """
    b = x_vision.shape[0]
    n_text = text_emb.shape[1]
    from repro.distributed.ctx import constrain
    with jax.named_scope("fo.io"):
        x = jnp.concatenate([text_emb.astype(dtype), x_vision.astype(dtype)],
                            axis=1)
        x = constrain(x, "dp", "sp", None)
        t_emb = (timestep_embedding(t * 1000.0, 256).astype(dtype)
                 @ params["t_mlp1"].astype(dtype))
        t_emb = (jax.nn.silu(t_emb)
                 @ params["t_mlp2"].astype(dtype)).astype(dtype)

    if layer_strategies is not None:
        if strategies is not None or strategy_row is not None:
            raise ValueError(
                "pass either layer_strategies or strategies/strategy_row, "
                "not both")
        strategies, strategy_row = _canonicalize_layer_strategies(
            layer_strategies, ecfg, cfg.n_layers)
    if strategies is not None and strategy_row is None:
        strategy_row = jnp.zeros((cfg.n_layers,), jnp.int32)
    with_row = strategies is not None and mode == "update"

    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)

    def body(x, sl):
        if with_row:
            p, st, li, sid = sl
        else:
            (p, st, li), sid = sl, None
        x, new_st = _block(cfg, ecfg, p, st, x, t_emb, mode=mode,
                           n_text=n_text, layer_idx=li, strategy_id=sid,
                           strategies=strategies if with_row else None,
                           step_idx=step_idx, num_steps=num_steps)
        return x, new_st

    xs = (params["blocks"], states, layer_ids)
    if with_row:
        xs = (*xs, jnp.asarray(strategy_row, jnp.int32))
    from repro.models import layers as L
    x, new_states = L.maybe_scan(body, x, xs, scan=cfg.scan_layers)
    with jax.named_scope("fo.io"):
        mod = jax.nn.silu(t_emb) @ params["final_mod"].astype(dtype)
        sh, sc = jnp.split(mod, 2, axis=-1)
        x = _modulate(L.rms_norm(x, params["final_norm"], cfg.norm_eps),
                      sh, sc)
        v = x[:, n_text:] @ params["final_proj"].astype(dtype)
    return v, new_states


def train_loss(params, cfg: ArchConfig, batch, *, dtype=jnp.bfloat16):
    """Flow-matching training loss (rectified flow): v_θ(x_t, t) ≈ x1 − x0.

    batch: {"latents": (B,N_v,patch_dim) clean targets,
            "patch_emb": (B,N_v,d_model) embedded noisy input,
            "text_emb": (B,N_t,d_model), "t": (B,), "noise": like latents}.
    """
    ecfg = EngineConfig()                    # engine off in training (dense)
    states = init_engine_states(cfg, ecfg, batch["patch_emb"].shape[0],
                                batch["text_emb"].shape[1] + batch["patch_emb"].shape[1])
    v, _ = denoise_step(params, cfg, ecfg, states, batch["patch_emb"],
                        batch["text_emb"], batch["t"], mode="dense", dtype=dtype)
    target = batch["latents"] - batch["noise"]
    return jnp.mean(jnp.square(v.astype(jnp.float32) - target.astype(jnp.float32)))
