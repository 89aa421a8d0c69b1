"""Jitted step builders shared by the real launchers and the dry-run.

Every builder returns ``(fn, in_shapes, in_shardings, out_shardings)`` so
``dryrun.py`` can ``jax.jit(fn, in_shardings=..., out_shardings=...)
.lower(*in_shapes).compile()`` and the launchers can feed real arrays.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.engine import EngineConfig
from repro.distributed.sharding import ShardingRules, named_sharding_tree
from repro.launch import specs as S
from repro.models.registry import get_model
from repro.optim.optimizer import AdamWConfig, adamw_init, adamw_state_specs, adamw_update

__all__ = ["build_train_step", "build_prefill_step", "build_decode_step",
           "build_dit_step", "eval_shape_tree"]


def eval_shape_tree(fn, *args):
    return jax.eval_shape(fn, *args)


def _shardings(tree_specs, mesh: Mesh, rules: ShardingRules):
    return named_sharding_tree(tree_specs, mesh, rules)


def build_train_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                     rules: ShardingRules, *, opt_cfg: AdamWConfig = AdamWConfig(),
                     cast_params_bf16: bool = False):
    """``cast_params_bf16`` (§Perf lever): convert the sharded f32 params to
    bf16 at step entry, BEFORE the FSDP all-gathers — halves both weight
    all-gather traffic and weight HBM reads in fwd/bwd."""
    model = get_model(cfg)
    p_specs = model.param_specs()
    o_specs = adamw_state_specs(p_specs)

    def train_step(params, opt_state, batch):
        from repro.distributed.ctx import activation_rules

        def loss_fn(p):
            if cast_params_bf16:
                p = jax.tree.map(
                    lambda w: w.astype(jnp.bfloat16)
                    if w.dtype == jnp.float32 else w, p)
            return model.train_loss(p, batch)

        with activation_rules(rules):   # activation sharding hints (§Perf A2)
            loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_o, gnorm = adamw_update(grads, opt_state, params, opt_cfg)
        return new_p, new_o, {"loss": loss, "grad_norm": gnorm}

    params_shape = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    opt_shape = jax.eval_shape(
        lambda: adamw_init_from_shapes(params_shape, opt_cfg))
    batch_shape = S.train_batch(cfg, shape)

    p_sh = _shardings(p_specs, mesh, rules)
    o_sh = _shardings(o_specs, mesh, rules)
    b_sh = _shardings(S.train_batch_logical(cfg), mesh, rules)
    m_sh = {"loss": NamedSharding(mesh, P()), "grad_norm": NamedSharding(mesh, P())}
    return (train_step, (params_shape, opt_shape, batch_shape),
            (p_sh, o_sh, b_sh), (p_sh, o_sh, m_sh))


def adamw_init_from_shapes(params_shape, opt_cfg: AdamWConfig = AdamWConfig()):
    dt = jnp.dtype(opt_cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return {"mu": jax.tree.map(zeros, params_shape),
            "nu": jax.tree.map(zeros, params_shape),
            "step": jnp.zeros((), jnp.int32)}


def _bf16_params_shape(model):
    ps = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype), ps)


def build_prefill_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                       rules: ShardingRules):
    model = get_model(cfg)

    def prefill_step(params, batch):
        return model.prefill(params, batch)

    params_shape = _bf16_params_shape(model)
    batch_shape = S.prefill_batch(cfg, shape)
    p_sh = _shardings(model.param_specs(), mesh, rules)
    b_sh = _shardings(S.prefill_batch_logical(cfg), mesh, rules)
    # vocab dim replicated: published vocabs aren't 16-divisible post-slice.
    out_sh = NamedSharding(mesh, P(rules.physical("dp"), None))
    return prefill_step, (params_shape, batch_shape), (p_sh, b_sh), out_sh


def build_decode_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                      rules: ShardingRules):
    model = get_model(cfg)
    b, s = shape.global_batch, shape.seq_len

    def decode_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)

    params_shape = _bf16_params_shape(model)
    cache_shape = jax.eval_shape(lambda: model.init_cache(b, s))
    token_shape = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    p_sh = _shardings(model.param_specs(), mesh, rules)
    c_sh = _shardings(model.cache_specs(), mesh, rules)
    t_sh = NamedSharding(mesh, P(rules.physical("dp")))
    s_sh = NamedSharding(mesh, P())
    logits_sh = NamedSharding(mesh, P(rules.physical("dp"), None))
    return (decode_step, (params_shape, cache_shape, token_shape, pos_shape),
            (p_sh, c_sh, t_sh, s_sh), (logits_sh, c_sh))


def build_dit_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                   rules: ShardingRules, *, mode: str = "dispatch",
                   ecfg: EngineConfig | None = None):
    """One diffusion denoise step (Update or Dispatch) for the paper archs."""
    from repro.models import dit as ditmod

    if ecfg is None:
        from repro.core.masks import MaskConfig
        ecfg = EngineConfig(
            mask=MaskConfig(tau_q=0.5, tau_kv=0.15, interval=5, order=1,
                            degrade=0.3, block_q=64, block_kv=64, pool=256),
            cap_q_frac=0.6, cap_kv_frac=0.9)

    def step(params, states, inputs):
        from repro.distributed.ctx import activation_rules
        with activation_rules(rules):   # §Perf iteration C1
            v, new_states = ditmod.denoise_step(
                params, cfg, ecfg, states, inputs["x_vision"], inputs["text_emb"],
                inputs["t"], mode=mode)
        return v, new_states

    model_shape = jax.eval_shape(lambda: ditmod.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    n_tok = shape.seq_len
    states_shape = jax.eval_shape(
        lambda: ditmod.init_engine_states(cfg, ecfg, shape.global_batch, n_tok))
    in_shape = S.dit_inputs(cfg, shape)

    p_sh = _shardings(ditmod.param_specs(cfg), mesh, rules)
    st_sh = _shardings(ditmod.engine_state_specs(cfg, ecfg), mesh, rules)
    in_sh = _shardings(S.dit_inputs_logical(cfg), mesh, rules)
    v_sh = NamedSharding(mesh, P(rules.physical("dp"), rules.physical("sp"), None))
    return (step, (model_shape, states_shape, in_shape),
            (p_sh, st_sh, in_sh), (v_sh, st_sh))
