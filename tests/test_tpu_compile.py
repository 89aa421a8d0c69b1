"""Compile rehearsals for a TPU v5e, with no chip attached.

The kernels of the served path and one full-width Dispatch step are
compiled for a *described* v5e
(``jax.experimental.topologies``).  Nothing executes, so these tests say
nothing about results or speed; they catch what interpret mode cannot: a
tile the Mosaic compiler refuses, a kernel over its VMEM/SMEM budget, a
step that does not lower with the kernels in it.

Shapes are flux-mmdit's published ones at batch 1: 512 text + 4,096
image tokens, d_model 3072, 24 heads x 128, tiles 64/64, pool 128.

The last test compiles the HunyuanVideo cell's whole sampler at the
paper's 33K tokens (``chipbench/configs/hunyuan-video.json``: 256 text +
32,768 video tokens, its depth, the ``(1, 4)`` sequence mesh) for the
described 2x2: it must hold the blockwise dense kernel and the CSR kernel,
build no score array of a shard's rows against every key, keep the CSR
kernel's SMEM under v5e's 1 MiB and fit a chip's 15.75 GiB.

The topology is described inside a module-scoped fixture and never at
import, in a ``skipif`` or in ``parametrize``: only the worker that runs
this file loads the TPU library, so every test that needs it is here.
The persistent compilation cache is turned off around these compiles,
since what they write could not be read back without a chip.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import arch_shapes, get_config
from repro.core.engine import EngineConfig

ARCH = get_config("flux-mmdit")
N_TOKENS = arch_shapes(ARCH)[0].seq_len                 # 4608
D, H, DH = ARCH.d_model, ARCH.n_heads, ARCH.hd
ECFG = EngineConfig(backend="pallas", interpret=False)  # MaskConfig tiles
SPEC = ECFG.caps(N_TOKENS)                              # cap_q 54, cap_kv 66
POOL = ECFG.mask.pool
CAP_ROWS = ECFG.cap_q_cmp(N_TOKENS)                     # live pool rows (27)
HBM_BYTES = 15.75 * 2 ** 30        # what the compiler lets a v5e program use
SMEM_BYTES = 2 ** 20               # v5e's scalar memory a core


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe a v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip at (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _csr_shapes(n_kv):
    bh, bf, i32 = H, jnp.bfloat16, jnp.int32
    return (((bh, CAP_ROWS * POOL, DH), bf), ((bh, n_kv, DH), bf),
            ((bh, n_kv, DH), bf), ((bh, N_TOKENS, DH), bf),
            ((bh, SPEC.cap_q), i32), ((bh, SPEC.cap_q, SPEC.cap_kv), i32),
            ((bh, SPEC.cap_q), i32), ((bh,), i32), ((bh, SPEC.cap_q), i32))


def test_csr_attention_compiles_at_flux_width(one_chip):
    """At flux width (B·H 24, N 4,608, d 128) the entry takes the resident
    walk: K and V of a head held in VMEM, one grid step per q-block row."""
    from repro.kernels.flashomni_attention import (csr_windows,
                                                   flashomni_attention_csr)
    assert csr_windows(N_TOKENS, DH, 2, SPEC.block_kv) == 1
    fn = functools.partial(flashomni_attention_csr, block_q=SPEC.block_q,
                           block_kv=SPEC.block_kv)
    compiled = _compile(
        lambda q, k, v, o, qi, ki, kc, qc, qs: fn(q, k, v, o, qi, ki, kc, qc,
                                                  q_src_ids=qs),
        one_chip, *_csr_shapes(N_TOKENS))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flashomni_csr_attention" in text


@pytest.mark.parametrize("budget,windows", [(None, 1), (12 * 2**20, 3)])
def test_windowed_csr_attention_compiles_past_the_resident_budget(
        one_chip, monkeypatch, budget, windows):
    """At hunyuan's 33,024 keys a head's K and V (33.8 MB double-buffered)
    stay resident under the default budget, the call raising its scoped
    VMEM limit; under a 12 MiB budget the walk cuts them into windows."""
    from repro.kernels import flashomni_attention as fa
    if budget is not None:
        monkeypatch.setattr(fa, "RESIDENT_VMEM_BYTES", budget)
    n_kv = 33_024
    assert fa.csr_windows(n_kv, DH, 2, SPEC.block_kv) == windows
    fn = functools.partial(fa.flashomni_attention_csr, block_q=SPEC.block_q,
                           block_kv=SPEC.block_kv)
    compiled = _compile(
        lambda q, k, v, o, qi, ki, kc, qc, qs: fn(q, k, v, o, qi, ki, kc, qc,
                                                  q_src_ids=qs),
        one_chip, *_csr_shapes(n_kv))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flashomni_csr_attention" in text


# The CSR call of one shard of the hunyuan cell: 129 of the shard's
# q-block rows, each listing up to 466 of the 517 KV blocks (33,088 keys)
# its exchange gathers.
_HUNYUAN_SHARD = (((H, 33_024, DH), jnp.bfloat16),
                  ((H, 33_088, DH), jnp.bfloat16),
                  ((H, 33_088, DH), jnp.bfloat16),
                  ((H, 8_256, DH), jnp.bfloat16),
                  ((H, 129), jnp.int32), ((H, 129, 466), jnp.int32),
                  ((H, 129), jnp.int32), ((H,), jnp.int32),
                  ((H, 129), jnp.int32))


@pytest.mark.parametrize("cell", ["flux", "hunyuan_shard"])
def test_grouped_csr_walk_lowers_every_group_width(one_chip, cell):
    """The resident walk's grouped update lowers for a v5e at flux's shape
    (4,608 keys) and at a hunyuan shard's (33,088 keys): one score block
    of each width 1..G blocks, G in the loop over full groups and each
    width below G in the last group's live-count branch.  Mosaic refuses
    a body past the call's scoped VMEM limit, so the compile checks VMEM;
    the limit is the resident window's, not raised for the group."""
    from repro.kernels import flashomni_attention as fa
    shapes = _csr_shapes(N_TOKENS) if cell == "flux" else _HUNYUAN_SHARD
    (_, n_kv, _), ckv = shapes[1][0], shapes[5][0][2]
    assert fa.csr_windows(n_kv, DH, 2, SPEC.block_kv) == 1
    fn = functools.partial(fa.flashomni_attention_csr, block_q=SPEC.block_q,
                           block_kv=SPEC.block_kv)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    traced = jax.jit(
        lambda q, k, v, o, qi, ki, kc, qc, qs: fn(q, k, v, o, qi, ki, kc, qc,
                                                  q_src_ids=qs)).trace(*args)
    (call,) = [e for e in _eqns(traced.jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    widths = {e.invars[1].aval.shape[0] // SPEC.block_kv
              for e in _eqns(call.params["jaxpr"])
              if e.primitive.name == "dot_general"
              and e.params["dimension_numbers"][0] == ((1,), (1,))}
    assert widths == set(range(1, fa.walk_group(ckv) + 1)), widths
    kv_bytes = fa._kv_window_bytes(n_kv // SPEC.block_kv, SPEC.block_kv, DH, 2)
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == (None if kv_bytes <= 12 * 2**20
                     else 4 * 2**20 + kv_bytes), limit
    text = traced.lower().compile().as_text()
    assert "flashomni_csr_attention" in text


def test_dense_attention_compiles_at_flux_width(one_chip):
    """Update's blockwise attention at flux width: 768-row query tiles
    against 1,536-key tiles, under its own name."""
    from repro.kernels.dense_attention import flashomni_dense_attention
    shape = ((1, H, N_TOKENS, DH), jnp.bfloat16)
    text = _compile(flashomni_dense_attention, one_chip, shape, shape,
                    shape).as_text()
    assert "tpu_custom_call" in text
    assert "flashomni_dense_attention" in text


def test_gemm_q_compiles_at_flux_width(one_chip):
    from repro.kernels.gemm_q import gemm_q_sparse_kernel
    compiled = _compile(
        lambda x, w, ri, rc: gemm_q_sparse_kernel(
            x, w, ri, block_rows=POOL, row_cnt=rc),
        one_chip, ((1, N_TOKENS, D), jnp.bfloat16),
        ((D, H * DH), jnp.bfloat16), ((1, CAP_ROWS), jnp.int32),
        ((1,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_gemm_o_compiles_at_flux_width(one_chip):
    from repro.kernels.gemm_o import gemm_o_sparse_kernel
    compiled = _compile(
        lambda o, w, b, ri, hi, hc: gemm_o_sparse_kernel(
            o, w, b, ri, hi, hc, block_rows=POOL),
        one_chip, ((1, H, N_TOKENS, DH), jnp.bfloat16),
        ((H, DH, D), jnp.bfloat16), ((1, N_TOKENS, D), jnp.bfloat16),
        ((1, CAP_ROWS), jnp.int32), ((1, CAP_ROWS, H), jnp.int32),
        ((1, CAP_ROWS), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_dispatch_step_compiles_with_kernels(one_chip):
    """One published-width block through ``denoise_step(mode="dispatch")``
    with the Pallas backend compiled for the chip, as ``serve --full``
    runs it, with the three kernels under their names."""
    from repro.models import dit
    cfg = dataclasses.replace(ARCH, n_layers=1)
    n_text = cfg.n_text_tokens

    def step(params, states, xv, te, t):
        return dit.denoise_step(params, cfg, ECFG, states, xv, te, t,
                                mode="dispatch")

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(lambda: dit.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    states = place(jax.eval_shape(lambda: dit.init_engine_states(
        cfg, ECFG, 1, N_TOKENS)))
    compiled = jax.jit(step).lower(
        params, states, *place((
            jax.ShapeDtypeStruct((1, N_TOKENS - n_text, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, n_text, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((1,), jnp.bfloat16)))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    # The kernels keep their names in the compiled module, where the
    # profile's reduction finds them.
    for name in ("flashomni_csr_attention", "flashomni_gemm_q",
                 "flashomni_gemm_o"):
        assert name in text, name




def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _smem_bytes(eqn) -> int:
    """Scalar-prefetched operands plus SMEM blocks, double-buffered."""
    gm = eqn.params["grid_mapping"]
    total = sum(v.aval.size * v.aval.dtype.itemsize
                for v in eqn.invars[:gm.num_index_operands])
    for bm in gm.block_mappings:
        block = bm.transformed_block_aval
        if str(getattr(block, "memory_space", "")) == "smem":
            total += 2 * block.size * block.dtype.itemsize
    return total


def test_hunyuan_sampler_at_33k_compiles_for_four_chips(topo, monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from chipbench import bench as B
    from chipbench import model as M
    from repro.core.engine import resolve_schedule
    from repro.diffusion import pipeline
    from repro.launch import mesh as launch_mesh
    from repro.models import dit

    cell = B.find_cell(B.load_benchmark(), "hunyuan.sparse.33k")
    spec, mix = cell["model"], cell["mix"]
    s = spec["sizes"]
    nt, nv = s["n_text_tokens"], s["n_image_tokens"]
    n, sp = nt + nv, spec["mesh"][1]
    assert (n, tuple(spec["mesh"]), cell["chips"]) == (33_024, (1, 4), 4)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(spec["mesh"]),
                ("data", "seq"))
    monkeypatch.setattr(launch_mesh, "make_engine_mesh",
                        lambda dp=1, sp=1: mesh)
    cfg, ecfg = B.program_configs(spec, mix)
    assert ecfg.backend == "pallas" and not ecfg.interpret
    sched = resolve_schedule(ecfg, mix["steps"], cfg.n_layers)
    whole = NamedSharding(mesh, PartitionSpec())

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=whole), tree)

    params = place(jax.eval_shape(lambda: M._params(
        s, cfg.n_layers, spec["weights"]["qk_norm_gain"], jnp.bfloat16,
        jax.random.key(0))))
    # The states as a request makes them: split by the engine's specs.
    states = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(lambda: dit.init_engine_states(cfg, ecfg, 1, n)),
        pipeline._state_shardings(cfg, ecfg))
    derivs = states.taylor.derivs
    assert derivs.sharding.spec[3] == "seq", derivs.sharding
    args = (params, place(jax.ShapeDtypeStruct((1, nv, s["patch_dim"]),
                                               jnp.float32)),
            states, place(jax.ShapeDtypeStruct((1, nt, s["d_model"]),
                                               jnp.float32)),
            place(jax.ShapeDtypeStruct((s["patch_dim"], s["d_model"]),
                                       jnp.float32)),
            place(sched.mode), place(sched.strategy_ids))
    sampler = pipeline.build_sampler(
        cfg, ecfg, pipeline.SamplerConfig(num_steps=mix["steps"],
                                          dtype=jnp.bfloat16),
        sched.strategies, 1, n, True)
    traced = sampler.trace(*args)

    # No scores of a shard's rows against every key (the XLA dense path's
    # (B, H, N/4, N) per shard, or (B, H, N, N) whole) anywhere.
    eqns = list(_eqns(traced.jaxpr.jaxpr))
    square = {v.aval.shape for e in eqns for v in (*e.invars, *e.outvars)
              if sum(d in (n, n // sp)
                     for d in getattr(v.aval, "shape", ())) > 1}
    assert not square, square

    kernels = {}
    for e in eqns:
        if e.primitive.name == "pallas_call":
            kernels.setdefault(e.params["name"], []).append(e)
    csr = kernels["flashomni_csr_attention"]
    assert "flashomni_dense_attention" in kernels
    smem = max(_smem_bytes(e) for e in csr)
    assert smem < SMEM_BYTES / 4, smem

    compiled = traced.lower().compile()       # Mosaic checks VMEM limits
    text = compiled.as_text()
    for name in ("flashomni_dense_attention", "flashomni_csr_attention",
                 "flashomni_gemm_q", "flashomni_gemm_o"):
        assert name in text, name
    m = compiled.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({"per_chip_bytes": per_chip, "csr_smem_bytes": smem}))
    assert per_chip <= 0.9 * HBM_BYTES, per_chip / 2 ** 30
