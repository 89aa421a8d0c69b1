"""Compile rehearsals for a TPU v5e, with no chip attached.

The three kernels of the served Dispatch path and one full-width
Dispatch step are compiled for a *described* v5e
(``jax.experimental.topologies``).  Nothing executes, so these tests say
nothing about results or speed; they catch what interpret mode cannot: a
tile the Mosaic compiler refuses, a kernel over its VMEM/SMEM budget, a
step that does not lower with the kernels in it.

Shapes are flux-mmdit's published ones at batch 1: 512 text + 4,096
image tokens, d_model 3072, 24 heads x 128, tiles 64/64, pool 128.

The topology is described inside a module-scoped fixture and never at
import, in a ``skipif`` or in ``parametrize``: only the worker that runs
this file loads the TPU library.  The persistent compilation cache is
turned off around these compiles, since what they write could not be
read back without a chip.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
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
    from repro.kernels.flashomni_attention import (csr_resident,
                                                   flashomni_attention_csr)
    assert csr_resident(N_TOKENS, DH, 2)
    fn = functools.partial(flashomni_attention_csr, block_q=SPEC.block_q,
                           block_kv=SPEC.block_kv)
    compiled = _compile(
        lambda q, k, v, o, qi, ki, kc, qc, qs: fn(q, k, v, o, qi, ki, kc, qc,
                                                  q_src_ids=qs),
        one_chip, *_csr_shapes(N_TOKENS))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flashomni_csr_attention" in text


def test_streaming_csr_attention_compiles_past_the_resident_budget(one_chip):
    """Past the VMEM budget (hunyuan's 33,024 tokens) the entry keeps the
    streaming grid, one K/V tile DMA'd per grid step."""
    from repro.kernels.flashomni_attention import (csr_resident,
                                                   flashomni_attention_csr)
    n_kv = 33_024
    assert not csr_resident(n_kv, DH, 2)
    fn = functools.partial(flashomni_attention_csr, block_q=SPEC.block_q,
                           block_kv=SPEC.block_kv)
    compiled = _compile(
        lambda q, k, v, o, qi, ki, kc, qc, qs: fn(q, k, v, o, qi, ki, kc, qc,
                                                  q_src_ids=qs),
        one_chip, *_csr_shapes(n_kv))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flashomni_csr_attention" in text


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
