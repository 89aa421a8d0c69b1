"""The benchmark's operation counts against the program's static cost
model of one dense step at small widths."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import work as W

SIZES = dict(d_model=256, n_heads=2, head_dim=128, d_ff=512, patch_dim=16,
             n_text_tokens=128, n_image_tokens=512)


def _dense_step_flops(sizes, n_layers):
    from repro.analysis.cost_model import cost_of_jaxpr
    from repro.configs.registry import get_config
    from repro.core.engine import EngineConfig
    from repro.models import dit

    cfg = dataclasses.replace(
        get_config("flux-mmdit"), n_layers=n_layers, d_model=sizes["d_model"],
        n_heads=sizes["n_heads"], n_kv_heads=sizes["n_heads"],
        head_dim=sizes["head_dim"], d_ff=sizes["d_ff"],
        patch_dim=sizes["patch_dim"], n_text_tokens=sizes["n_text_tokens"])
    ecfg = EngineConfig()
    n = sizes["n_text_tokens"] + sizes["n_image_tokens"]
    params = jax.eval_shape(lambda: dit.init_params(cfg, jax.random.PRNGKey(0)))
    states = jax.eval_shape(lambda: dit.init_engine_states(cfg, ecfg, 1, n))
    pe = jax.ShapeDtypeStruct((sizes["patch_dim"], sizes["d_model"]),
                              jnp.float32)

    def step(params, states, x, text, pe, t):
        v, _ = dit.denoise_step(params, cfg, ecfg, states, x @ pe, text, t,
                                mode="dense", dtype=jnp.float32)
        return (x @ pe).sum() + v.sum()

    x = jax.ShapeDtypeStruct((1, sizes["n_image_tokens"], sizes["patch_dim"]),
                             jnp.float32)
    text = jax.ShapeDtypeStruct((1, sizes["n_text_tokens"], sizes["d_model"]),
                                jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    jaxpr = jax.make_jaxpr(step)(params, states, x, text, pe, t)
    return cost_of_jaxpr(jaxpr).flops


@pytest.mark.parametrize("n_layers", [1, 3])
def test_dense_step_matches_cost_model(n_layers):
    got = W.step_flops(SIZES, n_layers, "dense")
    want = _dense_step_flops(SIZES, n_layers)
    # The cost model also counts element-wise work (softmax, norms,
    # activations), which the benchmark leaves out: a few percent here.
    assert 0.9 * want <= got <= want


def test_dispatch_counts_live_work_only():
    dense = W.step_flops(SIZES, 2, "dispatch", density=1.0, pair_live=1.0)
    assert dense == W.step_flops(SIZES, 2, "update")
    half = W.step_flops(SIZES, 2, "dispatch", density=0.5, pair_live=0.25)
    lf = W.layer_flops(SIZES)
    saved = 2 * (0.5 * (lf["q"] + lf["o"]) + 0.75 * lf["attention"])
    assert dense - half == pytest.approx(saved)


def test_kernel_work_per_device_on_a_mesh():
    one = W.kernel_work(SIZES, "csr_attention", 0.8, 0.6)
    four = W.kernel_work(SIZES, "csr_attention", 0.8, 0.6, mesh=(1, 4))
    assert four[0] == pytest.approx(one[0] / 4)
    # at dp 1 every sequence shard projects its rows whole
    assert W.kernel_work(SIZES, "gemm_q", 0.8, 0.6, mesh=(1, 4)) == \
        W.kernel_work(SIZES, "gemm_q", 0.8, 0.6)
    with pytest.raises(KeyError):
        W.kernel_work(SIZES, "no_kernel", 1.0, 1.0)
