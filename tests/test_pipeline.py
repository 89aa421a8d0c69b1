"""End-to-end diffusion pipeline integration: sparse sampling tracks the
dense oracle (the hardware-independent slice of paper Tables 1–3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke
from repro.core.engine import EngineConfig
from repro.core.masks import MaskConfig
from repro.diffusion.pipeline import SamplerConfig, sample
from repro.models import dit


def _psnr(a, b):
    mse = float(jnp.mean(jnp.square(a - b)))
    rng = float(jnp.max(jnp.abs(b))) or 1.0
    return 10 * np.log10(rng * rng / max(mse, 1e-12))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("flux-mmdit")
    params = dit.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(42)
    B, Nv = 1, 96
    x0 = jax.random.normal(key, (B, Nv, cfg.patch_dim))
    text = jax.random.normal(jax.random.fold_in(key, 1),
                             (B, cfg.n_text_tokens, cfg.d_model))
    return cfg, params, x0, text


def _ecfg(**kw):
    base = dict(tau_q=0.5, tau_kv=0.0, interval=4, order=1, degrade=0.0,
                block_q=16, block_kv=16, pool=32, warmup_steps=2)
    base.update(kw)
    return EngineConfig(mask=MaskConfig(**base), cache_dtype=jnp.float32)


def test_sparse_sampling_tracks_dense(setup):
    cfg, params, x0, text = setup
    scfg = SamplerConfig(num_steps=10)
    dense = sample(params, cfg, _ecfg(), text_emb=text, x0=x0, scfg=scfg,
                   force_dense=True)
    trace: list = []
    sparse = sample(params, cfg, _ecfg(), text_emb=text, x0=x0, scfg=scfg,
                    trace=trace)
    assert bool(jnp.isfinite(sparse).all())
    psnr = _psnr(sparse, dense)
    assert psnr > 15.0, psnr                      # visually faithful (smoke scale)
    kinds = [t["kind"] for t in trace]
    assert kinds[:2] == ["update", "update"]      # warmup
    assert "dispatch" in kinds


def test_density_drops_after_warmup(setup):
    """Fig. 7: density starts at 1 (warmup) then falls under sparsity."""
    cfg, params, x0, text = setup
    trace: list = []
    sample(params, cfg, _ecfg(tau_q=0.7), text_emb=text, x0=x0,
           scfg=SamplerConfig(num_steps=8), trace=trace)
    late = [t["density"] for t in trace if t["kind"] == "dispatch"]
    # density measures the PLANNED live fraction for the coming dispatches;
    # with sparsity on it sits strictly below 1 (Fig. 7 shape).
    assert late and min(late) < 1.0


def test_more_aggressive_interval_is_less_faithful(setup):
    """Table 3 ablation direction: larger 𝒩 -> lower fidelity."""
    cfg, params, x0, text = setup
    scfg = SamplerConfig(num_steps=12)
    dense = sample(params, cfg, _ecfg(), text_emb=text, x0=x0, scfg=scfg,
                   force_dense=True)
    psnrs = {}
    for interval in [2, 6]:
        out = sample(params, cfg, _ecfg(interval=interval, tau_q=0.6),
                     text_emb=text, x0=x0, scfg=scfg)
        psnrs[interval] = _psnr(out, dense)
    assert psnrs[2] >= psnrs[6] - 1.0, psnrs      # small slack for noise


def test_fresh_states_come_from_one_compiled_call(setup):
    """A request's engine states are made by one memoized compiled call:
    the eager construction's values, left uncommitted on the default
    device (where the sampler takes them off a mesh), and the second
    request reuses the first's call."""
    from repro.diffusion import pipeline
    cfg, _, x0, text = setup
    ecfg = _ecfg(interval=7)                  # a shape no other test made
    n = x0.shape[1] + text.shape[1]
    before = len(pipeline._INIT_CACHE)
    first = pipeline._init_states(cfg, ecfg, 1, n)
    again = pipeline._init_states(cfg, ecfg, 1, n)
    assert len(pipeline._INIT_CACHE) == before + 1
    eager = dit.init_engine_states(cfg, ecfg, 1, n)
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (first, again, eager))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
        assert not a.committed and a.dtype == c.dtype
