"""The plain reference computes attention by query chunks and splits a
request over the cell's chips without changing its arithmetic."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import bench as B
from chipbench import reference
from chipbench_cells import HUNYUAN_TOKENS, avals, hunyuan_step_args

POOL = 128


def _whole_matrix(q, k, v, allow):
    """Softmax attention over the whole (N, N) score matrix."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    s = s * q.shape[-1] ** -0.5
    if allow is not None:
        tok = jnp.repeat(jnp.repeat(allow, POOL, axis=-2), POOL, axis=-1)
        s = jnp.where(tok, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _qkv_mask(n, heads=2, dh=32):
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, heads, n, dh)),
                           jnp.float32) for _ in range(3))
    t = n // POOL
    allow = (rng.random((1, heads, t, t)) < 0.5) | np.eye(t, dtype=bool)
    return q, k, v, jnp.asarray(allow)


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("n,row0", [(1536, 0), (896, 0), (1024, 192)],
                         ids=["divides", "remainder", "offset-rows"])
def test_chunked_attention_matches_whole_matrix(masked, n, row0):
    """Two whole chunks; a last chunk of 128 rows (896 = 768 + 128); and
    832 query rows that start inside a block, as one chip of several
    holds them (a last chunk of 64)."""
    q, k, v, allow = _qkv_mask(n)
    allow = allow if masked else None
    want = _whole_matrix(q, k, v, allow)[:, :, row0:]
    got = reference._attention(q[:, :, row0:], k, v, allow, jnp.float32,
                               row0=row0)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("mode", ["update", "dispatch"])
def test_no_token_by_token_array_at_33k(mode):
    """At 33,024 tokens no value of the step has two token-long axes."""
    rc, args = hunyuan_step_args(n_layers=2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), (reference.TOKENS,))
    traced = reference._step.trace(*args, rc_items=tuple(sorted(rc.items())),
                                   mode=mode, compute=jnp.float32, mesh=mesh)
    n = sum(HUNYUAN_TOKENS)
    shapes = [a.shape for a in avals(traced.jaxpr.jaxpr)
              if hasattr(a, "shape")]
    assert any(n in s for s in shapes)
    assert not [s for s in shapes if s.count(n) > 1]


CHILD = r'''
import json, sys
import jax, numpy as np
sys.path[:0] = ["src", "tests/chipbench"]
from chipbench_cells import full_width_modulation, small_cell
from chipbench import bench as B, model as M, reference

full_width_modulation()
cell = small_cell()
spec, seed = cell["model"], 2 ** 33 + 5
params = M.make_weights(spec, np.float32, seed)
pe = M.patch_embed(spec["sizes"], seed)
x0, text = (np.asarray(a) for a in M.request_inputs(spec["sizes"], 1)(seed, 1))
rc = B.reference_config(spec, cell["mix"])
one = reference.sample(params, x0, text, pe, rc)
four = reference.sample(params, x0, text, pe, rc, devices=jax.devices()[:4])
print(json.dumps({"gap": reference.compare(four, one, x0)["rel_l2"],
                  "moved": float(np.abs(one - x0).max())}))
'''


def test_sample_split_over_four_devices_matches_one():
    """Tokens split over four devices give the one-device output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=B.ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["moved"] > 0.1
    assert res["gap"] <= 1e-6, res
