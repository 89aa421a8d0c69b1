"""Compile rehearsal of the plain reference for a described TPU v5e:2x2,
with no chip attached: the Update step at the paper's 33K setting (256
text + 32,768 video tokens, 8 blocks of HunyuanVideo width) split by token
over four chips fits each chip's memory with 10% free.  Nothing executes.

The topology is described in a child process of its own, so this file
never loads the TPU library into a test worker: the worker that runs
``tests/test_tpu_compile.py`` keeps it to itself.  The child allows a
second load of the library beside that worker's, writes no compiler logs
and keeps no persistent compilation cache, since what it wrote could not
be read back without a chip.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import bench as B

HBM_BYTES = 15.75 * 2 ** 30        # what the compiler lets a v5e program use

CHILD = r'''
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
sys.path[:0] = ["src", "tests/chipbench"]
from chipbench import reference
from chipbench_cells import HUNYUAN_TOKENS, avals, hunyuan_step_args

jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as e:  # no libtpu, or it cannot describe a v5e
    print(json.dumps({"skip": str(e)[:500]}))
    sys.exit(0)
mesh = Mesh(np.asarray(topo.devices[:4]), (reference.TOKENS,))

def place(token_dim):
    spec = [None] * (0 if token_dim is None else token_dim + 1)
    if token_dim is not None:
        spec[token_dim] = reference.TOKENS
    return NamedSharding(mesh, PartitionSpec(*spec))

rc, args = hunyuan_step_args(8, place)
traced = reference._step.trace(*args, rc_items=tuple(sorted(rc.items())),
                               mode="update", compute=jnp.float32, mesh=mesh)
n = sum(HUNYUAN_TOKENS)
square = [a.shape for a in avals(traced.jaxpr.jaxpr)
          if getattr(a, "shape", ()).count(n) > 1]
m = traced.lower().compile().memory_analysis()
print(json.dumps({"square": [list(s) for s in square],
                  "per_chip": m.argument_size_in_bytes + m.temp_size_in_bytes
                  + m.output_size_in_bytes - m.alias_size_in_bytes}))
'''


def test_update_step_at_33k_fits_four_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=B.ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    if "skip" in res:
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{res['skip']}")
    assert not res["square"]
    assert res["per_chip"] <= 0.9 * HBM_BYTES, res["per_chip"] / 2 ** 30
