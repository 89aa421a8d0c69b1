"""The four-chip cell's fault: the plan-sharded exchange between chips
left out.  Runs in a child process that has four host devices."""

import json
import os
import subprocess
import sys

from chipbench import bench as B

CHILD = r'''
import json, sys
import jax
sys.path[:0] = ["src", "tests/chipbench"]
from chipbench_cells import TickClock, full_width_modulation, small_cell
from chipbench import bench as B, run as R

full_width_modulation()
cell = small_cell("flux-mmdit-sp4", n_image_tokens=640)
cell["model"]["n_layers"] = 25                 # the cell's depth
over = {"backend": "xla", "interpret": True}

def run():
    R.time = TickClock()
    return R.run_cell(B.load_benchmark(), cell, 2 ** 33 + 3, 3.5, False,
                      jax.devices()[:4], R.time.perf_counter(),
                      engine_overrides=over)

sound = run()
from repro.core.lru import LruCache
from repro.diffusion import pipeline
pipeline._SAMPLER_CACHE = LruCache(4)          # trace the sampler anew
jax.lax.all_to_all = lambda x, *a, **k: jax.numpy.zeros_like(x)  # nothing arrives
broken = run()
print(json.dumps({"sound": sound, "broken": broken}))
print(sound["checks"], broken["checks"], file=sys.stderr)
'''


def test_exchange_left_out_is_incorrect():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=B.ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"]["correct"], res["sound"]["checks"]
    broken = res["broken"]["checks"]["rel_l2"]
    assert broken["value"] > broken["limit"]
    assert not res["broken"]["correct"]
