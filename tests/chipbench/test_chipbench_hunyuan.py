"""The HunyuanVideo cell at test size on four host devices, in a child
process: served through ``run_cell`` on the ``(1, 4)`` sequence mesh with
the Pallas kernels interpreted, it is ``correct`` against the plain
reference, its sampler lowers again for a traced run's ops, and its
fresh Taylor state is made split by token over the chips; with the
Update K/V gather left out (each chip attends its own keys only) it is
not."""

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
cell = small_cell("hunyuan-video", "sparse.s50", n_image_tokens=640)
spec = json.loads((B.ROOT / "chipbench" / "configs" /
                   "hunyuan-video.json").read_text())
cell["model"]["n_layers"] = 8
over = {"backend": "pallas", "interpret": True}

def run():
    R.time = TickClock()
    return R.run_cell(B.load_benchmark(), cell, 2 ** 33 + 5, 3.5, False,
                      jax.devices()[:4], R.time.perf_counter(),
                      engine_overrides=over)

sound = run()
# A traced run reads the compiled ops through the sampler's lower thunk,
# with the weights placed on the mesh and the inputs on one device.
server = R.Server(cell, 2 ** 33 + 5, over)
stats = {}
server.serve(server.request(0), stats=stats)
sound["lowered"] = bool(stats["lower"]().compile().as_text())
# The Taylor state is made split by token over the four chips.
from repro.diffusion import pipeline
n = sum(cell["model"]["sizes"][k] for k in ("n_text_tokens",
                                            "n_image_tokens"))
derivs = pipeline._init_states(server.cfg, server.ecfg, 1,
                               n).taylor.derivs
sound["state_shards"] = sorted({s.data.shape[3]
                                for s in derivs.addressable_shards})
sound["state_tokens"] = n
from repro.core.lru import LruCache
from repro.diffusion import pipeline
pipeline._SAMPLER_CACHE = LruCache(4)          # trace the sampler anew
jax.lax.all_gather = lambda x, *a, **k: x      # each chip keeps its own K/V
broken = run()
print(json.dumps({"sound": sound, "broken": broken}))
print(sound["checks"], broken["checks"], file=sys.stderr)
'''


def test_hunyuan_cell_is_correct_and_the_gather_left_out_is_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=B.ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    sound, broken = res["sound"], res["broken"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4 and sound["lowered"]
    assert sound["state_shards"] == [sound["state_tokens"] // 4]
    rel = broken["checks"]["rel_l2"]
    print(sound["checks"], rel)
    assert rel["value"] > rel["limit"]
    assert not broken["correct"]
