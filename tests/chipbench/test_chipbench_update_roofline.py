"""``update_attention_roofline``: the blockwise dense kernel's work per
device of the mesh, and its reader on a recorded-shape run whose device
events carry the kernel's name."""

import json
import types

import pytest

from chipbench import bench as B
from chipbench import dense_work as DW
from chipbench import work as W

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _sizes(config):
    spec = json.loads((B.ROOT / "chipbench" / "configs" /
                       f"{config}.json").read_text())
    return spec["sizes"]


@pytest.mark.parametrize("config,mesh", [("flux-mmdit", (1, 1)),
                                         ("hunyuan-video", (1, 4))])
def test_dense_work_is_the_step_models_attention_split_by_query(config,
                                                                mesh):
    s = _sizes(config)
    n = s["n_text_tokens"] + s["n_image_tokens"]
    f = s["n_heads"] * s["head_dim"]
    flops, byts = DW.dense_attention_work(s, mesh)
    assert flops * mesh[0] * mesh[1] == W.layer_flops(s)["attention"]
    # Q read and O written for the device's rows, K and V read whole.
    assert byts == 2 * f * (2 * n / mesh[1] + 2 * n)


def _run(config, mesh, events, kinds, n_layers=4):
    step = lambda kind: {"kind": kind, "density": 1.0, "pair_sparsity": 0.0}
    return types.SimpleNamespace(
        ops={name: {} for ev in events for name, _, _ in ev},
        sizes=_sizes(config), mesh=mesh, n_layers=n_layers,
        records=[{"trace": [step(k) for k in kinds]}],
        device_events=lambda: events, peaks=lambda: PEAKS)


def test_update_attention_roofline_reads_the_named_kernel():
    read = B.load_reader("update_attention_roofline")
    s = _sizes("hunyuan-video")
    flops, _ = DW.dense_attention_work(s, (1, 4))
    calls = 4 * 3                     # 4 blocks x (2 Update + 1 dense steps)
    at_peak_ns = calls * flops / PEAKS["bf16_flops_per_s"] * 1e9
    # Each chip spends twice the least time in the kernel, in two events.
    events = [[("flashomni_dense_attention.7", 0, at_peak_ns),
               ("flashomni_dense_attention", 0, at_peak_ns),
               ("fusion.3", 0, 5e9)] for _ in range(4)]
    run = _run("hunyuan-video", (1, 4), events,
               ["update", "update", "dense", "dispatch"])
    assert read(run) == pytest.approx(50.0)
    # A program without the kernel (XLA's attention) reads nothing.
    assert read(_run("hunyuan-video", (1, 4), [[("fusion.3", 0, 5e9)]] * 4,
                     ["update"])) is None
