"""Cells, traffic mixes and metrics are found by name: a later change adds
files and entries and edits no file the benchmark has."""

import json
import shutil
from pathlib import Path

from chipbench import bench as B


def _copy_benchmark(tmp: Path) -> dict:
    shutil.copy(B.ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(B.ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in (tmp / "chipbench").rglob("*")
            if p.is_file()}


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    before = _copy_benchmark(tmp_path)
    cb = tmp_path / "chipbench"
    config = json.loads((cb / "configs" / "flux-mmdit.json").read_text())
    config["n_layers"] = 12
    (cb / "configs" / "flux-mmdit-l12.json").write_text(json.dumps(config))
    (cb / "traffic" / "dense.s50.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "batch": 1, "steps": 50,
         "schedule": "dense"}))
    (cb / "metrics" / "requests_in_window.py").write_text(
        "def read(run):\n    return len(run.completed)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "flux-mmdit-l12", "source": "x",
                             "file": "chipbench/configs/flux-mmdit-l12.json",
                             "reduced": ["n_layers"], "why": "x"})
    bench["workloads"].append({"name": "flux12.dense.s50",
                               "config": "flux-mmdit-l12",
                               "traffic": "dense.s50", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "requests_in_window", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "steps_per_s",
                               "workloads": ["flux12.dense.s50"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = B.load_benchmark(tmp_path)
    cell = B.find_cell(bench, "flux12.dense.s50", tmp_path)
    assert cell["model"]["n_layers"] == 12
    assert cell["mix"]["steps"] == 50 and cell["mix"]["schedule"] == "dense"
    names = [m["name"] for m in B.cell_metrics(bench, cell["name"],
                                                "per_layer")]
    assert "requests_in_window" in names
    assert "requests_in_window" not in [
        m["name"] for m in B.cell_metrics(bench, "flux.sparse.s28",
                                          "per_layer")]
    read = B.load_reader("requests_in_window", tmp_path)
    assert read(type("Run", (), {"completed": [1, 2, 3]})) == 3
    # the reference takes the new configuration's settings from its file
    rc = B.reference_config(cell["model"], cell["mix"])
    assert rc["n_layers"] == 12 and rc["steps"] == 50
    assert rc["schedule"] == "dense"
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def test_every_metric_has_a_reader():
    bench = B.load_benchmark()
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(B.load_reader(m["name"])), m["name"]
    for w in bench["workloads"]:
        cell = B.find_cell(bench, w["name"])
        assert cell["mix"]["steps"] > 0
        assert set(cell["model"]["correct"]) == {"rel_l2"}
