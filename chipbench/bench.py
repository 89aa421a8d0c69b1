"""What a cell is, found by name: the benchmark file, configurations,
traffic mixes and metric readers.

``BENCHMARK.json`` names the cells.  A cell's configuration is the file its
``configs`` entry names; its traffic mix is ``chipbench/traffic/<traffic>
.json``; each metric is read by ``chipbench/metrics/<metric>.py``, a
module with ``read(run) -> float | None``.  Adding a cell, a mix or a
metric is adding files and entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration and traffic mix loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    cell["config_entry"] = entry
    cell["model"] = json.loads((root / entry["file"]).read_text())
    cell["mix"] = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, root: Path = ROOT):
    """``read`` of ``chipbench/metrics/<name>.py``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_config(model: dict, mix: dict) -> dict:
    """The plain reference's settings, from the configuration and the
    traffic mix alone."""
    s, e, m = model["sizes"], model["engine"], model["engine"]["mask"]
    return dict(
        schedule=mix["schedule"], steps=mix["steps"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_text=s["n_text_tokens"],
        n_layers=model["n_layers"], eps=model["norm_eps"], pool=m["pool"], tau_q=m["tau_q"], tau_kv=m["tau_kv"],
        interval=m["interval"], warmup_steps=m["warmup_steps"],
        order=m["order"], degrade=m["degrade"],
        protect_text=m["protect_text"], cap_q_frac=e["cap_q_frac"],
        cap_kv_frac=e["cap_kv_frac"])


def program_configs(model: dict, mix: dict, engine_overrides=None):
    """The program's ``ArchConfig`` and ``EngineConfig`` for a cell.

    ``engine_overrides`` replaces engine fields (the CPU tests run the
    Pallas kernels in interpret mode)."""
    from repro.configs.registry import get_config
    from repro.core.engine import EngineConfig
    from repro.core.masks import MaskConfig
    from repro.core.strategy import available_strategies

    s = model["sizes"]
    cfg = dataclasses.replace(
        get_config(model["arch"]), n_layers=model["n_layers"],
        d_model=s["d_model"], n_heads=s["n_heads"], n_kv_heads=s["n_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], patch_dim=s["patch_dim"],
        n_text_tokens=s["n_text_tokens"], norm_eps=model["norm_eps"])
    engine = {k: v for k, v in model["engine"].items() if k != "mask"}
    engine.update(engine_overrides or {})
    # The mix names a strategy, a registered schedule, or "dense" (every
    # step engine-off, built per request by the harness).
    sched = mix["schedule"]
    if sched in available_strategies():
        engine["strategy"] = sched
    elif sched != "dense":
        engine["schedule"] = sched
    dp, sp = model["mesh"]
    ecfg = EngineConfig(mask=MaskConfig(**model["engine"]["mask"]),
                        mesh_dp=dp, mesh_sp=sp, **engine)
    return cfg, ecfg
