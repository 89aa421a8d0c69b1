"""Run one benchmark cell on the chip and print its result line.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process per run.  Set-up makes the weights and the patchifier on the
device from the seed, and serves one warm-up request, which compiles (or
loads from the persistent compile cache) the one sampler executable the
cell uses.  The window then serves requests through
``repro.launch.batching.run_sequential``, one at a time in a closed loop,
until ``--seconds`` have passed; the request in flight at the deadline
finishes but does not count.  With ``--trace 1`` the profiler records the
window and the per-layer metrics are read from it; otherwise the
end-to-end metrics are reported.  Then a sampled request is compared with
the plain reference (``chipbench.reference``) to decide ``correct``.

The last line of stdout is the result as one JSON object.  Without a TPU,
or with fewer chips than the cell needs, the run exits 1 and prints none.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

from chipbench import bench as B

TRACE_DIR = B.ROOT / ".chipbench" / "trace"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices, or exit 1: never the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"[chipbench] JAX could not start a backend: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"[chipbench] no TPU: JAX runs on {devices[0].platform!r} "
                 f"({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"[chipbench] the cell needs {chips} TPU chips, JAX finds "
                 f"{len(devices)}")
    return devices[:chips]


def import_program() -> None:
    src = B.ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"[chipbench] the program is not in this checkout "
                 f"({src / 'repro'} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


@dataclasses.dataclass
class Run:
    """What the metric readers read (``chipbench/metrics/*.py``)."""

    cell: dict
    devices: list
    setup_s: float
    records: list            # every request of the window, in order
    completed: list          # those that finished by the deadline
    twin: object = None      # record -> engine-off output (np.ndarray)
    trace: dict = None       # {"devices": {plane: events}, "spans": [...]}
    ops: dict = None         # trace.hlo_ops of the sampler executable
    window_s: float = 0.0    # length of the traced window

    @property
    def sizes(self) -> dict:
        return self.cell["model"]["sizes"]

    @property
    def n_layers(self) -> int:
        return self.cell["model"]["n_layers"]

    @property
    def mesh(self) -> tuple:
        return tuple(self.cell["model"]["mesh"])

    def peaks(self) -> dict:
        table = json.loads((B.ROOT / "chipbench" / "peaks.json").read_text())
        kind = self.devices[0].device_kind
        if kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           "chipbench/peaks.json")
        return table["devices"][kind]

    def device_events(self) -> list:
        """Per device: its operations without control-flow wrappers."""
        from chipbench import trace as T
        return [T.leaf_events(ev, self.ops)
                for _, ev in sorted(self.trace["devices"].items())]


class Server:
    """The program set up for one cell and seed: weights and patchifier
    made on the device, the program's configs, and the request path."""

    def __init__(self, cell: dict, seed: int, engine_overrides=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from chipbench import model as M

        self.cell, self.seed = cell, seed
        spec, mix = cell["model"], cell["mix"]
        self.steps = mix["steps"]
        self.cfg, self.ecfg = B.program_configs(spec, mix, engine_overrides)
        self.dtype = jnp.dtype(spec["dtype"])
        sharding = None
        if tuple(spec["mesh"]) != (1, 1):
            from repro.launch.mesh import make_engine_mesh
            sharding = NamedSharding(make_engine_mesh(*spec["mesh"]),
                                     PartitionSpec())
        self.params = M.make_weights(spec, self.dtype, seed, sharding)
        self.patch_embed = M.patch_embed(spec["sizes"], seed)
        self._draw = M.request_inputs(spec["sizes"], mix["batch"])
        self.schedule = self.dense() if mix["schedule"] == "dense" else None
        jax.block_until_ready(self.params)

    def dense(self):
        """An all-dense schedule over the same strategy set: the engine-off
        twin of a request runs through the same executable."""
        from repro.core.schedule import SparsitySchedule
        return SparsitySchedule.from_config(self.ecfg, self.steps,
                                            self.cfg.n_layers,
                                            force_dense=True)

    def inputs(self, index: int):
        """Request ``index``'s noise latents and text embeddings."""
        return self._draw(self.seed, index)

    def request(self, index: int, schedule=None):
        from repro.launch.batching import Request
        x0, text = self.inputs(index)
        return Request(rid=index, x0=x0, text_emb=text, num_steps=self.steps,
                       schedule=schedule or self.schedule)

    def serve(self, req, stats=None) -> dict:
        from repro.launch.batching import run_sequential
        return run_sequential(self.params, self.cfg, self.ecfg, [req],
                              scfg_dtype=self.dtype,
                              patch_embed=self.patch_embed,
                              stats=stats)[req.rid]

    def reference(self, index: int, compute=None) -> tuple:
        """Request ``index``'s inputs (host) and the plain reference's
        output for them, computed in ``compute`` (float32 by default)."""
        import jax.numpy as jnp

        from chipbench import reference
        x0, text = (np.asarray(a) for a in self.inputs(index))
        rc = B.reference_config(self.cell["model"], self.cell["mix"])
        out = reference.sample(self.params, x0, text, self.patch_embed, rc,
                               compute=compute or jnp.float32)
        return x0, out


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, traced: bool,
             devices: list, t0: float, engine_overrides=None) -> dict:
    """Set up, serve the window, read the metrics and check the outputs."""
    import jax

    from chipbench import reference
    from repro.launch.serve import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{cell['name']}: seed {seed}, {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {cache}")
    server = Server(cell, seed, engine_overrides)
    request, serve = server.request, server.serve

    stats: dict = {}
    serve(request(0), stats=stats)            # warm-up: compiles or loads
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s")

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    records, index = [], 1
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        due = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.request"):
            req = request(index)
        with jax.profiler.TraceAnnotation("bench.serve"):
            res = serve(req)
        finish = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.record"):
            records.append(dict(index=index, due=due, finish=finish,
                                steps=server.steps, out=res["out"],
                                trace=res["trace"]))
        index += 1
    window_s = time.perf_counter() - start
    if traced:
        jax.profiler.stop_trace()
    completed = [r for r in records if r["finish"] <= deadline]
    failed = sum(not np.isfinite(r["out"]).all() for r in records)
    log(f"window {window_s:.3f} s: {len(records)} requests, "
        f"{len(completed)} by the deadline, {failed} not finite")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    run = Run(cell=cell, devices=devices, setup_s=setup_s,
              records=records, completed=completed)
    twins: dict = {}

    def twin(rec):
        if rec["index"] not in twins:
            req = request(rec["index"], server.dense())
            twins[rec["index"]] = serve(req)["out"]
        return twins[rec["index"]]

    run.twin = twin
    if traced:
        from chipbench import trace as T
        run.trace = T.load_trace(str(TRACE_DIR))
        run.ops = T.hlo_ops(stats.pop("lower")().compile().as_text())
        run.window_s = window_s
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in B.cell_metrics(bench, cell["name"], kind):
        value = B.load_reader(m["name"])(run) if completed else None
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"attempted": len(records), "failed": int(failed),
              "metrics": metrics, "device": device}
    if traced:
        result["device"].update(busy_s=_busy_s(run), window_s=window_s)
        result["breakdown"] = _breakdown(run)

    # The comparison with the reference, once the program's state is gone.
    stats.clear()
    twins.clear()
    checks, correct = {}, bool(completed) and not failed
    if completed:
        rec = completed[int(np.random.default_rng(seed).integers(
            len(completed)))]
        x0, ref = server.reference(rec["index"])
        got = reference.compare(rec["out"], ref, x0)
        for name, limit in cell["model"]["correct"].items():
            checks[name] = {"value": got[name], "limit": limit}
            correct = correct and got[name] <= limit
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return {"correct": bool(correct), **result, "checks": checks}


def _busy_s(run: Run) -> float:
    from chipbench import trace as T
    events = run.device_events()
    return sum(T.busy_ns(ev) for ev in events) / len(events) / 1e9


def _breakdown(run: Run) -> dict:
    """Top device operations (by step mode and kernel) and the longest idle
    gaps on the first device, named by the benchmark's host span."""
    from chipbench import trace as T
    events = run.device_events()[0]
    labelled = []
    for name, s, d in events:
        op = run.ops.get(name, {})
        base = op.get("kernel") or name.rsplit(".", 1)[0]
        labelled.append((f"{op.get('mode') or 'other'}:{base}", s, d))
    spans = run.trace["spans"]
    window = (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))
    return {"device_ops": T.top_ops(labelled),
            "idle_gaps": T.idle_gaps(events, spans, window)}


def main(argv=None) -> int:
    t0 = time.perf_counter()
    # libtpu would otherwise log to a fixed directory under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = B.load_benchmark()
    cell = B.find_cell(bench, args.workload)
    devices = require_tpu(cell["chips"])
    import_program()
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      devices, t0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
