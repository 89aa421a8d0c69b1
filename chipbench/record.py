"""Trace one request of a cell on the chip and reduce it by the program's
own names: scopes, kernel names, host spans and live-work counters.

    python -m chipbench.record --workload <cell> --seed <n> --out <dir>

Set-up is ``chipbench.run``'s (weights from the seed, one warm-up
request); then the profiler records one request.  Writes two files under
``<dir>`` and prints the first as the last line of stdout:

- ``summary.json``: device time per step mode (``trace.hlo_ops``) and per
  mode and block part (``scopes``), the kernels' grid occupancies, the
  longest idle gaps named by the innermost host span, device-idle time
  inside ``fo.request``, the host spans' counts and times, and whether
  any ``fo.launch`` compiled;
- ``excerpt.json``: the request as the CPU tests replay it, in the form
  of ``tests/chipbench/data/recorded_request.json``: the compiled HLO cut
  to the instructions that ran (name, opcode, ``op_name``, stack frame)
  with the stack-frame tables they use, per-instruction event counts and
  summed device ns, and the request's per-step program counters.

Exits 1 without a TPU, as ``chipbench.run`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time
from collections import Counter, defaultdict
from pathlib import Path

from chipbench import bench as B
from chipbench import run as R

TRACE_DIR = B.ROOT / ".chipbench" / "record"
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _table_lines(text: str, title: str) -> dict:
    m = re.search(rf"^{title}\n(.*?)(?:\n\n|\Z)", text, re.S | re.M)
    return {int(line.split(" ", 1)[0]): line
            for line in (m.group(1).splitlines() if m else [])}


def _mode_markers(text: str) -> set:
    """Per switch branch, the first instruction whose stack holds the
    function that names the branch's step mode (``trace.MODE_FUNCTIONS``).
    ``trace.hlo_ops`` gives a branch its mode from any instruction under
    it, and JAX keeps only the innermost frames of a stack, so the
    instructions that ran may all lack that function."""
    from chipbench import trace as T

    funcs = T._table(text, "FunctionNames", r'^(\d+) "(.*)"$')
    locs = T._table(text, "FileLocations",
                    r"^(\d+) \{file_name_id=\d+ function_name_id=(\d+)")
    frames = T._table(text, "StackFrames",
                      r"^(\d+) \{file_location_id=(\d+) parent_frame_id=(\d+)")

    def names_mode(fid: int) -> bool:
        seen = set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = (int(v) for v in frames[fid])
            fn = int(locs.get(loc, (0,))[0])
            if funcs.get(fn, ("",))[0] in T.MODE_FUNCTIONS:
                return True
            fid = parent
        return False

    markers, marked = set(), set()
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                     r"/branch_(\d+)_fun/.*stack_frame_id=(\d+)", line)
        if m and m.group(2) not in marked and names_mode(int(m.group(3))):
            markers.add(m.group(1))
            marked.add(m.group(2))
    return markers


def excerpt_hlo(text: str, keep) -> str:
    """The stack-frame tables and the instructions in ``keep``, each cut to
    its name, opcode, custom-call target and metadata, and per switch
    branch the instruction that keeps the branch's step mode
    (``_mode_markers``); source files are named relative to the
    checkout."""
    text = text.replace(f'"{B.ROOT}/', '"')
    keep = set(keep) | _mode_markers(text)
    tables = {t: _table_lines(text, t) for t in _TABLES}
    lines, frames = [], set()
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
    for line in text.splitlines():
        m = inst.match(line)
        if not m or m.group(1) not in keep:
            continue
        meta = re.search(r"(?<![\w])metadata=\{[^}]*\}", line)
        target = re.search(r'custom_call_target="[^"]*"', line)
        lines.append(f"  %{m.group(1)} = () {m.group(2)}()"
                     + (f", {target.group(0)}" if target else "")
                     + (f", {meta.group(0)}" if meta else ""))
        frame = re.search(r"stack_frame_id=(\d+)", line)
        if frame:
            frames.add(int(frame.group(1)))
    locs, files, funcs, todo = set(), set(), set(), list(frames)
    while todo:
        line = tables["StackFrames"].get(todo.pop(), "")
        ids = re.search(r"file_location_id=(\d+) parent_frame_id=(\d+)", line)
        if ids:
            locs.add(int(ids.group(1)))
            parent = int(ids.group(2))
            if parent not in frames:
                frames.add(parent)
                todo.append(parent)
    for loc in locs:
        ids = re.search(r"file_name_id=(\d+) function_name_id=(\d+)",
                        tables["FileLocations"].get(loc, ""))
        if ids:
            files.add(int(ids.group(1)))
            funcs.add(int(ids.group(2)))
    used = {"FileNames": files, "FunctionNames": funcs,
            "FileLocations": locs, "StackFrames": frames}
    out = []
    for t in _TABLES:
        out += [t] + [tables[t][i] for i in sorted(used[t])
                      if i in tables[t]] + [""]
    return "\n".join(out + lines) + "\n"


def reduce_request(text: str, events: list, spans: list, steps: list,
                   window) -> dict:
    """The readings of one traced request: ``events`` the device's
    ``(instruction, start, dur)``, ``spans`` the host spans
    ``(name, start, dur, args)``, ``steps`` the request's per-step
    counters, ``window`` the traced interval (start, end ns)."""
    from chipbench import scopes as S
    from chipbench import trace as T

    ops = T.hlo_ops(text)
    scopes = S.scope_ops(text)
    leaf = T.leaf_events(events, ops)
    kinds = Counter(st["kind"] for st in steps)
    step_ns = T.time_by(leaf, ops, "mode")
    modes = [m for m in ("update", "dispatch", "dense") if kinds[m]]
    host = defaultdict(lambda: [0, 0.0])
    for name, _, d, _ in spans:
        host[name][0] += 1
        host[name][1] += d / 1e6
    labelled = [(f"{(scopes.get(n) or {}).get('mode') or 'other'}:"
                 f"{(scopes.get(n) or {}).get('part') or 'none'}", s, d)
                for n, s, d in leaf]
    return {
        "steps": dict(kinds),
        "step_ms": {m: step_ns.get(m, 0.0) / kinds[m] / 1e6 for m in modes},
        "part_ms": {m: {str(p): ns / kinds[m] / 1e6 for p, ns in sorted(
            S.part_ns(leaf, scopes, m).items(), key=lambda kv: -kv[1])}
            for m in modes},
        "group_ms": {m: S.group_ms_per_step(leaf, scopes, m, kinds[m])
                     for m in modes},
        "kernel_s": {k: ns / 1e9 for k, ns in S.kernel_ns(leaf, scopes).items()},
        "kernel_s_by_file": {k: ns / 1e9 for k, ns in
                             T.time_by(leaf, ops, "kernel").items() if k},
        "occupancy": {k: S.live_share(steps, k) for k in
                      ("csr_tiles", "gemm_q_rows", "gemm_o_heads")},
        "dispatch_density": 100.0 * sum(
            st["density"] for st in steps if st["kind"] == "dispatch")
        / max(kinds["dispatch"], 1),
        "busy_s": T.busy_ns(leaf) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "top_ops": T.top_ops(labelled),
        "idle_gaps": T.idle_gaps(leaf, [sp[:3] for sp in spans], window),
        "idle_ms_per_request": [ns / 1e6 for ns in
                                S.idle_ns_in(leaf, spans, "fo.request")],
        "host_spans": {n: v for n, v in sorted(host.items())},
        "launch_compiled": [bool(a.get("compiled")) for n, _, _, a in spans
                            if n == "fo.launch"],
    }


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = B.find_cell(B.load_benchmark(), args.workload)
    devices = R.require_tpu(cell["chips"])
    R.import_program()

    import jax

    from chipbench import scopes as S
    from chipbench import trace as T
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    server = R.Server(cell, args.seed)
    stats: dict = {}
    server.serve(server.request(0), stats=stats)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.serve"):
        res = server.serve(server.request(1))
    served_s = time.perf_counter() - start
    jax.profiler.stop_trace()
    loaded = T.load_trace(str(TRACE_DIR))
    spans = S.load_spans(str(TRACE_DIR)) + [
        (n, s, d, {}) for n, s, d in loaded["spans"]]
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    text = stats.pop("lower")().compile().as_text()
    plane, events = sorted(loaded["devices"].items())[0]
    serve = [(s, s + d) for n, s, d, _ in spans if n == "bench.serve"][0]
    summary = {"workload": cell["name"], "seed": args.seed,
               "device": {"kind": devices[0].device_kind, "plane": plane},
               "served_s": served_s,
               **reduce_request(text, events, spans, res["trace"], serve)}

    totals = defaultdict(lambda: [0, 0.0])
    for name, _, d in events:
        totals[name][0] += 1
        totals[name][1] += d
    excerpt = {
        "about": (f"One {cell['name']} request on one {devices[0].device_kind}"
                  f" (seed {args.seed}): the sampler's compiled HLO cut to "
                  "the instructions that ran, with their stack-frame tables; "
                  "per-instruction event counts and summed device ns from "
                  "the profiler's XLA Ops line; the request's per-step "
                  "program counters"),
        "hlo": excerpt_hlo(text, set(totals)),
        "events": sorted(([n, c, t] for n, (c, t) in totals.items()),
                         key=lambda e: -e[2]),
        "steps": res["trace"],
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "summary.json").write_text(json.dumps(summary, indent=1) + "\n")
    (out / "excerpt.json").write_text(json.dumps(excerpt) + "\n")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
