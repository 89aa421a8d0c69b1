"""From a profiler trace and the compiled program's HLO text to intervals.

``load_trace`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the
device operations of each TPU (the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane) and the benchmark's own host spans (names that
start with ``bench.``).  ``hlo_ops`` reads the compiled module's text and
tells, for each instruction name, which step mode of the sampler's
``lax.switch`` it runs under, which Pallas kernel it is (by the source
file of the ``pallas_call``), and whether it is a collective.  The
functions below reduce those to the numbers the metric readers report.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

# Source file of a pallas_call -> the kernel's name in metric names.
KERNEL_FILES = {"gemm_q.py": "gemm_q", "gemm_o.py": "gemm_o",
                "flashomni_attention.py": "csr_attention"}
# The engine entry that marks which switch branch is which step mode.
MODE_FUNCTIONS = {"update_layer": "update", "dispatch_layer": "dispatch"}
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
# Instructions whose events would span their children's.
CONTROL = ("while", "conditional", "call")


def _table(text: str, title: str, pattern: str) -> dict:
    m = re.search(rf"^{title}\n(.*?)(?:\n\n|\Z)", text, re.S | re.M)
    if not m:
        return {}
    return {int(g[0]): g[1:]
            for g in re.findall(pattern, m.group(1), re.M)}


def hlo_ops(text: str) -> dict:
    """``{instruction: {"mode", "kernel", "collective", "opcode"}}``."""
    files = _table(text, "FileNames", r'^(\d+) "(.*)"$')
    funcs = _table(text, "FunctionNames", r'^(\d+) "(.*)"$')
    locs = _table(text, "FileLocations",
                  r"^(\d+) \{file_name_id=(\d+) function_name_id=(\d+)")
    frames = _table(text, "StackFrames",
                    r"^(\d+) \{file_location_id=(\d+) parent_frame_id=(\d+)")

    def chain(fid: int):
        seen = set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = (int(v) for v in frames[fid])
            f, fn = (int(v) for v in locs.get(loc, (0, 0)))
            yield files.get(f, ("",))[0], funcs.get(fn, ("",))[0]
            fid = parent

    ops, branch_funcs = {}, defaultdict(set)
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
    for line in text.splitlines():
        m = inst.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        branch = re.search(r"/branch_(\d+)_fun/", op_name)
        frame = re.search(r"stack_frame_id=(\d+)", line)
        stack = list(chain(int(frame.group(1)))) if frame else []
        kernel = None
        if "tpu_custom_call" in line and stack:
            kernel = KERNEL_FILES.get(stack[0][0].rsplit("/", 1)[-1])
        b = int(branch.group(1)) if branch else None
        if b is not None:
            branch_funcs[b].update(fn for _, fn in stack)
        collective = opcode.startswith(COLLECTIVES) or (
            opcode.startswith("async") and any(c in line for c in COLLECTIVES))
        ops[name] = {"branch": b, "kernel": kernel, "opcode": opcode,
                     "collective": collective}
    modes = {}
    for b, fns in branch_funcs.items():
        for fn, mode in MODE_FUNCTIONS.items():
            if fn in fns:
                modes[b] = mode
    for op in ops.values():
        op["mode"] = modes.get(op.pop("branch"))
    return ops


def instruction(event_name: str) -> str:
    """The HLO instruction an op event names: a TPU trace names the event by
    the instruction's whole text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return re.match(r"%?([\w.\-]+)", event_name).group(1)


def load_trace(trace_dir: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "spans": [...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (instruction(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "spans": spans}


def union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals of ``(start, duration)`` pairs."""
    out = []
    for s, d in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def covered(ivs) -> float:
    return sum(e - s for s, e in ivs)


def intersect(a, b) -> float:
    """Total length in both of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def leaf_events(events, ops: dict):
    """Events of one device, without control-flow instructions."""
    return [ev for ev in events
            if ops.get(ev[0], {}).get("opcode") not in CONTROL]


def busy_ns(events) -> float:
    return covered(union((s, d) for _, s, d in events))


def time_by(events, ops: dict, key: str) -> dict:
    """Summed device time per value of ``ops[name][key]``."""
    out = defaultdict(float)
    for name, _, d in events:
        out[ops.get(name, {}).get(key)] += d
    return dict(out)


def exposed_collective_ns(events, ops: dict) -> float:
    """Collective time during which no other operation runs."""
    coll = union((s, d) for n, s, d in events
                 if ops.get(n, {}).get("collective"))
    comp = union((s, d) for n, s, d in events
                 if not ops.get(n, {}).get("collective"))
    return covered(coll) - intersect(coll, comp)


def top_ops(events, k: int = 10) -> list:
    """The ``k`` names with the most summed device time, in seconds."""
    out = defaultdict(float)
    for name, _, d in events:
        out[name] += d
    return sorted(([n, t / 1e9] for n, t in out.items()),
                  key=lambda p: -p[1])[:k]


def idle_gaps(events, spans, window, k: int = 10) -> list:
    """The ``k`` longest idle gaps inside ``window`` (start, end ns), each
    named by the host span that covers the gap's middle."""
    busy = union((s, d) for _, s, d in events)
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        host = [(hd, n) for n, hs, hd in spans if hs <= mid <= hs + hd]
        named.append([min(host)[1] if host else "none", (e - s) / 1e9])
    return named
