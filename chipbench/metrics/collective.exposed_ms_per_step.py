"""Collective time with no other operation running on that chip, averaged
over the chips, per denoising step of the traced requests (ms)."""

from chipbench import trace as T


def read(run):
    if not any(op["collective"] for op in run.ops.values()):
        return None
    events = run.device_events()
    exposed = sum(T.exposed_collective_ns(ev, run.ops) for ev in events)
    steps = sum(r["steps"] for r in run.records)
    return exposed / len(events) / 1e6 / steps
