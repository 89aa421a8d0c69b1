"""Percent of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), averaged over the chips."""

from chipbench import trace as T


def read(run):
    events = run.device_events()
    busy = sum(T.busy_ns(ev) for ev in events) / len(events) / 1e9
    return 100.0 * (1.0 - busy / run.window_s)
