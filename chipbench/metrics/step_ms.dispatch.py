"""Device time of the operations under the sampler's Dispatch branch per
Dispatch step (ms)."""

from chipbench.readers import mode_ms_per_step


def read(run):
    return mode_ms_per_step(run, "dispatch")
