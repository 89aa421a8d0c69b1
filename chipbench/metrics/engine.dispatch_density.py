"""Mean live share of (block, head) pairs over the Dispatch steps of the
traced requests (%), from the sampler's per-step counters."""

from chipbench.readers import steps_of


def read(run):
    dens = [st["density"] for st in steps_of(run, "dispatch")]
    return 100.0 * sum(dens) / len(dens) if dens else None
