"""``engine.dispatch_density``, the mean live share of (block, head) pairs
over Dispatch steps (%), read in the HunyuanVideo cell: the same reader,
loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("engine.dispatch_density")(run)
