"""``step_ms.dispatch``, device time per Dispatch step (ms), read in the
HunyuanVideo cell: the same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("step_ms.dispatch")(run)
