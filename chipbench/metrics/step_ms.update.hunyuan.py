"""``step_ms.update``, device time per Update step (ms), read in the
HunyuanVideo cell: the same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("step_ms.update")(run)
