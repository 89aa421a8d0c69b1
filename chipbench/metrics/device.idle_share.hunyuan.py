"""``device.idle_share``, the share of the traced window with no operation on
the chips (%), read in the HunyuanVideo cell: the same reader, loaded by
name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("device.idle_share")(run)
