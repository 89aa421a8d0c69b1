"""``mfu`` (%), the whole step's share of the chips' peak, read in the
HunyuanVideo cell: the same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("mfu")(run)
