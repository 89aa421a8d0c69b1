"""``csr_attention.group_fill``, the fill of the CSR attention walk's fused
updates (%), read in the HunyuanVideo cell: the same reader, loaded by
name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("csr_attention.group_fill")(run)
