"""``gemm_o_roofline``, the GEMM-O kernel's roofline share (%), read in the
HunyuanVideo cell: the same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("gemm_o_roofline")(run)
