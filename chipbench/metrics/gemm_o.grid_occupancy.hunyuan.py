"""``gemm_o.grid_occupancy``, the share of GEMM-O's (row, head) slots that
are live (%), read in the HunyuanVideo cell: the same reader, loaded by
name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("gemm_o.grid_occupancy")(run)
