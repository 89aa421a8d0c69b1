"""``gemm_q.grid_occupancy``, the share of GEMM-Q's row slots that are live
(%), read in the HunyuanVideo cell: the same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("gemm_q.grid_occupancy")(run)
