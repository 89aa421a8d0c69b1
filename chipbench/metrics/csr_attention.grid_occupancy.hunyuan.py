"""``csr_attention.grid_occupancy``, the share of the CSR attention kernel's
grid steps with a live tile (%), read in the HunyuanVideo cell: the same
reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("csr_attention.grid_occupancy")(run)
