"""``csr_attention_roofline`` (%), read in the HunyuanVideo cell: the
same reader, loaded by name."""

from chipbench import bench as B


def read(run):
    return B.load_reader("csr_attention_roofline")(run)
