"""Roofline share of the gemm_o Pallas kernel on the Dispatch path (%)."""

from chipbench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "gemm_o")
