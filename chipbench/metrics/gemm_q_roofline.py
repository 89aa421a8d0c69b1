"""Roofline share of the gemm_q Pallas kernel on the Dispatch path (%)."""

from chipbench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "gemm_q")
