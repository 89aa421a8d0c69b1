"""Roofline share of the csr_attention Pallas kernel on the Dispatch path (%)."""

from chipbench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "csr_attention")
