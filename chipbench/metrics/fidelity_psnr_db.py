"""Mean PSNR of each completed request's output against its engine-off
twin: the same inputs and weights with an all-dense schedule, run after
the window through the same executable."""

from chipbench.readers import psnr


def read(run):
    vals = [psnr(r["out"], run.twin(r)) for r in run.completed]
    return sum(vals) / len(vals)
