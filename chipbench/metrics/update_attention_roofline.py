"""Roofline share of the blockwise dense attention kernel of the Update
(and engine-off) steps (%): the least time the chip could take for the
kernel's work (``chipbench.dense_work``, the larger of the FLOP and the
byte bound) over its summed device time, averaged over the chips.  The
kernel is found by its name, which its compiled instruction takes; a
program without it reads nothing."""

from chipbench import dense_work as DW
from chipbench.readers import steps_of


def read(run):
    names = {n for n in run.ops if n.split(".")[0] == DW.KERNEL}
    if not names:
        return None
    per = [sum(d for n, _, d in ev if n in names)
           for ev in run.device_events()]
    seconds = sum(per) / len(per) / 1e9
    calls = run.n_layers * (len(steps_of(run, "update"))
                            + len(steps_of(run, "dense")))
    if seconds <= 0 or not calls:
        return None
    flops, byts = DW.dense_attention_work(run.sizes, run.mesh)
    peak = run.peaks()
    return 100.0 * calls * max(flops / peak["bf16_flops_per_s"],
                               byts / peak["hbm_bytes_per_s"]) / seconds
