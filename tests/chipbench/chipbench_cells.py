"""Small cells for the CPU tests of the benchmark: the committed
configuration and traffic files with the widths, depth and steps cut to
what the Pallas interpreter runs in seconds."""

import json

from chipbench import bench as B
from chipbench import model as M

PUBLISHED_WIDTH = 3072

# The CPU runs the Pallas kernels in interpret mode.
INTERPRET = {"backend": "pallas", "interpret": True}


def small_cell(config="flux-mmdit", traffic="sparse.s28", **sizes) -> dict:
    """A cell of the committed configuration and traffic files, cut to
    test size."""
    root = B.ROOT / "chipbench"
    spec = json.loads((root / "configs" / f"{config}.json").read_text())
    mix = json.loads((root / "traffic" / f"{traffic}.json").read_text())
    spec["n_layers"] = 2
    spec["sizes"].update(d_model=256, n_heads=2, head_dim=128, d_ff=512,
                         n_text_tokens=128, n_image_tokens=512)
    spec["sizes"].update(sizes)
    mix["steps"] = 8
    return {"name": f"{config}.{traffic}", "chips": 1, "model": spec,
            "mix": mix}


def full_width_modulation():
    """Scale the seeded timestep and adaLN weights so that the modulation
    (shifts, scales, gates) has the magnitude it has at the published
    width: at a test width the 0.02-scaled chain leaves gates near 0.006
    and the blocks barely touch the output.  Returns the undo."""
    import jax
    import jax.numpy as jnp

    draw = M._params

    def params(sizes, n_layers, qk_gain, dtype, key):
        p = draw(sizes, n_layers, qk_gain, jnp.float32, key)
        g = (PUBLISHED_WIDTH / sizes["d_model"]) ** 0.5
        for leaf in ("t_mlp2", "final_mod"):
            p[leaf] = p[leaf] * g
        p["blocks"]["adaln"] = p["blocks"]["adaln"] * g
        return jax.tree.map(lambda a: a.astype(dtype), p)

    M._params = params
    return lambda: setattr(M, "_params", draw)



class TickClock:
    """A stand-in for ``time`` in ``chipbench.run`` whose clock advances one
    second per reading: a window of 3.5 s then serves exactly one request,
    which finishes by the deadline, however slow the machine is."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        self.now += 1.0
        return self.now
