"""Small cells for the CPU tests of the benchmark: the committed
configuration and traffic files with the widths, depth and steps cut to
what the Pallas interpreter runs in seconds."""

import functools
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


def avals(jaxpr):
    """Every abstract value of ``jaxpr`` and of the jaxprs inside it."""
    import jax.extend.core

    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            yield var.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from avals(sub)


HUNYUAN_TOKENS = (256, 32_768)          # text, video: the paper's 33K setting


def hunyuan_step_args(n_layers: int, place=lambda token_dim: None):
    """The reference's settings and one step's arguments, as shapes, at
    256 text + 32,768 video tokens and HunyuanVideo's widths (those of
    flux-mmdit: d_model 3072, 24 heads x 128, d_ff 12,288), bf16 weights.
    ``place(token_dim)`` gives each argument's sharding: ``token_dim`` is
    the dimension split by token, or None where the argument is whole."""
    import jax
    import jax.numpy as jnp

    spec = json.loads((B.ROOT / "chipbench" / "configs" /
                       "flux-mmdit.json").read_text())
    nt, nv = HUNYUAN_TOKENS
    spec["n_layers"] = n_layers
    spec["sizes"].update(n_text_tokens=nt, n_image_tokens=nv)
    rc = B.reference_config(spec, {"schedule": "flashomni", "steps": 50})
    s = spec["sizes"]
    d, h, n = s["d_model"], s["n_heads"], nt + nv
    t = n // rc["pool"]

    def arg(shape, dtype, token_dim=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place(token_dim))

    params = jax.eval_shape(functools.partial(
        M._params, s, n_layers, spec["weights"]["qk_norm_gain"],
        jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: arg(a.shape, a.dtype), params)
    states = dict(
        m_c=arg((n_layers, 1, h, t), bool),
        m_s=arg((n_layers, 1, h, t, t), bool),
        derivs=arg((n_layers, rc["order"] + 1, 1, n, d), jnp.float32, 3),
        n_updates=arg((n_layers,), jnp.int32))
    f32 = jnp.float32
    return rc, (params, states, arg((1, nv, s["patch_dim"]), f32, 1),
                arg((1, nt, d), f32), arg((s["patch_dim"], d), f32),
                arg((1,), f32), arg((), jnp.int32))
