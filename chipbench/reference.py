"""Plain reference of what a served request computes.

A rectified-flow Euler sampler over single-stream DiT blocks (adaLN-Zero
modulation, RMSNorm on Q and K, GELU MLP) whose attention follows the
FlashOmni Update-Dispatch method (arXiv 2509.25401, §3.2-§3.5):

* Update step (the first ``warmup_steps`` steps, then every
  ``interval``-th): dense attention.  The caching symbol (per head, per
  ``pool``-token block: recompute or reuse) comes from the pooled
  attention map by the C-and-G cumulative-mass rule with threshold
  ``tau_q`` and the ``degrade`` fallback; the skipping symbol (per head and
  block pair) from the per-row cumulative-mass rule with ``tau_kv``, text
  rows and columns always kept.  Each is clamped to its static capacity
  by attention mass, and blocks beyond the row capacity are reused in
  every head.  The output projection of the reused heads is stored, with
  its finite differences, for Taylor forecasting.
* Dispatch step: live (block, head) pairs attend to their live key blocks
  only; the output is their projection plus the Taylor forecast of the
  stored projection of the reused heads.

Everything is written out here in ``jax.numpy``: nothing of the program
under test is imported.  Matrix products take their operands rounded to
``compute`` (float32 for the reference, a float8 type for the control)
and accumulate in float32 at the highest precision; everything else is
float32.  One jitted step per mode scans the blocks, and attention runs
head by head, so the reference fits beside the weights on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec: str, a, b, compute):
    a = a.astype(compute).astype(F32)
    b = b.astype(compute).astype(F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None]) + shift[:, None]


def _timestep_embedding(t, dim=256, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32) / half)
    ang = t[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _low_mass(scores, tau):
    """True where a block lies in the ascending run whose mass <= tau."""
    order = jnp.argsort(scores, axis=-1)
    cum = jnp.cumsum(jnp.take_along_axis(scores, order, axis=-1), axis=-1)
    picked = cum <= tau * jnp.sum(scores, axis=-1, keepdims=True)
    return jnp.put_along_axis(jnp.zeros(scores.shape, bool), order, picked,
                              axis=-1, inplace=False)


def _keep_top(mask, score, cap):
    """``mask`` with at most ``cap`` True entries: the highest ``score``."""
    if cap >= mask.shape[-1]:
        return mask
    s = jnp.where(mask, score, -jnp.inf)
    _, ids = jax.lax.top_k(s, cap)
    keep = jnp.put_along_axis(jnp.zeros(mask.shape, bool), ids, True,
                              axis=-1, inplace=False)
    return mask & keep


def capacity(t: int, frac: float) -> int:
    return int(min(max(math.ceil(t * frac), 1), t))


def symbols(q, k, rc: dict):
    """Caching mask (B, H, T) and skipping mask (B, H, T, T), True = compute."""
    pool, dh = rc["pool"], q.shape[-1]
    b, h, n, _ = q.shape
    t = n // pool
    qc = q.reshape(b, h, t, pool, dh).mean(-2)
    kc = k.reshape(b, h, t, pool, dh).mean(-2)
    pmap = jax.nn.softmax(
        jnp.einsum("bhid,bhjd->bhij", qc, kc, precision=HIGHEST) * dh ** -0.5,
        axis=-1)
    nt = -(-rc["n_text"] // pool)
    contrib = pmap[..., :nt, nt:].sum(-2)
    guide = jax.nn.softmax(jnp.swapaxes(pmap[..., nt:, :nt], -1, -2),
                           axis=-1).sum(-2)
    reuse = _low_mass(contrib, rc["tau_q"]) & _low_mass(guide, rc["tau_q"])
    m_c = jnp.concatenate([jnp.ones((b, h, nt), bool), ~reuse], axis=-1)
    live_frac = jnp.mean(m_c.astype(F32), axis=-1, keepdims=True)
    m_c = jnp.where(live_frac < rc["degrade"], False, m_c)
    col_mass = pmap.sum(-2)
    m_c = _keep_top(m_c, col_mass, capacity(t, rc["cap_q_frac"]))
    m_s = ~_low_mass(pmap, rc["tau_kv"])
    if rc["protect_text"]:
        text = jnp.arange(t) < nt
        m_s = m_s | text[:, None] | text[None, :]
    m_s = _keep_top(m_s, pmap, capacity(t, rc["cap_kv_frac"]))
    # Row capacity: beyond it a block is reused in every head.
    row_live = m_c.any(axis=1)
    row_score = jnp.where(m_c, col_mass, 0.0).sum(axis=1)
    rows = _keep_top(row_live, row_score, capacity(t, rc["cap_q_frac"]))
    return m_c & rows[:, None, :], m_s


def _attention(q, k, v, allow, compute):
    """Softmax attention head by head.  q, k, v (B, H, N, dh); ``allow``
    (B, H, T, T) block mask or None (dense)."""
    b, h, n, dh = q.shape
    flat = lambda a: a.reshape(b * h, *a.shape[2:])

    def one(args):
        qh, kh, vh, mh = args
        s = _mm("qd,kd->qk", qh, kh, compute) * dh ** -0.5
        if mh is not None:
            rep = n // mh.shape[-1]
            tok = jnp.repeat(jnp.repeat(mh, rep, axis=0), rep, axis=1)
            s = jnp.where(tok, s, -jnp.inf)
        return _mm("qk,kd->qd", jax.nn.softmax(s, axis=-1), vh, compute)

    masks = None if allow is None else flat(allow)
    o = jax.lax.map(one, (flat(q), flat(k), flat(v), masks))
    return o.reshape(b, h, n, dh)


def _taylor_coeffs(order: int, k, interval: int):
    x = k.astype(F32) / interval
    return [x ** i / math.factorial(i) for i in range(order + 1)]


def _block(rc, mode, p, st, x, t_emb, k_since, compute):
    d, heads, eps = rc["d_model"], rc["n_heads"], rc["eps"]
    p = jax.tree.map(lambda a: a.astype(F32), p)
    mod = _mm("bd,df->bf", jax.nn.silu(t_emb), p["adaln"], compute) + p["adaln_b"]
    sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
    xa = _modulate(_rms(x, eps), sh_a, sc_a)
    b, n, _ = x.shape

    def heads_of(w):
        return _mm("bnd,df->bnf", xa, w, compute).reshape(
            b, n, heads, -1).transpose(0, 2, 1, 3)

    q = _rms(heads_of(p["wq"]), 1e-6) * p["q_scale"]
    k = _rms(heads_of(p["wk"]), 1e-6) * p["k_scale"]
    v = heads_of(p["wv"])
    wo = p["wo"].reshape(heads, -1, d)
    pool = rc["pool"]

    def project(o, live):                        # live (B, H, T) or None
        o = o.transpose(0, 2, 1, 3)              # (B, N, H, dh)
        if live is not None:
            tok = jnp.repeat(jnp.swapaxes(live, 1, 2), pool, axis=1)
            o = jnp.where(tok[..., None], o, 0.0)
        return _mm("bnhd,hdf->bnf", o, wo, compute)

    if mode == "dense":
        out = project(_attention(q, k, v, None, compute), None)
    elif mode == "update":
        o = _attention(q, k, v, None, compute)
        m_c, m_s = symbols(q, k, rc)
        out = project(o, None)
        reused = project(o, ~m_c)
        new = [reused]
        for i in range(1, rc["order"] + 1):
            new.append(new[i - 1] - st["derivs"][i - 1])
        n_upd = st["n_updates"] + 1
        derivs = jnp.stack([jnp.where(i < n_upd, di, 0.0)
                            for i, di in enumerate(new)])
        st = dict(m_c=m_c, m_s=m_s, derivs=derivs, n_updates=n_upd)
    else:
        o = _attention(q, k, v, st["m_s"], compute)
        coef = _taylor_coeffs(rc["order"], k_since, rc["interval"])
        forecast = sum(c * di for c, di in zip(coef, st["derivs"]))
        out = project(o, st["m_c"]) + forecast
    x = x + g_a[:, None] * out
    xm = _modulate(_rms(x, eps), sh_m, sc_m)
    y = jax.nn.gelu(_mm("bnd,df->bnf", xm, p["mlp_wi"], compute))
    y = _mm("bnf,fd->bnd", y, p["mlp_wo"], compute)
    return x + g_m[:, None] * y, st


@functools.partial(jax.jit, static_argnames=("rc_items", "mode", "compute"),
                   donate_argnames=("states",))
def _step(params, states, x, text, patch_embed, t, k_since, *, rc_items,
          mode, compute):
    rc = dict(rc_items)
    h = jnp.concatenate([text, _mm("bnp,pd->bnd", x, patch_embed, compute)],
                        axis=1)
    f32 = lambda a: a.astype(F32)
    t_emb = _mm("bk,kd->bd", _timestep_embedding(t * 1000.0),
                f32(params["t_mlp1"]), compute)
    t_emb = _mm("bd,de->be", jax.nn.silu(t_emb), f32(params["t_mlp2"]), compute)

    def body(h, layer):
        p, st = layer
        return _block(rc, mode, p, st, h, t_emb, k_since, compute)

    h, states = jax.lax.scan(body, h, (params["blocks"], states))
    mod = _mm("bd,df->bf", jax.nn.silu(t_emb), f32(params["final_mod"]),
              compute)
    sh, sc = jnp.split(mod, 2, axis=-1)
    h = _modulate(_rms(h, rc["eps"]) * f32(params["final_norm"]), sh, sc)
    vel = _mm("bnd,dp->bnp", h[:, rc["n_text"]:], f32(params["final_proj"]),
              compute)
    return x + vel / rc["steps"], states


def step_mode(step: int, rc: dict) -> str:
    """``dense`` for an engine-off schedule; otherwise ``update`` for the
    warm-up steps and every ``interval``-th step after, else ``dispatch``."""
    if rc["schedule"] == "dense":
        return "dense"
    if rc["schedule"] != "flashomni":
        raise NotImplementedError(
            f"the reference has no symbol rule for {rc['schedule']!r}")
    w = rc["warmup_steps"]
    update = step < w or (step - w) % rc["interval"] == 0
    return "update" if update else "dispatch"


def sample(params, x0, text, patch_embed, rc: dict, compute=F32):
    """Denoise ``x0`` (B, N_v, patch_dim) in ``rc["steps"]`` steps; returns
    the latents as a host array."""
    b, nv, _ = x0.shape
    n = nv + text.shape[1]
    t_blocks = n // rc["pool"]
    if n % rc["pool"]:
        raise ValueError(f"{n} tokens do not divide into {rc['pool']}-token "
                         "blocks")
    layers, h = rc["n_layers"], rc["n_heads"]
    states = dict(
        m_c=jnp.ones((layers, b, h, t_blocks), bool),
        m_s=jnp.ones((layers, b, h, t_blocks, t_blocks), bool),
        derivs=jnp.zeros((layers, rc["order"] + 1, b, n, rc["d_model"]), F32),
        n_updates=jnp.zeros((layers,), jnp.int32))
    rc_items = tuple(sorted(rc.items()))
    x = jnp.asarray(x0, F32)
    text = jnp.asarray(text, F32)
    last = 0
    for i in range(rc["steps"]):
        mode = step_mode(i, rc)
        if mode == "update":
            last = i
        t = jnp.full((b,), i / rc["steps"], F32)
        x, states = _step(params, states, x, text, patch_embed, t,
                          jnp.int32(i - last), rc_items=rc_items, mode=mode,
                          compute=compute)
    return np.asarray(x)


def compare(out: np.ndarray, ref: np.ndarray, x0: np.ndarray) -> dict:
    """The number that decides ``correct`` for one request: ``rel_l2``,
    the relative L2 gap over what the model added to the noise
    (``out - x0``)."""
    got = np.asarray(out, np.float64) - x0
    want = np.asarray(ref, np.float64) - x0
    return {"rel_l2": float(np.linalg.norm(got - want)
                            / np.linalg.norm(want))}
