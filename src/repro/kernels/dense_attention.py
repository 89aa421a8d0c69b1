"""Blockwise dense attention — the Pallas TPU kernel of Update and
engine-off steps.

Exact softmax attention of every query against every key, computed one
``(block_q, block_kv)`` tile at a time with an online softmax (running
max, sum and output accumulated in f32; Q, K, P and V enter the MXU in
their input dtype), so no ``(N_q, N_kv)`` score array exists anywhere:
VMEM holds one tile's scores.  Grid ``(BH, N_q / block_q, N_kv /
block_kv)``, the key axis innermost.

Query tiles are the largest multiple of 16 rows up to :data:`MAX_BLOCK_Q`
that divides the length; key tiles split the keys evenly into as few
multiples of 128 columns up to :data:`MAX_BLOCK_KV` as hold them (wide
key tiles cost fewer online-softmax rescales: on one v5e at flux width a
call took 2.57 ms with 768-key tiles and 2.06 ms with 1,536).  A length
no tile divides is padded to a tile multiple: padded keys are masked out
of the softmax and padded query rows are sliced off the output.

Same result as :func:`repro.core.attention.dense_attention` up to
rounding: that one rounds the scores to the input dtype before the f32
softmax, this one keeps them in f32 and rounds P to the input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MAX_BLOCK_Q", "MAX_BLOCK_KV", "dense_tiles",
           "flashomni_dense_attention"]

MAX_BLOCK_Q = 1024
MAX_BLOCK_KV = 1536
_NEG_INF = -1e30
_LANES = 128


def _even(n: int, step: int, cap: int) -> int:
    """The tile that splits ``n`` evenly into as few tiles, each a multiple
    of ``step`` up to ``cap``, as hold it."""
    tiles = -(-n // cap)
    return -(-(-(-n // tiles)) // step) * step


def _tile(n: int, step: int, cap: int) -> int:
    """The largest multiple of ``step`` up to ``cap`` that divides ``n`` and
    is at least a quarter of ``min(cap, n)``, else :func:`_even`'s."""
    for t in range(min(cap, n) // step * step, step - 1, -step):
        if n % t == 0 and 4 * t >= min(cap, n):
            return t
    return _even(n, step, cap)


def dense_tiles(n_q: int, n_kv: int) -> tuple[int, int]:
    """``(block_q, block_kv)`` :func:`flashomni_dense_attention` uses; a
    length they do not divide is padded to a multiple."""
    return _tile(n_q, 16, MAX_BLOCK_Q), _even(n_kv, _LANES, MAX_BLOCK_KV)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, n_kv: int, block_kv: int, masked: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if masked:
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < n_kv, s, _NEG_INF)
    m_prev = m_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def flashomni_dense_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              scale: Optional[float] = None,
                              interpret: bool = False) -> jax.Array:
    """Softmax attention ``(..., N_q, d)`` of ``q`` over ``k``/``v``
    ``(..., N_kv, d)``, in ``q.dtype``, in the tiles of
    :func:`dense_tiles`."""
    *lead, n_q, d = q.shape
    n_kv = k.shape[-2]
    scale = (d ** -0.5) if scale is None else scale
    bq, bk = dense_tiles(n_q, n_kv)
    nq_pad, nk_pad = -(-n_q // bq) * bq, -(-n_kv // bk) * bk
    flat = lambda a, n: jnp.pad(a.reshape(-1, *a.shape[-2:]),
                                ((0, 0), (0, n - a.shape[-2]), (0, 0)))
    qf, kf, vf = flat(q, nq_pad), flat(k, nk_pad), flat(v, nk_pad)
    bh = qf.shape[0]
    # K and V are read once per query tile; Q read and O written once.
    cost = pl.CostEstimate(
        flops=4 * bh * n_q * n_kv * d, transcendentals=bh * n_q * n_kv,
        bytes_accessed=q.dtype.itemsize * bh * d * (
            2 * nq_pad + 2 * (nq_pad // bq) * nk_pad))
    kernel = functools.partial(_kernel, scale=scale, n_kv=n_kv, block_kv=bk,
                               masked=nk_pad != n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq_pad // bq, nk_pad // bk),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bh, nq_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
        name="flashomni_dense_attention",
    )(qf, kf, vf)
    return out[:, :n_q].reshape(*lead, n_q, d)
