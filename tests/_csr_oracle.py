"""What the uniform CSR attention kernel computes, one online-softmax
update at a time: the kernel's own update function, jitted per update as
the interpreter runs it, so a walk that makes the same updates in the
same order gives the same bits."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np


def _group_by_group(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt, blk,
                    q_src, *, group, window=None):
    """Each live row's live KV blocks in ascending id order, cut into runs
    at multiples of ``window`` blocks (the windowed walk's cuts; ``None``:
    one run), each run taken ``group`` blocks an update, the last update
    of a run partial.  Rows past ``q_cnt`` are left as ``o_reuse``."""
    from repro.kernels import flashomni_attention as fa
    scale = q.shape[-1] ** -0.5
    upd = jax.jit(lambda qb, ks, vs, carry: fa._group_update(
        qb, ks, vs, carry, scale))
    out = np.array(o_reuse)
    for b in range(q.shape[0]):
        for c in range(int(q_cnt[b])):
            src = int(q_src[b, c])
            qb = q[b, src * blk:(src + 1) * blk].astype(jnp.float32)
            carry = (jnp.full((blk, 128), -1e30, jnp.float32),
                     jnp.zeros((blk, 128), jnp.float32),
                     jnp.zeros((blk, q.shape[-1]), jnp.float32))
            ids = [int(i) for i in kv_ids[b, c, :int(kv_cnt[b, c])]]
            for _, run in itertools.groupby(
                    ids, key=lambda i: 0 if window is None else i // window):
                run = list(run)
                for j in range(0, len(run), group):
                    blocks = run[j:j + group]
                    carry = upd(qb, [k[b, i * blk:(i + 1) * blk]
                                     for i in blocks],
                                [v[b, i * blk:(i + 1) * blk]
                                 for i in blocks], carry)
            r = int(q_ids[b, c])
            out[b, r * blk:(r + 1) * blk] = np.asarray(
                fa._normalize(carry[2], carry[1]).astype(o_reuse.dtype))
    return out


def _tile_by_tile(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt, blk,
                  q_src):
    """One (q-block, kv-block) tile an update, in ascending id order: the
    bucketed and symbols kernels' order."""
    return _group_by_group(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt,
                           blk, q_src, group=1)
