"""Weights and request inputs, made on the device from ``--seed``.

The draw follows ``repro.launch.serve.init_weights`` and
``repro.models.dit.init_params`` (same leaves, shapes and scales: normal
weights scaled by ``fan_in ** -0.5``, adaLN and embedding weights by
0.02), except that the RMSNorm gains of Q and K are the configuration's
``qk_norm_gain``: with gains of 1, random Q and K give attention logits of
unit spread, nearly flat attention, and outputs that hardly depend on
which blocks are attended.  It lives here so that the benchmark, not the
program, decides what the weights are, and the plain reference can use
them without taking anything the program made.  One jitted call draws the
whole stack in f32 inside the program and emits it in the serving dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _words(seed: int, stream: int) -> list[int]:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream & 0xFFFFFFFF]


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``(seed, stream)``; ``seed`` may exceed 32 bits."""
    key = jax.random.PRNGKey(0)
    for word in _words(seed, stream):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def _block(sizes: dict, qk_gain: float, key) -> dict:
    d, f = sizes["d_model"], sizes["n_heads"] * sizes["head_dim"]
    ff = sizes["d_ff"]
    ks = jax.random.split(key, 7)
    n = jax.random.normal
    return {
        "wq": n(ks[0], (d, f)) * d ** -0.5,
        "wk": n(ks[1], (d, f)) * d ** -0.5,
        "wv": n(ks[2], (d, f)) * d ** -0.5,
        "wo": n(ks[3], (f, d)) * f ** -0.5,
        "q_scale": jnp.full((sizes["head_dim"],), qk_gain),
        "k_scale": jnp.full((sizes["head_dim"],), qk_gain),
        "mlp_wi": n(ks[4], (d, ff)) * d ** -0.5,
        "mlp_wo": n(ks[5], (ff, d)) * ff ** -0.5,
        "adaln": n(ks[6], (d, 6 * d)) * 0.02,
        "adaln_b": jnp.zeros((6 * d,)),
    }


def _params(sizes: dict, n_layers: int, qk_gain: float, dtype, key) -> dict:
    kb, kt, kf, kp = jax.random.split(key, 4)
    d = sizes["d_model"]
    keys = jax.vmap(lambda i: jax.random.fold_in(kb, i))(jnp.arange(n_layers))
    params = {
        "blocks": jax.vmap(lambda k: _block(sizes, qk_gain, k))(keys),
        "t_mlp1": jax.random.normal(kt, (256, d)) * 0.02,
        "t_mlp2": jax.random.normal(jax.random.fold_in(kt, 1), (d, d)) * 0.02,
        "final_mod": jax.random.normal(kf, (d, 2 * d)) * 0.02,
        "final_proj": jax.random.normal(kp, (d, sizes["patch_dim"])) * 0.02,
        "final_norm": jnp.ones((d,)),
    }
    return jax.tree.map(lambda a: a.astype(dtype), params)


def make_weights(spec: dict, dtype, seed: int, sharding=None) -> dict:
    """The served weights of configuration ``spec``, in ``dtype``, placed
    by ``sharding`` as made."""
    fn = functools.partial(_params, spec["sizes"], spec["n_layers"],
                           spec["weights"]["qk_norm_gain"], dtype)
    return jax.jit(fn, out_shardings=sharding)(seed_key(seed, 0))


def request_inputs(sizes: dict, batch: int):
    """A jitted draw of one request's inputs: ``f(seed, index)`` gives
    ``x0`` (B, image tokens, patch_dim) noise latents and ``text``
    (B, text tokens, d_model) text embeddings, f32 as the serving path
    takes them.  Compiled once in set-up; each call is one small program."""
    nv, nt = sizes["n_image_tokens"], sizes["n_text_tokens"]
    d, pd = sizes["d_model"], sizes["patch_dim"]

    @jax.jit
    def draw(words):
        key = jax.random.PRNGKey(0)
        for i in range(3):
            key = jax.random.fold_in(key, words[i])
        kx, kt = jax.random.split(key)
        return (jax.random.normal(kx, (batch, nv, pd)),
                jax.random.normal(kt, (batch, nt, d)))

    def inputs(seed: int, index: int):
        return draw(np.asarray(_words(seed, 2 + index), np.uint32))

    return inputs


def patch_embed(sizes: dict, seed: int) -> jax.Array:
    """The stub patchifier (patch_dim, d_model), f32."""
    shape = (sizes["patch_dim"], sizes["d_model"])
    return jax.jit(lambda k: jax.random.normal(k, shape) * 0.2)(
        seed_key(seed, 1))
