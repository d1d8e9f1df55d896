"""Seeded random weights for a configuration, made on the device in one
jitted call, in the layout the served model reads and the type it serves
(bfloat16 matrices, float32 norm vectors).

Matrices are normal with standard deviation fan_in ** -0.5, the embedding
0.02; norm weights are small and random (the norms multiply by 1 + w, so
random w makes the reference show that it follows that form).  The rows
of the embedding (and columns of an untied head) past ``vocab_size`` up
to the next multiple of 256, which the program keeps as padding, are
made too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def key_for(seed: int):
    """A PRNG key from any whole number."""
    seed = int(seed)
    key = jax.random.PRNGKey(abs(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, (abs(seed) >> 32) * 2 + (seed < 0))


def make_weights(conf: dict, seed: int):
    m = conf["model"]
    dims = (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"],
            padded_vocab(m["vocab_size"]), bool(m["tie_word_embeddings"]))
    return _build(dims, key_for(seed))


@functools.partial(jax.jit, static_argnums=0)
def _build(dims, key):
    L, d, nh, nkv, hd, ff, vpad, tied = dims
    ks = iter(jax.random.split(key, 16))
    bf = jnp.bfloat16

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(bf)

    def vec(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * 0.1

    block = {
        "ln": vec((L, d)),
        "wq": mat((L, d, nh * hd), d),
        "wk": mat((L, d, nkv * hd), d),
        "wv": mat((L, d, nkv * hd), d),
        "wo": mat((L, nh * hd, d), nh * hd),
        "ln2": vec((L, d)),
        "wi": mat((L, d, 2 * ff), d),
        "wo_mlp": mat((L, ff, d), ff),
    }
    w = {"embed": (jax.random.normal(next(ks), (vpad, d), jnp.float32)
                   * 0.02).astype(bf),
         "final_norm": vec((d,)),
         "blocks": (block,)}
    if not tied:
        w["lm_head"] = mat((d, vpad), d)
    return w
