"""Shared pieces of the plain references (float32, highest precision)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def gelu_tanh(x):
    """The tanh form of GELU (the original BERT and GPT-2 code)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)
    ))


def attention(qkv, heads, keep, causal):
    """Multi-head attention over a packed [B, S, 3H] projection. `keep`
    scales the probabilities: the program's dropout is Fluid's
    "downgrade_in_infer", which in test mode multiplies by (1 - p) where
    other frameworks rescale while training (a departure from the
    published models' code, numerically a constant factor)."""
    b, s, h3 = qkv.shape
    h = h3 // 3
    d = h // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(qkv[..., i * h:(i + 1) * h]) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1) * keep
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, h)


def cross_entropy(logits, labels):
    """Mean of -log softmax(logits)[label] over the rows."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return -jnp.mean(picked)
