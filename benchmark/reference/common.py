"""Plain pieces the per-architecture references share: float32 throughout,
no kernel, no cache, no batching tricks.  Every reference runs under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise done in bf16 passes) and walks the layers in a Python loop, one
layer's weights upcast at a time, so it needs one layer of float32
weights on the device, not the model.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def attention(q, k, v, window=None):
    """Causal softmax attention.  q: [B, S, H, D]; k, v: [B, S, Hkv, D]
    (each key/value head serves H/Hkv query heads).  ``window``: a query
    at position i sees keys i-window+1 .. i."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def rope(x, positions, theta):
    """Rotary embedding, half-split layout: dims (i, i + D/2) are a pair
    turned by ``position * theta**(-2i/D)``.  x: [B, S, H, D]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[..., None].astype(F32) * inv          # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def cross_entropy(logits, labels):
    """Mean negative log-likelihood over the labels that are not -100."""
    keep = labels != -100
    safe = jnp.where(keep, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return (nll * keep).sum() / jnp.maximum(keep.sum(), 1)


def run_layers(layer_fn, x, stacked, n_layers, device):
    """``x`` through ``n_layers`` layers whose weights are stacked on axis
    0 of every leaf of ``stacked`` (wherever they live, in whatever
    dtype): slice one layer, bring it to ``device`` in float32, apply."""
    step = jax.jit(layer_fn)
    for i in range(n_layers):
        w = jax.tree.map(lambda a: jax.device_put(a[i], device), stacked)
        x = step(x, f32(w))
    return x


highest = partial(jax.default_matmul_precision, "highest")
