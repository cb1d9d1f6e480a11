"""Reference equations that architectures share, in float32, written from
the published descriptions and imported from nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, scale=None):
    """float8_e4m3fn with a per-tensor scale (the tensor's own amax, or a
    fixed ``scale``); the gradient passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX \
        if scale is None else scale
    # the barrier keeps the float8 value: a compiler that may keep excess
    # precision is free to drop a cast pair inside one fusion
    q = jax.lax.optimization_barrier((x / s).astype(jnp.float8_e4m3fn))
    q = q.astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    if quant:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HI)


def _qp(p, scales):
    """The weights as the forward reads them: in the control, each leaf
    held in float8 on the grid of its fixed scale (the float32 master
    beside it takes the update, as the program keeps one beside its
    bfloat16 weights)."""
    if scales is None:
        return p
    return {k: _q8(v, scales[k]) for k, v in p.items()}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x [B, S, H, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, group=8, window=None):
    """softmax(q k^T / sqrt(hd) + causal mask) v, a few heads at a time
    (each group recomputed in the backward) so the scores fit.  With a
    ``window``, position ``s`` sees only ``t`` with ``s - t < window``."""
    B, S, H, hd = q.shape
    G = k.shape[2]
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)
    mask = jnp.tril(jnp.ones((S, S), bool))
    if window is not None:
        pos = jnp.arange(S)
        mask = mask & (pos[:, None] - pos[None, :] < window)

    @jax.checkpoint
    def heads(qkv):
        qh, kh, vh = qkv                                    # [B, S, g, hd]
        s = jnp.einsum("bsgd,btgd->bgst", qh, kh, precision=HI) / math.sqrt(hd)
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgst,btgd->bsgd", p, vh, precision=HI)

    g = min(group, H)
    split = lambda a: a.reshape(B, S, H // g, g, hd).transpose(2, 0, 1, 3, 4)  # noqa: E731
    out = jax.lax.map(heads, (split(q), split(k), split(v)))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, S, H * hd)
