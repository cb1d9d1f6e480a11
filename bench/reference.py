"""Plain float32 reference of a training cell: the model's equations, the
loss, the gradients and AdamW, written from the published descriptions
and imported from nothing of the program.

It runs layer by layer: the forward keeps each layer's input, the
backward recomputes one layer at a time under ``jax.vjp``, so that the
whole model never has to be live at once.  Layer ``g`` and its optimizer
state live on ``devices[g * len(devices) // layers]``, so a model whose
float32 state does not fit one chip spreads over the cell's chips.

``mode`` selects what runs in the program's place:

- ``"f32"``: the reference, every matmul at ``Precision.HIGHEST``.
- ``"fp8"``: the control, one precision below the configuration's
  bfloat16: every weight as the forward reads it (the float32 master
  stays for the update, as the program keeps one beside its bfloat16
  weights), and both operands of every projection and of the head, are
  rounded to float8_e4m3fn with a per-tensor scale (the gradient passes
  straight through); the rest as ``"f32"``.
- ``"half"``: a fault, half of the microbatches left out and the mean
  taken over the rest.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import model as M
from bench import weights as W
from bench.synthetic import SyntheticLM

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


def _scales(p):
    """Per-leaf float8 scales, fixed from the weights as first made."""
    return {k: jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / F8_MAX
            for k, v in p.items()}


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


def _causal_attention(q, k, v, group=8):
    """softmax(q k^T / sqrt(hd) + causal mask) v, a few heads at a time
    (each group recomputed in the backward) so the scores fit."""
    B, S, H, hd = q.shape
    G = k.shape[2]
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)
    mask = jnp.tril(jnp.ones((S, S), bool))

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


def attn_layer(m: M.Model, p, x, scales):
    B, S, d = x.shape
    p, quant = _qp(p, scales), scales is not None
    h = _rmsnorm(x, p["norm1.scale"], m.eps)
    q = _mm(h, p["attn.wq"], quant).reshape(B, S, m.heads, m.hd)
    k = _mm(h, p["attn.wk"], quant).reshape(B, S, m.kv_heads, m.hd)
    v = _mm(h, p["attn.wv"], quant).reshape(B, S, m.kv_heads, m.hd)
    o = _causal_attention(_rope(q, m.rope_theta), _rope(k, m.rope_theta), v)
    x = x + _mm(o, p["attn.wo"], quant)
    h = _rmsnorm(x, p["norm2.scale"], m.eps)
    a = jax.nn.silu(_mm(h, p["mlp.wg"], quant)) * _mm(h, p["mlp.wi"], quant)
    return x + _mm(a, p["mlp.wo"], quant)


def embed(m: M.Model, table, tok, scale=None):
    if scale is not None:
        table = _q8(table, scale)
    return table[tok] * math.sqrt(m.d)


def head_loss(m: M.Model, s, x, labels, scales):
    s, quant = _qp(s, scales), scales is not None
    h = _rmsnorm(x, s["final_norm.scale"], m.eps)
    w = s["embed.head"] if "embed.head" in s else s["embed.tokens"].T
    logits = _mm(h, w, quant)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def lr_at(o: dict, step: int) -> float:
    """Linear warm-up, then cosine (or linear, or constant) decay to
    ``min_lr_ratio`` of the peak at ``total_steps``."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    frac = min(max((step - o["warmup_steps"]) /
                   max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    r = o["min_lr_ratio"]
    decay = {"cosine": r + (1 - r) * 0.5 * (1 + math.cos(math.pi * frac)),
             "linear": 1.0 - (1 - r) * frac}.get(o["schedule"], 1.0)
    return o["lr"] * warm * decay


def _adam(p, g, mu, nu, *, lr, clip, step, o, dec):
    b1, b2 = o["beta1"], o["beta2"]
    g = g * clip
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    u = (mu / (1 - b1 ** step)) / (jnp.sqrt(nu / (1 - b2 ** step)) + o["eps"])
    if dec:
        u = u + o["weight_decay"] * p
    return p - lr * u, mu, nu


def train(m: M.Model, traffic: dict, seed: int, devices, *,
          mode="f32") -> dict:
    """Runs the cell's first ``traffic["check_steps"]`` steps and returns
    the readings that the check compares: ``losses``, ``grad`` (norm of
    the clipped first gradient per (layer, leaf)), ``grad_sketch`` (its
    ``weights.sketch``) and ``change`` (norm of each leaf's change after
    the last step)."""
    steps = traffic["check_steps"]
    o = traffic["optimizer"]
    L = m.layers
    dev = [devices[g * len(devices) // L] for g in range(L)]
    first, last = devices[0], dev[-1]
    key0 = W.base_key(W.seed32(seed))
    lleaves, sleaves = M.layer_leaves(m), M.shared_leaves(m)

    def make_layer(g):
        """Layer ``g``'s weights, made on the device that holds it."""
        return jax.jit(lambda: {
            p: W.leaf(key0, g, i, s, init, dt).astype(jnp.float32)
            for i, (p, s, init, dt) in enumerate(lleaves)},
            out_shardings=SingleDeviceSharding(dev[g]))()

    def make_shared():
        return jax.jit(lambda: {
            p: W.leaf(key0, W.SHARED, i, s, init, dt).astype(jnp.float32)
            for i, (p, s, init, dt) in enumerate(sleaves)},
            out_shardings=SingleDeviceSharding(first))()

    fwd = jax.jit(lambda p, x, sc: attn_layer(m, p, x, sc))
    bwd = jax.jit(lambda p, x, gy, sc: jax.vjp(
        lambda p, x: attn_layer(m, p, x, sc), p, x)[1](gy))
    emb = jax.jit(lambda t, tok, sc: embed(m, t, tok, sc))
    emb_bwd = jax.jit(lambda t, tok, gx, sc: jax.vjp(
        lambda t: embed(m, t, tok, sc), t)[1](gx)[0])
    head = jax.jit(jax.value_and_grad(
        lambda s, x, lab, sc: head_loss(m, s, x, lab, sc), argnums=(0, 1)))
    onto = jax.jit(lambda p, sc: {k: _q8(v, sc[k]) for k, v in p.items()})
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    sq = jax.jit(lambda t: {k: jnp.sum(v * v) for k, v in t.items()})
    scale = jax.jit(lambda t, c: jax.tree.map(lambda a: a * c, t))
    def zeros(t):
        # made on each leaf's own device: a jitted zeros_like reads no
        # input and would land on the default device
        return {k: jnp.zeros(v.shape, v.dtype, device=v.sharding)
                for k, v in t.items()}

    params = [make_layer(g) for g in range(L)]
    shared = make_shared()
    lsc, ssc = [None] * L, None
    if mode == "fp8":
        # the control's weights are made in float8, as the program's are
        # made in bfloat16: the master starts on the float8 grid
        lsc = [jax.jit(_scales)(p) for p in params]
        ssc = jax.jit(_scales)(shared)
        params = [onto(p, sc) for p, sc in zip(params, lsc)]
        shared = onto(shared, ssc)
    init_l = [jax.tree.map(jnp.copy, p) for p in params]
    init_s = jax.tree.map(jnp.copy, shared)
    mu = [zeros(p) for p in params]
    nu = [zeros(p) for p in params]
    smu, snu = zeros(shared), zeros(shared)

    def adam_tree(p, gr, mu_, nu_, lr, clip, step):
        out = {k: _adam_jit(p[k], gr[k], mu_[k], nu_[k], lr, clip, step,
                            M.decays(k, p[k].shape)) for k in p}
        return ({k: v[0] for k, v in out.items()},
                {k: v[1] for k, v in out.items()},
                {k: v[2] for k, v in out.items()})

    @functools.partial(jax.jit, static_argnums=(7,))
    def _adam_jit(p, g, mu_, nu_, lr, clip, step, dec):
        return _adam(p, g, mu_, nu_, lr=lr, clip=clip, step=step, o=o,
                     dec=dec)

    mcount = traffic["microbatches"]
    use = mcount // 2 if mode == "half" else mcount
    if mode not in ("f32", "fp8", "half"):
        raise ValueError(f"unknown mode {mode!r}")
    data = SyntheticLM(m.vocab, traffic["seq_len"] + 1, seed)
    losses, first_grad = [], None
    for step in range(1, steps + 1):
        rows = data.next_batch(mcount * traffic["microbatch_size"])
        rows = rows.reshape(mcount, traffic["microbatch_size"], -1)[:use]
        gl = [None] * L
        gs, loss = None, 0.0
        s_last = jax.device_put(shared, last)
        sc_last = jax.device_put(ssc, last)
        for mb in range(use):
            tok = jnp.asarray(rows[mb])
            x = emb(shared["embed.tokens"], jax.device_put(tok[:, :-1], first),
                    None if ssc is None else ssc["embed.tokens"])
            xs = []
            for g in range(L):
                x = jax.device_put(x, dev[g])
                xs.append(x)
                x = fwd(params[g], x, lsc[g])
            lv, (g_s, gx) = head(s_last, jax.device_put(x, last),
                                 jax.device_put(tok[:, 1:], last), sc_last)
            loss += float(lv)
            for g in reversed(range(L)):
                gp, gx = bwd(params[g], xs[g], jax.device_put(gx, dev[g]),
                             lsc[g])
                gl[g] = gp if gl[g] is None else add(gl[g], gp)
            del xs
            g_s = jax.device_put(g_s, first)
            g_e = emb_bwd(shared["embed.tokens"],
                          jax.device_put(tok[:, :-1], first),
                          jax.device_put(gx, first),
                          None if ssc is None else ssc["embed.tokens"])
            g_s = dict(g_s, **{"embed.tokens": g_s["embed.tokens"] + g_e})
            gs = g_s if gs is None else add(gs, g_s)
        losses.append(loss / use)
        gl = [scale(t, 1.0 / use) for t in gl]
        gs = scale(gs, 1.0 / use)
        tot = sum(float(v) for t in gl + [gs] for v in sq(t).values())
        clip = min(1.0, o["grad_clip"] / max(math.sqrt(tot + 1e-30), 1e-9)) \
            if o["grad_clip"] > 0 else 1.0
        if step == 1:
            first_grad = _norms(gl, gs, clip)
            sketch = _sketches(key0, gl, gs, clip, lleaves, sleaves)
        lr = lr_at(o, step)
        for g in range(L):
            params[g], mu[g], nu[g] = adam_tree(params[g], gl[g], mu[g], nu[g],
                                                lr, clip, step)
        shared, smu, snu = adam_tree(shared, gs, smu, snu, lr, clip, step)
        del gl, gs
    change_l = [jax.tree.map(jnp.subtract, p, p0)
                for p, p0 in zip(params, init_l)]
    change_s = jax.tree.map(jnp.subtract, shared, init_s)
    return {"losses": losses, "grad": first_grad, "grad_sketch": sketch,
            "change": _norms(change_l, change_s, 1.0)}


def _norms(layers, shared, c) -> dict:
    out = {}
    for g, t in enumerate(layers):
        for k, v in t.items():
            out[(g, k)] = float(jnp.sqrt(jnp.sum(v * v))) * c
    for k, v in shared.items():
        out[(-1, k)] = float(jnp.sqrt(jnp.sum(v * v))) * c
    return out


def _sketches(key0, layers, shared, c, lleaves, sleaves) -> dict:
    """``weights.sketch`` of each leaf of the clipped first gradient."""
    sk = jax.jit(W.sketch, static_argnums=(1, 2))
    out = {}
    for g, t in enumerate(layers):
        for i, (k, *_) in enumerate(lleaves):
            out[(g, k)] = np.asarray(sk(key0, g, i, t[k])) * c
    for i, (k, *_) in enumerate(sleaves):
        out[(-1, k)] = np.asarray(sk(key0, W.SHARED, i, shared[k])) * c
    return out
