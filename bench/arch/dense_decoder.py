"""The dense decoder: RMSNorm, MHA or GQA attention with RoPE, a SwiGLU
MLP, and an embedding and head (tied or not).  Every layer is alike.

Model FLOPs (for ``mfu``) count what the model needs, not what the
program runs: per token, ``6 x`` the parameters that enter a matmul
(every layer's projections and the head; the embedding lookup is a
gather), plus the sequence mixer:

- attention: ``12 * layers * seq * d`` (PaLM, arXiv:2204.02311, App. B:
  ``2 * 2 * seq * d`` per layer for q.k and p.v in the forward, times 3
  for forward and backward; the causal half is not taken off).

Recomputed work is not model work and is left out here.
``executed_matmul`` counts what the program's step does run in matmuls,
replays included, for ``matmul_roofline``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench.arch.common import _causal_attention, _mm, _q8, _qp, _rmsnorm, \
    _rope


@dataclass(frozen=True)
class Model:
    arch: str
    name: str
    d: int
    layers: int
    vocab: int              # ids drawn by the traffic
    vocab_rows: int         # rows of the embedding (vocab padded)
    tie: bool
    eps: float
    param_dtype: str
    heads: int
    kv_heads: int
    hd: int
    ff: int
    rope_theta: float


def describe(c: dict) -> Model:
    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: only SwiGLU MLPs are described here")
    return Model(
        arch=c["arch"], name=c["name"], d=c["hidden_size"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        vocab_rows=c["vocab_size"], tie=c["tie_word_embeddings"],
        eps=c["rms_norm_eps"], param_dtype=c["dtype"]["params"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        hd=c["hidden_size"] // c["num_attention_heads"],
        ff=c["intermediate_size"], rope_theta=c["rope_theta"])


def layer_leaves(m: Model, g: int) -> List[tuple]:
    """The leaves of every layer, in a fixed order (the order keys the
    seed).  Names are those of the program's parameter tree."""
    p, d = m.param_dtype, m.d
    qd, kvd = m.heads * m.hd, m.kv_heads * m.hd
    return [
        ("norm1.scale", (d,), ("ones",), p),
        ("attn.wq", (d, qd), ("normal", d ** -0.5), p),
        ("attn.wk", (d, kvd), ("normal", d ** -0.5), p),
        ("attn.wv", (d, kvd), ("normal", d ** -0.5), p),
        ("attn.wo", (qd, d), ("normal", qd ** -0.5), p),
        ("norm2.scale", (d,), ("ones",), p),
        ("mlp.wi", (d, m.ff), ("normal", d ** -0.5), p),
        ("mlp.wg", (d, m.ff), ("normal", d ** -0.5), p),
        ("mlp.wo", (m.ff, d), ("normal", m.ff ** -0.5), p),
    ]


def shared_leaves(m: Model) -> List[tuple]:
    p, d, v = m.param_dtype, m.d, m.vocab_rows
    out = [("embed.tokens", (v, d), ("normal", d ** -0.5), p)]
    if not m.tie:
        out.append(("embed.head", (d, v), ("normal", d ** -0.5), p))
    out.append(("final_norm.scale", (d,), ("ones",), p))
    return out


def decays(path: str, shape) -> bool:
    """AdamW's decay rule as the traffic states it: matmul, embedding and
    head weights (rank >= 2 in one layer), not norm scales."""
    return len(shape) >= 2


def matmul_params(m: Model) -> Dict[str, int]:
    """Parameters that enter a matmul per token: per layer, and the head
    (the embedding lookup is a gather, not a matmul)."""
    per_layer = sum(math.prod(s) for _, s, _, _ in layer_leaves(m, 0)
                    if len(s) == 2)
    return {"layer": per_layer, "head": m.d * m.vocab_rows}


# -- the reference ------------------------------------------------------

def layer_kind(m: Model, g: int) -> str:
    return "decoder"


def layer(m: Model, g: int, p, x, scales):
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


def embed(m: Model, table, tok, scale=None):
    if scale is not None:
        table = _q8(table, scale)
    return table[tok] * math.sqrt(m.d)


def head_loss(m: Model, s, x, labels, scales):
    s, quant = _qp(s, scales), scales is not None
    h = _rmsnorm(x, s["final_norm.scale"], m.eps)
    w = s["embed.head"] if "embed.head" in s else s["embed.tokens"].T
    logits = _mm(h, w, quant)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


# -- the program --------------------------------------------------------

def program_config(m: Model):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=m.name, family="dense", num_layers=m.layers,
                       d_model=m.d, num_heads=m.heads,
                       num_kv_heads=m.kv_heads, d_ff=m.ff, head_dim=m.hd,
                       rope_theta=m.rope_theta, vocab_size=m.vocab_rows,
                       tie_embeddings=m.tie, norm_eps=m.eps, act="silu",
                       param_dtype=m.param_dtype,
                       compute_dtype=m.param_dtype)


# -- counts -------------------------------------------------------------

def mixer_fwd_flops_per_token(m: Model, seq: int) -> float:
    """Forward FLOPs per token of one layer's attention (outside the
    projections)."""
    return 4.0 * seq * m.heads * m.hd


def model_flops_per_token(m: Model, seq: int) -> float:
    mp = matmul_params(m)
    dense = 6.0 * (mp["layer"] * m.layers + mp["head"])
    return dense + 3.0 * m.layers * mixer_fwd_flops_per_token(m, seq)


def executed_matmul(m: Model, traffic: dict) -> dict:
    """FLOPs and bytes of the matmuls one step runs, by group.

    Per layer and token the projections run ``2 Pm`` in the forward and
    ``4 Pm`` in the backward, and ``2 Pm`` again in each layer of the
    replayed (recomputed) chunks.  The mixer's batched matmuls run once
    in the forward, again in the backward (the program saves only
    matmuls without batch dimensions), twice more in the backward
    proper, and once more in a replayed layer.  The head runs ``2 d V``
    forward and ``4 d V`` backward per token.  Bytes are the bf16
    operands and results of each pass."""
    t = traffic
    pl = t["plan"]
    seq = t["seq_len"]
    tokens = t["microbatches"] * t["microbatch_size"] * seq
    rc = pl["recompute"]
    replay_share = (rc["num_recomp_chunks"] / pl["num_chunks"]
                    if rc["mode"] != "none" else 0.0)
    mp = matmul_params(m)
    # passes of 2 FLOPs per weight and token: forward 1, backward 2,
    # replay 1 in the replayed share of the layers
    passes = 3.0 + replay_share
    proj_f = 2.0 * mp["layer"] * m.layers * tokens * passes
    proj_b = 2.0 * passes * m.layers * (mp["layer"] + tokens * _proj_io(m))
    mix_passes = 4.0 + replay_share
    mix_f = mixer_fwd_flops_per_token(m, seq) * m.layers * tokens * mix_passes
    mix_b = _mixer_bytes(m, traffic) * m.layers * mix_passes
    head_f = 6.0 * m.d * m.vocab_rows * tokens
    head_b = 2.0 * 3 * (m.d * m.vocab_rows + tokens * (m.d + m.vocab_rows))
    return {"projections": (proj_f, proj_b), "mixer": (mix_f, mix_b),
            "head": (head_f, head_b)}


def _proj_io(m: Model) -> float:
    """Activation elements read and written per token by one layer's
    projections in one pass."""
    qd = m.heads * m.hd
    kvd = m.kv_heads * m.hd
    return (m.d + qd + 2 * kvd) + (qd + m.d) + (m.d + 2 * m.ff) + \
        (m.ff + m.d)


def _mixer_bytes(m: Model, traffic: dict) -> float:
    """Bytes of one forward pass of one layer's mixer matmuls."""
    t = traffic
    b = t["microbatches"] * t["microbatch_size"]
    s = t["seq_len"]
    # q, k, v, out in bf16; the score and probability blocks in f32
    return b * (2.0 * 4 * s * m.heads * m.hd + 4.0 * 2 * m.heads * s * s)
