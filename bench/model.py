"""The benchmark's own description of a configuration: sizes, the leaves
of each layer and how the seed makes them.

Read from ``bench/configs/<name>.json`` (the keys of the model's public
config).  The reference, the weights and the FLOP counts use this and
nothing of the program; ``program.py`` alone maps it onto the program.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Model:
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


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def from_config(c: dict) -> Model:
    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: only SwiGLU MLPs are described here")
    return Model(
        name=c["name"], d=c["hidden_size"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        vocab_rows=c["vocab_size"], tie=c["tie_word_embeddings"],
        eps=c["rms_norm_eps"], param_dtype=c["dtype"]["params"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        hd=c["hidden_size"] // c["num_attention_heads"],
        ff=c["intermediate_size"], rope_theta=c["rope_theta"])


# A leaf: (dotted path, shape, init, dtype).  init is ("normal", std) or
# ("ones",).
Leaf = Tuple[str, Tuple[int, ...], tuple, str]


def layer_leaves(m: Model) -> List[Leaf]:
    """The leaves of one layer, in a fixed order (the order keys the
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


def shared_leaves(m: Model) -> List[Leaf]:
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
    per_layer = sum(math.prod(s) for _, s, _, _ in layer_leaves(m)
                    if len(s) == 2)
    return {"layer": per_layer, "head": m.d * m.vocab_rows}


def layer_params(m: Model) -> int:
    return sum(math.prod(s) for _, s, _, _ in layer_leaves(m))
