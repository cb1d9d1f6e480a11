"""Operations and bytes of a training cell, from its shapes.

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

import json
import os

from bench import model as M

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind that is not in ``peaks.json`` is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def mixer_fwd_flops_per_token(m: M.Model, seq: int) -> float:
    """Forward FLOPs per token of one layer's attention (outside the
    projections)."""
    return 4.0 * seq * m.heads * m.hd


def model_flops_per_token(m: M.Model, seq: int) -> float:
    mp = M.matmul_params(m)
    dense = 6.0 * (mp["layer"] * m.layers + mp["head"])
    return dense + 3.0 * m.layers * mixer_fwd_flops_per_token(m, seq)


def executed_matmul(m: M.Model, traffic: dict) -> dict:
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
    mp = M.matmul_params(m)
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


def _proj_io(m: M.Model) -> float:
    """Activation elements read and written per token by one layer's
    projections in one pass."""
    qd = m.heads * m.hd
    kvd = m.kv_heads * m.hd
    return (m.d + qd + 2 * kvd) + (qd + m.d) + (m.d + 2 * m.ff) + \
        (m.ff + m.d)


def _mixer_bytes(m: M.Model, traffic: dict) -> float:
    """Bytes of one forward pass of one layer's mixer matmuls."""
    t = traffic
    b = t["microbatches"] * t["microbatch_size"]
    s = t["seq_len"]
    # q, k, v, out in bf16; the score and probability blocks in f32
    return b * (2.0 * 4 * s * m.heads * m.hd + 4.0 * 2 * m.heads * s * s)
