"""Operations and bytes of a training cell, from its shapes, and the
chip's peaks.

The counts are the architecture's (``bench/arch/<arch>.py``):
``model_flops_per_token`` counts what the model needs, recomputation
left out, for ``mfu``; ``executed_matmul`` counts what the program's
step runs in matmuls, replays included, for ``matmul_roofline``.
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


def model_flops_per_token(m, seq: int) -> float:
    return M.arch(m).model_flops_per_token(m, seq)


def executed_matmul(m, traffic: dict) -> dict:
    """FLOPs and bytes of the matmuls one step runs, by group:
    ``{group: (flops, bytes)}``."""
    return M.arch(m).executed_matmul(m, traffic)
