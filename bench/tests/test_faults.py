"""The harness drives a whole run on the CPU at a small size, past its
look for a chip, and ``correct`` comes out true for the program as it
is and false with the timed path broken underneath: a step that returns
its state unchanged, and a step that leaves half of the batch out and
takes the mean over the rest.  (A one-chip cell has no exchange between
chips, and a training step produces no token to alter.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness
from bench.program import Program

ATTN = {"name": "tiny-attn", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "hidden_act": "silu",
        "tie_word_embeddings": False, "dtype": {"params": "bfloat16"}}
CELLS = {"attn": (ATTN, "deepseek-7b-stage.recomp-1chip")}
E2E = [{"name": "train_tokens_per_s", "unit": "tokens/s"},
       {"name": "hbm_peak_gib", "unit": "GiB"},
       {"name": "setup_s", "unit": "s"}]


def _traffic():
    t = harness.load_traffic("recomp-1chip")
    return dict(t, seq_len=64)


class Unchanged(Program):
    """The step runs, and hands back the state it was given."""

    def __post_init__(self):
        super().__post_init__()
        real = self.step

        def step(p, o, b):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, met = real(copy(p), copy(o), b)
            return p, o, met
        self.step = step


class HalfBatch(Program):
    """The step sees only the first half of the microbatches."""

    def __post_init__(self):
        super().__post_init__()
        t = self.traffic
        k = t["microbatches"] // 2
        half = Program(self.model, dict(t, microbatches=k), self.devices)
        self.step = lambda p, o, b: half.step(
            p, o, {"tokens": b["tokens"][:k]})


def _run(kind, build):
    cfg, cell = CELLS[kind]
    return harness.run_cell(
        {"name": cell}, cfg, _traffic(), seed=2 ** 31 + 17, seconds=0.2,
        trace=False, devices=jax.devices()[:1],
        limits=check.load_limits(cell), e2e=E2E, per_layer=[],
        t_start=time.monotonic(), build=build)


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    res = _run(kind, None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {e["name"] for e in E2E}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("fault", [Unchanged, HalfBatch],
                         ids=["unchanged", "half_batch"])
def test_fault_is_not_correct(kind, fault):
    res = _run(kind, fault)
    assert not res["correct"], res["checks"]


def _four(fault):
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [root, os.path.join(root, "src")]))
    r = subprocess.run([sys.executable, "-m", "bench.tests.tiny4", fault],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chips_sound_and_exchange_left_out():
    """The four-chip cell's path on four virtual CPU devices: correct as
    it is, not correct with the boundary exchange left out."""
    assert _four("")["correct"]
    assert not _four("noexchange")["correct"]
