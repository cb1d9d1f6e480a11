"""The program's named scopes in its compiled training step, and the
training driver's host spans on the profiler's clock.

``bench/scopes.py`` maps each device op of a profiler trace to one scope
through the optimized HLO of the traced step.  These tests hold that map
to the step the phase executor compiles on the CPU at a tiny size, for a
Chronos-Recomp plan on one device and a Chronos-Pipe plan on four: every
matmul belongs to one phase, every backward tick's replayed forward is
told apart from its pullback, and the AdamW ops carry ``optimizer``.
"""
import glob
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
HELPER = os.path.join(ROOT, "tests", "helpers", "scope_hlo.py")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402

#: plan -> (schedule, pipeline devices)
PLANS = {"chronos_recomp-p1": ("chronos_recomp", 1),
         "chronos-p4": ("chronos", 4)}
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = (.*)$", re.M)


@pytest.fixture(scope="module")
def hlo(tmp_path_factory):
    """plan -> (compiled HLO text, ``scopes.Module``), compiled once."""
    done = {}

    def get(plan):
        if plan not in done:
            schedule, n = PLANS[plan]
            out = tmp_path_factory.mktemp("hlo") / f"{plan}.txt"
            env = dict(os.environ, PYTHONPATH=SRC)
            env.pop("XLA_FLAGS", None)
            r = subprocess.run([sys.executable, HELPER, schedule, str(n),
                                str(out)], env=env, capture_output=True,
                               text=True, timeout=600)
            assert r.returncode == 0, \
                f"compile failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
            text = out.read_text()
            done[plan] = (text, scopes.Module(text))
        return done[plan]

    return get


def _named(text, opcodes):
    """Names of the instructions whose opcode is in ``opcodes``."""
    out = []
    for name, rhs in INSTR.findall(text):
        op = scopes.OPCODE.search(rhs.split(", metadata=", 1)[0])
        if op and op.group(1) in opcodes:
            out.append(name)
    return out


@pytest.mark.parametrize("plan", PLANS)
def test_every_matmul_has_one_phase(hlo, plan):
    text, mod = hlo(plan)
    mms = _named(text, ("dot", "convolution"))
    assert mms
    bad = {n: mod.resolve(n) for n in mms
           if scopes.phase(mod.scope(n) or scopes.UNSCOPED)
           not in ("fwd", "replay", "bwd", "optimizer")}
    assert not bad, bad


@pytest.mark.parametrize("plan", PLANS)
def test_replay_reruns_the_chunk_forward(hlo, plan):
    """Every backward tick runs its chunk's forward again from the
    stored boundary inside ``jax.vjp``, in both plans: the replay holds
    as many chunk-body matmuls as the F tick's forward, and besides
    them the batched attention matmuls that ``jax.checkpoint``'s policy
    does not save, run once more inside the pullback."""
    text, mod = hlo(plan)
    by = {"fwd": 0, "replay": 0, "rematted": 0}
    for n in _named(text, ("dot", "convolution")):
        path, label = mod.resolve(n)
        if "jit(chunk_core)" not in path:
            continue                        # the head's matmuls
        if label == "replay" and "rematted_computation" in path:
            by["rematted"] += 1
        elif label in by:
            by[label] += 1
    assert by["fwd"] > 0
    assert by["replay"] == by["fwd"], by
    assert by["rematted"] > 0, by


@pytest.mark.parametrize("plan", PLANS)
def test_adamw_carries_optimizer(hlo, plan):
    """AdamW's square roots (the global norm and the second-moment
    denominator) all lie in ``optimizer``, and the scope holds the
    update's arithmetic."""
    text, mod = hlo(plan)
    roots = _named(text, ("sqrt",))
    assert roots
    assert {mod.scope(n) for n in roots} == {"optimizer"}
    ops = [n for n in mod.instr if mod.scope(n) == "optimizer"]
    assert len(ops) > len(roots)


@pytest.mark.parametrize("plan", PLANS)
def test_pullback_is_not_replay(hlo, plan):
    """JAX names a pullback's ops after the forward they transpose
    (``transpose(jvp(...))``): those are ``bwd``, except the forward that
    ``jax.checkpoint`` re-runs inside the pullback
    (``rematted_computation``), which is ``replay``."""
    _, mod = hlo(plan)
    seen = {"bwd": 0, "replay": 0}
    for n in mod.instr:
        path, label = mod.resolve(n)
        if path is None or label is None or "transpose(" not in path:
            continue
        want = "replay" if "rematted_computation" in path else "bwd"
        assert scopes.phase(label) == want, (n, path, label)
        seen[want] += 1
    assert seen["bwd"] > 0 and seen["replay"] > 0, seen


def test_train_pipeline_host_spans(tmp_path):
    """A profiled two-step ``train_pipeline`` run with Chronos-Offload
    writes the driver's host spans on the profiler's clock: the step,
    the input fetch, the offload submit and collect, the checkpoint, and
    the host AdamW on the offload worker's thread."""
    import jax
    from jax.profiler import ProfileData

    from repro.configs import get_reduced
    from repro.configs.base import (OffloadConfig, OptimizerConfig,
                                    ParallelPlan, RecomputeConfig,
                                    ShapeConfig, TrainConfig)
    from repro.launch.train import train_pipeline
    from repro.models.sharding import make_mesh

    plan = ParallelPlan(pp_axis="pp", schedule="chronos", num_chunks=2,
                        microbatch_size=2, recompute=RecomputeConfig(),
                        offload=OffloadConfig(enabled=True,
                                              num_offload_chunks=1))
    tc = TrainConfig(model=get_reduced("tinyllama-1.1b"),
                     shape=ShapeConfig("smoke", 18, 4, "train"), plan=plan,
                     optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                               total_steps=2),
                     checkpoint_dir=str(tmp_path / "ckpt"),
                     checkpoint_every=10 ** 9)
    mesh = make_mesh((1,), ("pp",), devices=jax.devices()[:1])
    with jax.profiler.trace(str(tmp_path / "trace")):
        out = train_pipeline(tc, mesh=mesh, steps=2, log=lambda _: None)
    assert out["steps"] == 2
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events}
    want = {"train", "input", "offload_submit", "offload_collect",
            "checkpoint", "host_update"}
    assert want <= names, want - names
