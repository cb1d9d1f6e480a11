"""Device time by program scope (``bench/scopes.py``): the op -> scope
map on hand-written HLO, the split of busy time on hand-built
four-device events, and a trace recorded on one v5e chip from a program
without scopes."""
import os
import shutil

import pytest

from bench import scopes as S
from bench.harness import Ctx
from bench.metrics import (optimizer_share, replay_share,
                           tick_bookkeeping_share)

DATA = os.path.join(os.path.dirname(__file__), "data")
PRE = "jit(step)/while/body/closed_call/cond/branch_1_fun"


@pytest.mark.parametrize("path,label", [
    (f"{PRE}/replay/jvp(jit(chunk_core))/while/body/closed_call/"
     "dot_general", "replay"),
    (f"{PRE}/bwd/transpose(jvp(jit(chunk_core)))/while/body/closed_call/"
     "checkpoint/dot_general", "bwd"),
    (f"{PRE}/bwd/transpose(jvp(jit(chunk_core)))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "replay"),
    (f"{PRE}/replay/transpose(jvp(jit(head_core)))/head_loss/dot_general",
     "bwd/head_loss"),
    (f"{PRE}/bwd/cond/branch_1_fun/replay/jvp(jit(head_core))/head_loss/"
     "dot_general", "replay/head_loss"),
    ("jit(step)/while/body/closed_call/cond/branch_0_fun/fwd/jit(fwd_core)/"
     "cond/branch_1_fun/jit(embed_core)/embed/gather", "fwd/embed"),
    ("jit(step)/shard_map/while/body/closed_call/wire/cond/branch_1_fun/"
     "exchange/all_gather", "exchange"),
    ("jit(step)/optimizer/optimizer/sqrt", "optimizer"),
    ("jit(step)/while/body/add", None),
    ("x", None),
])
def test_path_scope(path, label):
    assert S.path_scope(path) == label


MODULE = """HloModule m

%fused_computation.1 (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%param_0, %param_0), metadata={op_name="jit(step)/fwd/jit(chunk_core)/dot_general"}
}

%fused_computation.2 (param_1: bf16[8,8]) -> bf16[8,8] {
  %param_1 = bf16[8,8]{1,0} parameter(0)
  %negate.3 = bf16[8,8]{1,0} negate(%param_1)
  ROOT %add.3 = bf16[8,8]{1,0} add(%negate.3, %negate.3), metadata={op_name="jit(step)/grad_accum/cond/add"}
}

%fused_computation.3 (param_2: bf16[8,8]) -> bf16[8,8] {
  %param_2 = bf16[8,8]{1,0} parameter(0)
  ROOT %bitcast.4 = bf16[8,8]{1,0} bitcast(%param_2)
}

ENTRY %main (p.1: bf16[8,8]) -> bf16[8,8] {
  %p.1 = bf16[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %copy.1 = bf16[8,8]{1,0} copy(%p.1)
  %fusion.1 = bf16[8,8]{1,0} fusion(%copy.1), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = bf16[8,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %dynamic-slice.5 = bf16[8,8]{1,0} dynamic-slice(%fusion.3), metadata={op_name="jit(step)/while/body/ring/dynamic_slice"}
  ROOT %copy.2 = bf16[8,8]{1,0} copy(bf16[8,8]{1,0} %dynamic-slice.5)
}
"""


def test_module_scopes():
    mod = S.Module(MODULE)
    # a fusion without metadata: its fused root's
    assert mod.scope("fusion.1") == "fwd"
    # ... else the first fused instruction that has one
    assert mod.scope("fusion.2") == "grad_accum"
    # no metadata in the fusion at all: its first scoped operand's
    assert mod.scope("fusion.3") == "grad_accum"
    assert mod.resolve("fusion.3")[0] == "jit(step)/grad_accum/cond/add"
    assert mod.scope("dynamic-slice.5") == "ring"
    assert mod.scope("copy.2") == "ring"
    # a copy of a parameter reaches no scope
    assert mod.scope("copy.1") is None
    assert mod.scope("not-there") is None


def test_instr_name():
    assert S.instr_name("%fusion.78 = bf16[8] fusion(%a), kind=kLoop") == \
        "fusion.78"
    assert S.instr_name("ROOT %copy.2 = bf16[8] copy(%a)") == "copy.2"


def _trace(ops_by_dev, host=()):
    return {"devices": {d: [(n, s * 1e6, e * 1e6) for n, s, e in ops]
                        for d, ops in ops_by_dev.items()},
            "host": [(t, n, s * 1e6, e * 1e6) for t, n, s, e in host]}


def test_partition_counts_each_instant_once():
    # ns; where ops overlap, the instant goes to the one started last
    assert S.partition([(0e6, 10e6, "a"), (2e6, 4e6, "b"),
                        (3e6, 12e6, "c")]) == pytest.approx(
        {"a": 0.002, "b": 0.001, "c": 0.009})


def test_four_devices_scoped_plus_unscoped_is_busy():
    """Four devices with the same program, ops overlapping on two of
    them, idle gaps over 20 ms under two host events."""
    base = [("fusion.1", 0, 5), ("fusion.2", 5, 7), ("copy.1", 7, 8),
            ("dynamic-slice.5", 8, 9)]
    devs = {0: base, 1: base + [("copy.2", 8.5, 12)],
            2: [("fusion.1", 0, 6), ("fusion.2", 1, 3)],
            3: [("fusion.1", 0, 5), ("fusion.2", 40, 50)]}
    host = [("main", "dispatch", 1, 2), ("pool", "Execute", 4, 45),
            ("main", "wait", 6, 44)]
    red = S.reduce(_trace(devs, host), MODULE, (0.0, 60e6))
    for d, r in red.items():
        assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"])
    assert red[0]["scope_s"] == pytest.approx(
        {"fwd": 0.005, "grad_accum": 0.002, "unscoped": 0.001,
         "ring": 0.001})
    assert red[0]["unscoped_top"] == [("copy.1", pytest.approx(0.001))]
    assert red[2]["scope_s"] == pytest.approx({"fwd": 0.004,
                                               "grad_accum": 0.002})
    assert red[1]["scope_s"]["ring"] == pytest.approx(0.004)
    # device 3: 5-40 ms idle (35 ms); the gap 50-60 ms is under 20 ms
    assert [g["gap_s"] for g in red[3]["gaps"]] == [pytest.approx(0.035)]
    assert red[3]["gaps"][0]["host"] == [
        ("pool", "Execute", pytest.approx(0.035)),
        ("main", "wait", pytest.approx(0.034))]
    assert red[0]["gaps"] == [{"start_s": pytest.approx(0.009),
                               "gap_s": pytest.approx(0.051),
                               "host": [("pool", "Execute",
                                         pytest.approx(0.036)),
                                        ("main", "wait",
                                         pytest.approx(0.035))]}]
    scope_s = {d: r["scope_s"] for d, r in red.items()}
    assert S.share(scope_s, 0.060, "tick_bookkeeping_share") == \
        pytest.approx(100 * (0.003 + 0.006 + 0.002 + 0.010) / 4 / 0.060)
    assert S.share(scope_s, 0.060, "replay_share") == 0.0


def test_recorded_trace_without_scopes_is_unscoped(tmp_path):
    """The recorded tiny trace comes from a program without scopes: all
    its time is ``unscoped``, and every share reads nothing."""
    shutil.copy(os.path.join(DATA, "tiny_1dev.xplane.pb"),
                tmp_path / "run.xplane.pb")
    with open(os.path.join(DATA, "tiny_1dev.hlo.txt")) as f:
        hlo = f.read()
    tr = S.load(str(tmp_path))
    assert [n for n, *_ in tr["devices"][0]].count(
        "convolution_reduce_fusion") == 3
    win = next((s, e) for _, n, s, e in tr["host"] if n == "window")
    red = S.reduce(tr, hlo, win)
    assert set(red[0]["scope_s"]) == {S.UNSCOPED}
    assert red[0]["scope_s"][S.UNSCOPED] == pytest.approx(red[0]["busy_s"])
    assert red[0]["unscoped_top"][0][0] == "convolution_reduce_fusion"
    ctx = Ctx(trace={"window_s": (win[1] - win[0]) * 1e-9},
              scopes={d: r["scope_s"] for d, r in red.items()})
    for mod in (replay_share, optimizer_share, tick_bookkeeping_share):
        assert mod.read(ctx) is None


def test_readers():
    ctx = Ctx(trace={"window_s": 2.0},
              scopes={0: {"replay": 0.5, "replay/head_loss": 0.1,
                          "optimizer": 0.1, "ring": 0.2, "wire": 0.1,
                          "grad_accum": 0.1, "bwd": 0.6},
                      1: {"replay": 0.3, "optimizer": 0.1,
                          "unscoped": 0.4}})
    assert replay_share.read(ctx) == pytest.approx(100 * 0.45 / 2.0)
    assert optimizer_share.read(ctx) == pytest.approx(100 * 0.1 / 2.0)
    assert tick_bookkeeping_share.read(ctx) == pytest.approx(
        100 * 0.2 / 2.0)
    # the harness as it stands hands no scopes: the readers read nothing
    assert replay_share.read(Ctx(trace={"window_s": 2.0})) is None
