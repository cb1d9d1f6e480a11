"""The reduction from trace to metrics, on a trace recorded on one v5e
chip and on four devices' events written out by hand."""
import os
import shutil

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_recorded_one_device_trace(tmp_path):
    """Three calls of a jitted 512 x 512 bf16 matmul, traced on a
    "TPU v5 lite" between the harness's own host spans."""
    shutil.copy(os.path.join(DATA, "tiny_1dev.xplane.pb"),
                tmp_path / "run.xplane.pb")
    with open(os.path.join(DATA, "tiny_1dev.hlo.txt")) as f:
        tr = T.load(str(tmp_path), f.read())
    assert list(tr["devices"]) == [0]
    names = [n for n, *_ in tr["devices"][0]]
    assert names.count("convolution_reduce_fusion:kOutput") == 3
    assert {c for *_, c in tr["devices"][0]} == {"matmul", "other"}
    assert [n for n, *_ in tr["host"]].count("dispatch") == 3
    red = T.reduce(tr)
    dev = red["devices"][0]
    assert 0 < dev["busy_s"] < red["window_s"]
    assert dev["class_s"]["matmul"] <= dev["busy_s"]
    assert dev["exposed_collective_s"] == 0
    # the longest gaps lie where the host waited or slept between calls
    assert red["idle_gaps"][0][0] == "wait@tpu0"


def test_leaves_drop_containers():
    evs = [("while", 0, 100), ("a", 10, 20), ("cond", 30, 90),
           ("b", 40, 50), ("c", 60, 70), ("d", 110, 120)]
    assert [e[0] for e in T.leaves(evs)] == ["a", "b", "c", "d"]


MODULE = """HloModule m

%fused_computation.7 (p0: bf16[2048,4096], p1: bf16[4096,4096]) -> bf16[] {
  %p0 = bf16[2048,4096]{1,0} parameter(0)
  %p1 = bf16[4096,4096]{1,0} parameter(1)
  ROOT %convolution.2 = bf16[2048,4096]{1,0} convolution(%p0, %p1)
}

%fused_computation.8 (p0: bf16[8,4096], p1: s32[]) -> bf16[8,4096] {
  %p0 = bf16[8,4096]{1,0} parameter(0)
  ROOT %dynamic-update-slice.1 = bf16[8,4096]{1,0} dynamic-update-slice(%p0)
}

%inner (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  ROOT %dot.1 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %p0, %p0)
}

%fused_computation.9 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  ROOT %call.1 = f32[64,64]{1,0} call(%p0), to_apply=%inner
}

ENTRY %main (a: bf16[2048,4096]) -> bf16[] {
  %a = bf16[2048,4096]{1,0} parameter(0)
  ROOT %fusion.78 = bf16[2048,4096]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.7
}
"""


def test_op_names_and_classes():
    mm = T.matmul_computations(MODULE)
    assert mm == {"fused_computation.7", "inner", "fused_computation.9",
                  "main"}
    hlo = ("%fusion.78 = bf16[2048,32,128]{0,2,1} fusion(bf16[1,2048,4096] "
           "%copy-done.34), kind=kOutput, calls=%fused_computation.7")
    assert T.op_name(hlo) == "fusion:kOutput"
    assert T.classify(hlo, mm) == "matmul"
    # an output fusion without a matmul inside is not one
    dus = ("%bitcast_dynamic-update-slice_fusion.2 = bf16[8,4096]{1,0} "
           "fusion(%x, %i), kind=kOutput, calls=%fused_computation.8")
    assert T.op_name(dus) == "bitcast_dynamic-update-slice_fusion:kOutput"
    assert T.classify(dus, mm) == "other"
    assert T.classify(hlo.replace(".7", ".9"), mm) == "matmul"
    # without the module no fusion counts as a matmul
    assert T.classify(hlo) == "other"
    assert T.classify("%dot.3 = f32[8,8]{1,0} dot(f32[8,8] %a, %b)") == \
        "matmul"
    cp = "%collective-permute-start.3 = (f32[8]) collective-permute-start(%x)"
    assert T.op_name(cp) == "collective-permute-start"
    assert T.classify(cp, mm) == "collective"
    assert T.classify("%all-reduce.1 = f32[8] all-reduce(%x), "
                      "to_apply=%inner", mm) == "collective"


def _four_devices():
    """Window 0-1000 ns.  Device d computes for 100 ns from 100 * d, then
    runs a collective 500-700; device 3's collective overlaps its own
    compute 600-650, and device 0 also computes 800-900."""
    devs = {}
    for d in range(4):
        evs = [("fusion:kOutput", 100.0 * d, 100.0 * d + 100, "matmul"),
               ("collective-permute", 500.0, 700.0, "collective")]
        if d == 3:
            evs.append(("fusion:kLoop", 600.0, 650.0, "other"))
        if d == 0:
            evs.append(("fusion:kLoop", 800.0, 900.0, "other"))
        devs[d] = evs
    host = [("window", 0.0, 1000.0), ("dispatch", 0.0, 450.0),
            ("wait", 450.0, 1000.0)]
    return {"devices": devs, "host": host}


def test_four_devices_by_hand():
    red = T.reduce(_four_devices())
    assert red["window_s"] == pytest.approx(1e-6)
    busy = {d: v["busy_s"] * 1e9 for d, v in red["devices"].items()}
    assert busy == pytest.approx({0: 400, 1: 300, 2: 300, 3: 300})
    exposed = {d: v["exposed_collective_s"] * 1e9
               for d, v in red["devices"].items()}
    assert exposed == pytest.approx({0: 200, 1: 200, 2: 200, 3: 150})
    assert red["devices"][3]["class_s"]["collective"] * 1e9 == \
        pytest.approx(200)
    # op time is the mean over devices: 200 ns of collective-permute each
    ops = dict((n, t * 1e9) for n, t in red["device_ops"])
    assert ops["collective-permute"] == pytest.approx(200)
    assert ops["fusion:kOutput"] == pytest.approx(100)
    # device 1 idles 200-500, mostly under dispatch, and 700-1000 under
    # wait; device 0's longest gap, 100-500, is the longest of all
    gaps = [(n, round(t * 1e9)) for n, t in red["idle_gaps"]]
    assert gaps[0] == ("dispatch@tpu0", 400)
    assert ("dispatch@tpu1", 300) in gaps and ("wait@tpu1", 300) in gaps


def test_no_window_reads_nothing():
    tr = _four_devices()
    tr["host"] = [h for h in tr["host"] if h[0] != "window"]
    assert T.reduce(tr) == {}
