"""SPMD pipeline-executor tests.

Each case runs in a subprocess (JAX pins the device count at first init,
so virtual-device tests can't share the pytest process).  The helper
checks numerical equivalence of pipeline gradients against single-device
autodiff — the strongest invariant: every schedule must produce the SAME
gradients, only with different memory/time profiles.

Fast tier-1 runs one fused schedule (chronos), one split-backward
schedule (chronos_zb, which exercises the B/W stash path including the
mid/first/last op variants), and the direct split-vs-fused gradient
comparison.  Everything else — more schedules, deeper pipelines, the
exotic architectures, dp/tp meshes — is ``@pytest.mark.slow``
(~30-90 s of CPU jit each; run with --runslow or RUN_SLOW=1).
"""
import os
import subprocess
import sys

import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "pipeline_check.py")
SPLIT_HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                            "split_fused_check.py")
OFFLOAD_HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                              "offload_train_check.py")
SEQ_HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                          "seq_train_check.py")
CALIB_HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                            "overlap_calibration_check.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    return subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=timeout)


def run_case(arch, schedule, P, v, m, ndev=None, dp=1, tp=1, n_seq=1,
             timeout=600):
    args = [sys.executable, HELPER, arch, schedule, str(P), str(v), str(m)]
    if ndev or n_seq > 1:
        args += [str(ndev or P), str(dp), str(tp)]
    if n_seq > 1:
        args += [str(n_seq)]
    r = _run(args, timeout=timeout)
    assert r.returncode == 0, \
        f"{arch}/{schedule} failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


@pytest.mark.parametrize("schedule", [
    "chronos",
    "chronos_zb",                     # split backward, v=2 (B/W mid ops)
    pytest.param("1f1b", marks=pytest.mark.slow),
    pytest.param("zb_h1", marks=pytest.mark.slow),
    pytest.param("interleaved", marks=pytest.mark.slow),
    pytest.param("chronos_recomp", marks=pytest.mark.slow),
    pytest.param("chronos_zero2", marks=pytest.mark.slow),
])
def test_dense_schedules_grad_equivalence(schedule):
    v = 1 if schedule in ("1f1b", "zb_h1") else 2
    run_case("tinyllama-1.1b", schedule, P=2, v=v, m=4)


def test_split_backward_matches_fused_runtime():
    """zb_h1 (B = input grad + stash, W = deferred weight grad) must
    reproduce the fused 1f1b pipeline gradients to <= 1e-5."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "zb", "2", "4"])
    assert r.returncode == 0, \
        f"split-vs-fused failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


def test_recomp_matches_norecomp_runtime_bitwise():
    """chronos_recomp (explicit R ticks: boundary checkpoint handed to
    the remat ring, replay fused into B's vjp) must reproduce the
    chronos pipeline gradients *bitwise* (tolerance 0 in the helper)."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "recomp", "2", "4"])
    assert r.returncode == 0, \
        f"recomp-vs-norecomp failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=0.000e+00" in r.stdout


def test_sync_default_matches_overlap_runtime_bitwise():
    """The default synchronous table (chronos P=2 m=4, 20 ticks) must
    reproduce the double-buffered table's gradients *bitwise*: both keep
    the per-device op order."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "tick_contract",
              "2", "4"])
    assert r.returncode == 0, \
        f"tick-contract pair failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=0.000e+00" in r.stdout


@pytest.mark.parametrize("pair", ["zb", "recomp", "seq", "wire_int8"])
def test_pairs_on_double_buffered_wire(pair):
    """The same pairs with ``overlap=True`` on both sides: the deferred
    exchange, its queues and the ``wire`` carry under the W ops (zb),
    the R ops (recomp), the seq-chunked executor and the int8 wire,
    at the pairs' own tolerances."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", pair, "--overlap",
              "2", "4"])
    assert r.returncode == 0, \
        f"{pair} (overlap) failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert ("MAXERR=0.000e+00" if pair == "recomp" else "MAXERR=") \
        in r.stdout


def test_offload_pipeline_step_shapes():
    """Chronos-Offload step builder (trace only): device opt state
    excludes the deep chunks; the step returns their gradients."""
    r = _run([sys.executable, OFFLOAD_HELPER, "--dry"])
    assert r.returncode == 0, \
        f"offload dry check failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "OK=1" in r.stdout


def test_vshape_matches_interleaved_runtime():
    """v_min (V-shape placement: device d holds blocks d and 2P-1-d,
    split B/W backward, device-local chunk hops incl. the new up/down/
    local routing channels) must reproduce the interleaved chronos
    pipeline gradients on the same network (parameters remapped
    position-for-position between placements) to <= 1e-5."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "vshape", "2", "4"])
    assert r.returncode == 0, \
        f"vshape-vs-interleaved failed:\n{r.stdout[-2000:]}\n" \
        f"{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


@pytest.mark.slow
def test_vshape_deeper_pipeline_matches_interleaved():
    """P=4 exercises every V routing channel (F up, B down, locals) and
    the mid-stage op codes on the folded chunk."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "vshape", "4", "8"])
    assert r.returncode == 0, \
        f"vshape P=4 failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("schedule", ["v_min", "v_half", "v_zb"])
def test_vshape_grad_equivalence_vs_single_device(schedule):
    """The whole V family against single-device autodiff (the reference
    mapping runs through the placement-aware ``StageLayout.global_idx``)."""
    run_case("tinyllama-1.1b", schedule, P=2, v=2, m=4)


@pytest.mark.parametrize("pair,tol_note", [
    ("wire_bf16", "2e-2"),
    pytest.param("wire_int8", "1e-1", marks=pytest.mark.slow),
])
def test_compressed_wire_matches_fp32_wire(pair, tol_note):
    """Quantized boundary payloads (bf16 / int8-with-scale inside the
    packed uint16 wire) track the fp32-wire chronos gradients at the
    pinned per-dtype normalized tolerances (helper docstring has the
    measured errors; fp32 wire itself stays bitwise vs overlap=False,
    covered by test_deferred_exchange_short_circuits / the calibration
    pair)."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", pair, "2", "4"])
    assert r.returncode == 0, \
        f"{pair} failed (tol {tol_note}):\n{r.stdout[-2000:]}\n" \
        f"{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


def test_seq_chunked_matches_unchunked_runtime():
    """chronos_seq (sequence-chunked units, prefix-KV causal attention,
    dKV accumulation through the vjp cotangents) must reproduce the
    unchunked chronos pipeline gradients: chunked attention is
    row-for-row identical to full-sequence attention, so the only
    divergence is float summation order in the weight-gradient
    reductions (<= 2e-5)."""
    r = _run([sys.executable, SPLIT_HELPER, "--pair", "seq", "2", "4"])
    assert r.returncode == 0, \
        f"seq-vs-unchunked failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "MAXERR=" in r.stdout


def test_seq_pipeline_step_builder_dry():
    """ParallelPlan(seq_chunks>1) -> make_pipeline_train_step -> seqpipe
    executor plumbing, trace-only."""
    r = _run([sys.executable, SEQ_HELPER, "--dry"])
    assert r.returncode == 0, \
        f"seq dry check failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "OK=1" in r.stdout


@pytest.mark.slow
def test_offload_train_matches_device_optimizer():
    """train_pipeline with the host optimizer for the deepest chunk
    tracks the all-on-device run (few 1e-3 over 3 steps) and reports
    the Eq. (5)/(7) overlap validation."""
    r = _run([sys.executable, OFFLOAD_HELPER, "2", "3"])
    assert r.returncode == 0, \
        f"offload train check failed:\n{r.stdout[-2000:]}\n" \
        f"{r.stderr[-3000:]}"
    assert "OK=1" in r.stdout and "report=" in r.stdout


@pytest.mark.slow
def test_seq1f1b_grad_equivalence_vs_single_device():
    """seq1f1b at 4 seq chunks against single-device autodiff."""
    run_case("tinyllama-1.1b", "seq1f1b", P=2, v=1, m=4, ndev=2, dp=1,
             tp=1, n_seq=4)


@pytest.mark.slow
def test_chronos_seq_grad_equivalence_vs_single_device():
    run_case("tinyllama-1.1b", "chronos_seq", P=2, v=2, m=4, ndev=2,
             dp=1, tp=1, n_seq=2)


@pytest.mark.slow
def test_seq_train_driver_matches_unchunked():
    """train_pipeline with seq1f1b tracks the unchunked 1f1b run
    step-for-step (same data/seed; float-summation-order noise only)."""
    r = _run([sys.executable, SEQ_HELPER, "2", "3", "3"])
    assert r.returncode == 0, \
        f"seq train check failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "OK=1" in r.stdout


@pytest.mark.slow
def test_overlap_calibration_measured_vs_predicted():
    """Measured steady step with the double-buffered wire at P=4 must
    track ``comm_calibration``'s tc-overlapped retime prediction closer
    than the pay-per-tick null model (sync time scaled by the overlap
    table's tick stretch) — the CPU-tolerant form of 'overlap converges
    to the modelled async comm cost'.  See the helper docstring."""
    r = _run([sys.executable, CALIB_HELPER], timeout=900)
    assert r.returncode == 0, \
        f"overlap calibration failed:\n{r.stdout[-2000:]}\n" \
        f"{r.stderr[-3000:]}"
    assert "OK=1" in r.stdout


@pytest.mark.slow
def test_deeper_pipeline_p4():
    run_case("tinyllama-1.1b", "chronos", P=4, v=2, m=8)


@pytest.mark.slow
def test_deeper_split_pipeline_p4():
    """P=4 exercises zb_h1's BWD/WGT mid-stage op codes."""
    run_case("tinyllama-1.1b", "zb_h1", P=4, v=1, m=8)


@pytest.mark.slow
def test_moe_pipeline():
    run_case("qwen2-moe-a2.7b", "chronos", P=2, v=2, m=4)


@pytest.mark.slow
def test_hybrid_mamba_moe_pipeline():
    run_case("jamba-pipe", "chronos", P=2, v=2, m=4)


@pytest.mark.slow
def test_encdec_pipeline_with_padding():
    # whisper smoke: 2 decoder layers padded to 4 (2 null layers)
    run_case("whisper-base", "chronos", P=2, v=2, m=4)


@pytest.mark.slow
def test_vlm_prefix_pipeline():
    run_case("paligemma-3b", "chronos", P=2, v=2, m=4)


@pytest.mark.slow
def test_pipeline_with_tp_dp_auto_axes():
    """pp + dp/tp on an 8-device mesh: the executor keeps pp manual and
    dp/tp auto, and the multi-axis gradients must match the
    single-device reference."""
    run_case("tinyllama-1.1b", "chronos", P=2, v=2, m=4, ndev=8, dp=2, tp=2)
