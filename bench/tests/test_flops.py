"""Counts against the hand counts of the benchmark's configurations."""
import pytest

from bench import flops, harness, model as M


def _m(name):
    return M.from_config(M.load_config(name))


def test_layer_params():
    # DeepSeek LLM 7B: 4 * 4096^2 attention + 3 * 4096 * 11008 MLP + norms
    assert M.layer_params(_m("deepseek-7b-stage")) == pytest.approx(
        202.4e6, rel=1e-3)


def test_model_flops_per_token():
    ds = _m("deepseek-7b-stage")
    # 6 x (2 layers x 202.4 M + 4096 x 12800 head) + 12 x 2 x s x 4096
    assert flops.model_flops_per_token(ds, 4096) == pytest.approx(
        3.14e9, rel=2e-3)
    assert flops.model_flops_per_token(ds, 2048) == pytest.approx(
        6 * (2 * 202.37e6 + 4096 * 12800) + 12 * 2 * 2048 * 4096, rel=1e-4)


def test_executed_matmul_counts_the_replay():
    ds = _m("deepseek-7b-stage")
    t = harness.load_traffic("recomp-1chip")
    ex = flops.executed_matmul(ds, t)
    tokens = 8 * 2048
    proj = 2 * 202.37e6 * 2 * tokens * 3.5   # F, 2 x B, replay of 1 of 2
    assert ex["projections"][0] == pytest.approx(proj, rel=1e-4)
    assert ex["head"][0] == pytest.approx(6 * 4096 * 12800 * tokens)
    no_replay = dict(t, plan=dict(t["plan"], recompute={
        "mode": "none", "num_recomp_chunks": 0}))
    assert flops.executed_matmul(ds, no_replay)["projections"][0] == \
        pytest.approx(proj * 3 / 3.5, rel=1e-4)


def test_peaks():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 1.97e14 and pk["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("source")
