"""The seam between the benchmark and an architecture (``bench/arch/``):
the dense decoder behind it describes what it described before, a second
architecture runs through the harness without an edit to the benchmark,
the placer maps a structural period above 1, and a configuration that
names no architecture, or an unknown one, is refused."""
import dataclasses
import json
import sys
import time
import types
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import check, harness, model as M
from bench import weights as W
from bench.arch import common, dense_decoder as dense
from bench.program import Program
from bench.tests.test_faults import ATTN, E2E, _traffic

# -- the dense decoder, as the benchmark described it before the seam ----

PROGRAM_CONFIG = {
    "family": "dense", "d_model": 4096, "num_heads": 32, "num_kv_heads": 32,
    "d_ff": 11008, "vocab_size": 12800, "head_dim": 128, "qkv_bias": False,
    "rope_theta": 10000.0, "sliding_window": 0, "attn_pattern_period": 0,
    "global_offsets": (), "act": "silu", "norm_eps": 1e-06,
    "tie_embeddings": False, "moe": None, "ssm": None, "encdec": None,
    "vision": None, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
LAYER_LEAVES = [
    ("norm1.scale", (4096,), ("ones",), "bfloat16"),
    ("attn.wq", (4096, 4096), ("normal", 0.015625), "bfloat16"),
    ("attn.wk", (4096, 4096), ("normal", 0.015625), "bfloat16"),
    ("attn.wv", (4096, 4096), ("normal", 0.015625), "bfloat16"),
    ("attn.wo", (4096, 4096), ("normal", 0.015625), "bfloat16"),
    ("norm2.scale", (4096,), ("ones",), "bfloat16"),
    ("mlp.wi", (4096, 11008), ("normal", 0.015625), "bfloat16"),
    ("mlp.wg", (4096, 11008), ("normal", 0.015625), "bfloat16"),
    ("mlp.wo", (11008, 4096), ("normal", 0.009531160645787792), "bfloat16")]
SHARED_LEAVES = [
    ("embed.tokens", (12800, 4096), ("normal", 0.015625), "bfloat16"),
    ("embed.head", (4096, 12800), ("normal", 0.015625), "bfloat16"),
    ("final_norm.scale", (4096,), ("ones",), "bfloat16")]


@pytest.mark.parametrize("name,layers", [("deepseek-7b-stage", 2),
                                         ("deepseek-7b-4stages", 8)])
def test_dense_decoder_describes_what_it_did(name, layers):
    m = M.from_config(M.load_config(name))
    assert M.arch(m) is dense
    assert dataclasses.asdict(dense.program_config(m)) == dict(
        PROGRAM_CONFIG, name=name, num_layers=layers)
    for g in range(layers):
        assert M.layer_leaves(m, g) == LAYER_LEAVES
    assert M.shared_leaves(m) == SHARED_LEAVES


# -- a second architecture, brought by a module alone ---------------------

WINDOW = 16


def _windowed_arch(name, reference_window):
    """The dense decoder with a sliding window of ``WINDOW`` on every odd
    layer; with ``reference_window`` false its reference leaves the
    window out while the program keeps it."""
    mod = types.ModuleType(f"bench.arch.{name}")
    for k in ("Model", "describe", "layer_leaves", "shared_leaves", "decays",
              "matmul_params", "embed", "head_loss",
              "model_flops_per_token", "executed_matmul"):
        setattr(mod, k, getattr(dense, k))

    def layer_kind(m, g):
        return "window" if g % 2 else "full"

    def layer(m, g, p, x, scales):
        B, S, d = x.shape
        p, quant = common._qp(p, scales), scales is not None
        h = common._rmsnorm(x, p["norm1.scale"], m.eps)
        q = common._mm(h, p["attn.wq"], quant).reshape(B, S, m.heads, m.hd)
        k = common._mm(h, p["attn.wk"], quant).reshape(B, S, m.kv_heads,
                                                       m.hd)
        v = common._mm(h, p["attn.wv"], quant).reshape(B, S, m.kv_heads,
                                                       m.hd)
        win = WINDOW if reference_window and layer_kind(m, g) == "window" \
            else None
        o = common._causal_attention(common._rope(q, m.rope_theta),
                                     common._rope(k, m.rope_theta), v,
                                     window=win)
        x = x + common._mm(o, p["attn.wo"], quant)
        h = common._rmsnorm(x, p["norm2.scale"], m.eps)
        a = jax.nn.silu(common._mm(h, p["mlp.wg"], quant)) * \
            common._mm(h, p["mlp.wi"], quant)
        return x + common._mm(a, p["mlp.wo"], quant)

    def program_config(m):
        return dataclasses.replace(dense.program_config(m),
                                   sliding_window=WINDOW,
                                   attn_pattern_period=2, global_offsets=(0,))

    mod.layer_kind, mod.layer, mod.program_config = \
        layer_kind, layer, program_config
    return mod


@pytest.mark.parametrize("reference_window", [True, False],
                         ids=["windowed", "window_left_out"])
def test_second_architecture_runs_without_an_edit(monkeypatch,
                                                  reference_window):
    name = "windowed_decoder_test"
    monkeypatch.setitem(sys.modules, f"bench.arch.{name}",
                        _windowed_arch(name, reference_window))
    cell = "deepseek-7b-stage.recomp-1chip"
    res = harness.run_cell(
        {"name": cell}, dict(ATTN, arch=name), _traffic(),
        seed=2 ** 31 + 29, seconds=0.2, trace=False,
        devices=jax.devices()[:1], limits=check.load_limits(cell), e2e=E2E,
        per_layer=[], t_start=time.monotonic())
    assert res["correct"] is reference_window, res["checks"]


# -- the placer at a structural period of 2 -------------------------------

def _two_kind_arch():
    """Even layers hold one leaf, odd layers two of another shape."""
    mod = types.ModuleType("bench.arch.two_kinds_test")

    def layer_leaves(m, g):
        if g % 2 == 0:
            return [("a.w", (4,), ("normal", 1.0), "float32")]
        return [("b.scale", (3,), ("ones",), "float32"),
                ("b.w", (3,), ("normal", 1.0), "float32")]

    mod.layer_leaves = layer_leaves
    mod.shared_leaves = lambda m: [
        ("embed.tokens", (5, 4), ("normal", 1.0), "float32")]
    return mod


def test_placer_at_period_two(monkeypatch):
    from repro.core.pipeline_runtime import StageLayout
    monkeypatch.setitem(sys.modules, "bench.arch.two_kinds_test",
                        _two_kind_arch())
    lay = StageLayout(P=2, v=2, L=16, L_pad=16, K=4, period=2, M=2)
    prog = Program.__new__(Program)
    prog.model = SimpleNamespace(arch="two_kinds_test", layers=16)
    prog.spec = SimpleNamespace(layout=lay)
    gidx = prog.block_layers()
    # [d][c][mi][j]: block (c * P + d) holds layers 4 (c * P + d) .. + 3
    assert gidx.tolist() == [[[[0, 1], [2, 3]], [[8, 9], [10, 11]]],
                             [[[4, 5], [6, 7]], [[12, 13], [14, 15]]]]

    sds = jax.ShapeDtypeStruct
    prog.structs = ({"blocks": [{"a": {"w": sds((2, 2, 2, 4), "float32")}},
                                {"b": {"scale": sds((2, 2, 2, 3), "float32"),
                                       "w": sds((2, 2, 2, 3), "float32")}}],
                     "embed": {"tokens": sds((5, 4), "float32")}},)
    seed = W.seed32(2 ** 31 + 5)
    params = prog._make_placer()(np.uint32(seed))
    key0 = W.base_key(seed)
    for (d, c, mi, j), g in np.ndenumerate(gidx):
        for i, (path, shape, init, dt) in enumerate(
                M.layer_leaves(prog.model, int(g))):
            a, b = path.split(".")
            want = W.leaf(key0, int(g), i, shape, init, dt)
            np.testing.assert_array_equal(
                params["blocks"][j][a][b][d, c, mi], want)


# -- configurations that name no architecture ----------------------------

@pytest.mark.parametrize("arch", [None, "no_such_arch", "../dense_decoder"])
def test_configuration_without_a_known_arch_is_refused(monkeypatch, tmp_path,
                                                       arch):
    cfg = M.load_config("deepseek-7b-stage")
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "x.json"
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(M, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match=str(path)):
        M.load_config("x")
