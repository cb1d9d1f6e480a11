"""Phase-compiled executor: factorization properties, trace-dedup
accounting, wire packing, and the degenerate-collective fix.

The numerical equivalence of the phase executor itself rides in the
existing suites (it is the default executor for every pipeline test and
every ``split_fused_check`` pair).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.schedules import REGISTRY, get_schedule
from repro.core.tasktable import (build_task_table, factor_phases,
                                  replay_phases, validate_table)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TRACE_HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                            "phase_trace_check.py")


def _sched_kwargs(name):
    kw = {}
    if name in ("chronos", "interleaved", "chronos_zero2", "chronos_zb",
                "chronos_recomp", "chronos_seq"):
        kw["v"] = 2
    if name in ("seq1f1b", "chronos_seq"):
        kw["n_seq"] = 2
    return kw


@pytest.mark.parametrize("name", sorted(REGISTRY))
@pytest.mark.parametrize("P,m", [(4, 8), (4, 16)])
def test_phase_factorization_is_pure_reencoding(name, P, m):
    """For every registered schedule x placement, the phase-factored
    table replayed tick-for-tick (steady template advanced by the mb
    stride, modular ring slots re-derived) equals the original [T, P]
    table in every column — factorization is a pure re-encoding, which
    is exactly the invariant that lets the executor consume the
    replayed stream."""
    sched = get_schedule(name, P, m, **_sched_kwargs(name))
    tab = build_task_table(sched)
    validate_table(tab)
    plan = factor_phases(tab)
    assert plan.T == tab.T
    rep = replay_phases(tab, plan)
    assert rep.shape == tab.arrays().shape
    assert np.array_equal(rep, tab.arrays()), \
        f"{name}: replay diverges at " \
        f"{np.argwhere(rep != tab.arrays())[:4].tolist()}"


@pytest.mark.parametrize("name,P,m,period", [
    ("chronos", 4, 8, 4),        # the acceptance cell
    ("1f1b", 4, 16, 2),
    ("zb_h1", 4, 16, 2),
    ("v_min", 4, 16, 6),
])
def test_known_steady_periods(name, P, m, period):
    """Families with analytically obvious steady states compress to
    their expected period lengths (documented in docs/SCHEDULES.md)."""
    sched = get_schedule(name, P, m, **_sched_kwargs(name))
    plan = factor_phases(build_task_table(sched))
    assert plan.period == period, plan
    assert plan.n_periods >= 2
    assert plan.compressed_ticks < plan.T


def test_phase_executor_traces_each_body_once():
    """Trace-dedup accounting: lowering the phase executor runs the
    embed / chunk / head Python bodies exactly once each, for the
    fused, split (B/W), and seq-chunked paths — switch branches reuse
    the recorded jaxpr instead of re-tracing."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, TRACE_HELPER], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, \
        f"trace check failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("COUNTS")]
    assert len(lines) == 3, r.stdout
    for ln in lines:
        assert ln.endswith("embed=1 chunk=1 head=1"), ln


def test_ppermute_skips_degenerate_perms():
    """The P=1 hop wrap (``perm = [(0, 0)]``) and any all-identity
    permutation pass the payload through without issuing a collective
    (the legacy path used to ppermute a self-permutation)."""
    import jax.numpy as jnp

    from repro.core.pipeline_runtime import _ppermute
    from repro.seqpipe.runtime import _ppermute as _ppermute_seq
    x = {"x": jnp.arange(6.0).reshape(2, 3)}
    for fn in (_ppermute, _ppermute_seq):
        out = fn(x, "pp", [(0, 0)])
        assert out is x          # no collective, exact pass-through
        out = fn(x, "pp", [(0, 0), (1, 1)])
        assert out is x


@pytest.mark.parametrize("schedule,n_seq", [("chronos", 1),
                                            ("chronos_seq", 2)])
def test_deferred_exchange_short_circuits_without_xdev(schedule, n_seq):
    """P=1 under ``overlap=True``: the table carries the overlap flag
    but holds no cross-device send code, so the double-buffered wire
    must collapse to the synchronous tick — no send/recv buffer pair,
    no exchange collective in the compiled HLO (mirroring
    ``_ppermute``'s identity skip) — in BOTH runtimes (core phase
    executor and the seq-chunked executor), with gradients bitwise
    equal to the overlap=False build."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.core.pipeline_runtime import (init_pipeline_params,
                                             make_pipeline_spec,
                                             make_train_grads_fn)
    from repro.models.sharding import make_mesh
    from repro.models import shard_env

    cfg = get_reduced("tinyllama-1.1b")
    mesh = make_mesh((1,), ("pp",))
    S = 13 if n_seq > 1 else 12       # seq executor: n_seq | (S - 1)
    kw = dict(P=1, v=2, m=2, microbatch=2, seq_len=S,
              schedule=schedule)
    if n_seq > 1:
        kw["n_seq"] = n_seq
    layout = make_pipeline_spec(cfg, **kw).layout
    params, _ = init_pipeline_params(jax.random.key(0), cfg, layout)
    tokens = jax.random.randint(jax.random.key(1), (2, 2, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    grads = {}
    with shard_env(mesh, {}):
        for name, ov in (("sync", False), ("overlap", True)):
            spec = make_pipeline_spec(cfg, **kw, overlap=ov)
            assert spec.table.overlap is ov
            fn = jax.jit(make_train_grads_fn(spec, mesh,
                                             executor="phase"))
            hlo = fn.lower(params, batch).compile().as_text()
            # the wire collectives must be absent; (all-reduce for the
            # final loss/shared-grad psum is outside the wire protocol)
            assert "collective-permute" not in hlo
            assert "all-gather" not in hlo
            g, _ = fn(params, batch)
            grads[name] = g
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        grads["sync"], grads["overlap"])


@pytest.mark.parametrize("schedule,P,kw,overlap,want_T", [
    ("chronos", 4, {}, None, 44),
    ("interleaved", 4, {}, None, 38),
    ("v_min", 4, {}, None, 59),
    ("chronos_seq", 4, {"n_seq": 2}, None, 76),
    ("chronos_recomp", 1, {"recomp_chunks": 1}, None, 40),
    ("chronos", 4, {}, True, 78),
    ("interleaved", 4, {}, True, 52),
    ("interleaved", 4, {}, False, 38),
])
def test_tick_contract_default_is_synchronous(schedule, P, kw, overlap,
                                              want_T):
    """``make_pipeline_spec`` builds the synchronous task table unless
    ``overlap=True`` pins the double-buffered one.  Without a
    cross-device send (P=1) both contracts give the same table, so the
    one-chip step keeps its ticks and ring depths.  Tables only, no
    compile."""
    from repro.configs import get_reduced
    from repro.core.pipeline_runtime import make_pipeline_spec

    def table(**ov):
        return make_pipeline_spec(get_reduced("tinyllama-1.1b"), P=P, v=2,
                                  m=8, microbatch=2, seq_len=17,
                                  schedule=schedule, **kw, **ov).table

    tab = table(**({} if overlap is None else {"overlap": overlap}))
    assert tab.overlap is bool(overlap) and tab.T == want_T
    other = table(overlap=not overlap)
    if P == 1:
        np.testing.assert_array_equal(tab.arrays(), other.arrays())
        assert (tab.fq_depth, tab.bq_depth, tab.act_depth,
                tab.rmt_depth) == (other.fq_depth, other.bq_depth,
                                   other.act_depth, other.rmt_depth)
    else:
        # the double-buffered contract stretches every cross-device
        # dependency, so it always takes more ticks
        assert (tab.T < other.T) is not bool(overlap)


def test_phase_executor_bf16_recomp_p1_grads_finite():
    """The one-chip shape of ``chip_smoke.py`` at toy widths: bf16
    parameters, ``chronos_recomp`` P=1 v=2.  The head and embed
    pullbacks (bf16) and the zero branches (fp32 accumulators) of one
    ``cond`` must agree on dtype, and the gradients come out finite."""
    import dataclasses

    import jax

    from repro.configs import get_reduced
    from repro.core.pipeline_runtime import (init_pipeline_params,
                                             make_pipeline_spec,
                                             make_train_grads_fn)
    from repro.models import shard_env
    from repro.models.sharding import make_mesh

    cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    mesh = make_mesh((1,), ("pp",))
    spec = make_pipeline_spec(cfg, P=1, v=2, m=4, microbatch=1,
                              seq_len=17, schedule="chronos_recomp",
                              recomp_chunks=1)
    assert spec.table.has_r
    params, _ = init_pipeline_params(jax.random.key(0), cfg, spec.layout)
    tokens = jax.random.randint(jax.random.key(1), (4, 1, 17), 0,
                                cfg.vocab_size)
    with shard_env(mesh, {}):
        grads, met = jax.jit(make_train_grads_fn(spec, mesh))(
            params, {"tokens": tokens})
    assert np.isfinite(float(met["loss"]))
    for g in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(g, np.float32)))


def test_payload_packing_roundtrip_bitwise():
    """The byte-packed wire format is an exact (bitcast) round-trip,
    including the broadcast-row aux scalar."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.core.pipeline_runtime import (_pack_payload,
                                             _payload_words,
                                             _unpack_payload,
                                             make_pipeline_spec)
    cfg = get_reduced("tinyllama-1.1b")
    spec = make_pipeline_spec(cfg, P=2, v=2, m=4, microbatch=2,
                              seq_len=17, schedule="chronos")
    key = jax.random.key(0)
    pay = {"x": jax.random.normal(
        key, (spec.mbB, spec.S, cfg.d_model),
        jnp.dtype(cfg.compute_dtype)),
        "aux": jax.random.normal(jax.random.key(1), (1,), jnp.float32)}
    flat = _pack_payload(spec, pay)
    assert flat.shape == (spec.mbB, _payload_words(spec))
    assert flat.dtype == jnp.uint16
    out = _unpack_payload(spec, flat)
    for k in pay:
        assert out[k].dtype == pay[k].dtype
        assert jnp.array_equal(out[k], pay[k],
                               equal_nan=True), k


def test_planner_dse_perf_smoke():
    """Perf regression pin: a full planner enumeration at P=8 (the
    benchmarks/planner_dse.py ladder) stays under a generous wall-clock
    bound now that the schedule IR hot loops (check / peak_activation /
    retime_with_comm) are numpy-vectorized.  Measured ~1-2 s on the
    2-core CI box; the bound leaves ~15x headroom for slower hosts."""
    import time

    from benchmarks.common import GB, PAPER_ACT_SCALE
    from repro.configs.llama70b_paper import with_layers
    from repro.plan import PlannerQuery, enumerate_points
    q = PlannerQuery(cfg=with_layers(48), pp=8, tp=8,
                     hbm_bytes=32 * GB, reserve=1 * GB,
                     act_scale=PAPER_ACT_SCALE)
    t0 = time.perf_counter()
    pts = list(enumerate_points(q))
    elapsed = time.perf_counter() - t0
    assert len(pts) >= 30
    assert elapsed < 30.0, f"planner enumeration took {elapsed:.1f}s"
