"""Subprocess helper: pairwise gradient-equivalence checks between two
pipeline schedules on the same parameters and batch.

Cross-schedule pairs (same ``kernels="xla"`` backend both sides):
    zb      1f1b (fused backward) vs zb_h1 (B = input-grad + residual
            stash, W = deferred weight-grad); tolerance 1e-5.
    recomp  chronos (no recompute) vs chronos_recomp rho=1 (explicit R
            tasks: boundary checkpoint handed act-ring -> remat-ring,
            replay fused into B's vjp); the compiled gradient math is
            identical, so the tolerance is 0.0 — bitwise.
    seq     chronos (whole-sequence tasks) vs chronos_seq n_seq=2
            (sequence-chunked units; prefix-KV causal attention + dKV
            accumulation through the vjp cotangents).  Chunked
            attention is row-for-row identical to full-sequence
            attention, so per-token forwards match bitwise; weight
            gradients and the loss differ only by float summation
            order (one dot over S vs n_seq partial dots + adds) —
            tolerance 2e-5.
    vshape  chronos v=2 (interleaved placement, fused backward) vs
            v_min (V-shape placement: device d holds blocks d and
            2P-1-d, split B/W backward).  The v_min parameters are the
            chronos parameters remapped position-for-position to the
            V layout (`remap_blocks`), so both runs compute the same
            network; gradients are remapped back before comparing.
            Same-math-different-split tolerance as the zb pair (1e-5).

Cross-backend pairs (same schedule, ``kernels="xla"`` vs ``"fused"`` —
the repro.models.backend seam dispatching the Pallas kernel library,
interpret=True on CPU).  The fused rmsnorm forward is bitwise; flash
attention and the SSD kernel change only the softmax / chunk-dot
reduction order, so forwards agree to a few ulps and gradients to the
same same-math-different-summation tolerances as above:
    fused_chronos  chronos v=2        tolerance 1e-4
    fused_zb       zb_h1   v=1        tolerance 1e-4
    fused_vmin     v_min   v=2        tolerance 1e-4
    fused_seq      chronos_seq n_seq=2 (+ loss mask; exercises the
                   dynamic-q_offset flash path)      tolerance 1e-4
    fused_mamba    chronos v=2 on the mamba2-2.7b reduced config
                   (SSD chunk-scan kernel, S=17 not a chunk multiple
                   so the dt=0 zero-padding path runs)  tolerance 1e-4

Wire-dtype pairs (same schedule both sides, chronos v=2; side a is the
default fp32-mantissa wire, side b quantizes boundary payloads inside
the packed uint16 buffer).  Gradient error is *normalized* per leaf
(max |g_a - g_b| / max |g_a|) because the wire error is relative to the
activation scale; pinned tolerances carry headroom over the measured
errors on the reduced config (bf16 ~5.6e-3, int8 ~4.1e-2):
    wire_bf16   bf16 payloads   normalized tolerance 2e-2
    wire_int8   int8 + per-tile scale in the aux words   tolerance 1e-1

Tick-contract pair (same schedule, chronos v=2; side a takes
``make_pipeline_spec``'s default synchronous table, side b pins the
double-buffered one).  Both tables keep the per-device op order, so the
tolerance is 0.0 — bitwise:
    tick_contract   overlap=False (default) vs overlap=True

``--overlap`` runs any other pair but ``opt`` with the double-buffered
table on both sides (``overlap=True``), so the deferred exchange, its
queues and the ``wire`` carry are checked under the same tolerances.

Optimizer-fusion pair:
    opt     zb_h1 with kernels="fused": N steps of the in-executor
            fused AdamW (make_train_update_fn — update inside the
            shard_map region after the tick scan) vs the phase-separate
            reference (make_train_grads_fn -> astype(f32)/m ->
            adamw_update(use_kernel=True)).  Same step count, losses
            and final parameters compared per step; the only
            reassembled quantity is the clipping norm (psum of local
            square-sums), so the trajectory matches to float-summation
            tolerance 1e-5.

Usage: python split_fused_check.py [--pair NAME] [--overlap] [P] [m]
Exits 0 when max |g_a - g_b| <= tol; prints MAXERR=... for the parent
test to parse.
"""
import functools
import os
import sys

args = sys.argv[1:]
pair = "zb"
if args and args[0] == "--pair":
    pair = args[1]
    args = args[2:]
pinned = bool(args) and args[0] == "--overlap"
if pinned:
    args = args[1:]
P_ = int(args[0]) if len(args) > 0 else 2
m = int(args[1]) if len(args) > 1 else 4
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P_}"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.core.pipeline_runtime import (init_pipeline_params,  # noqa: E402
                                         make_pipeline_spec,
                                         make_train_grads_fn)
from repro.models.sharding import make_mesh  # noqa: E402
from repro.models import shard_env  # noqa: E402

mbB, S = 2, 17
mesh = make_mesh((P_,), ("pp",))
if pinned:
    assert pair not in ("opt", "tick_contract"), pair
    make_pipeline_spec = functools.partial(make_pipeline_spec, overlap=True)

WIRE_PAIRS = {"wire_bf16": ("bf16", 2e-2), "wire_int8": ("int8", 1e-1)}

FUSED_PAIRS = {
    "fused_chronos": dict(schedule="chronos", v=2),
    "fused_zb": dict(schedule="zb_h1", v=1),
    "fused_vmin": dict(schedule="v_min", v=2),
    "fused_seq": dict(schedule="chronos_seq", v=2, n_seq=2, mask=True),
    "fused_mamba": dict(schedule="chronos", v=2, arch="mamba2-2.7b"),
}

cfg = get_reduced(FUSED_PAIRS.get(pair, {}).get("arch", "tinyllama-1.1b"))

if pair == "opt":
    # ---- in-executor fused AdamW vs phase-separate optimizer ----
    from repro.configs.base import OptimizerConfig  # noqa: E402
    from repro.core.pipeline_runtime import make_train_update_fn  # noqa
    from repro.optim import (adamw_init, adamw_update,  # noqa: E402
                             cast_like)

    nsteps = 3
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    spec = make_pipeline_spec(cfg, P=P_, v=1, m=m, microbatch=mbB,
                              seq_len=S, schedule="zb_h1",
                              kernels="fused")
    assert spec.table.has_w
    params, _ = init_pipeline_params(jax.random.key(0), cfg, spec.layout)
    grads_fn = jax.jit(make_train_grads_fn(spec, mesh))
    update_fn = jax.jit(make_train_update_fn(spec, mesh, ocfg, m))
    pa, sa = params, adamw_init(params)
    pb, sb = params, adamw_init(params)
    errs, la, lb = [], 0.0, 0.0
    with shard_env(mesh, {}):
        for t in range(nsteps):
            tokens = jax.random.randint(
                jax.random.fold_in(jax.random.key(1), t), (m, mbB, S), 0,
                cfg.vocab_size)
            batch = {"tokens": tokens}
            g, met_a = grads_fn(pa, batch)
            g = jax.tree.map(lambda a: a.astype(jnp.float32) / m, g)
            master, sa, _ = adamw_update(g, sa, ocfg, use_kernel=True)
            pa = cast_like(master, pa)
            pb, sb, met_b = update_fn(pb, sb, batch)
            la, lb = float(met_a["loss"]), float(met_b["loss"])
            errs.append(abs(la - lb))
    assert int(sa["step"]) == nsteps and int(sb["step"]) == nsteps, \
        "step-count mismatch between fused and phase-separate optimizer"
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        errs.append(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))))
    maxerr = max(errs)
    print(f"MAXERR={maxerr:.3e} pair={pair} loss_a={la:.6f} "
          f"loss_b={lb:.6f}")
    sys.exit(0 if maxerr <= 1e-5 else 1)

if pair == "zb":
    spec_a = make_pipeline_spec(cfg, P=P_, v=1, m=m, microbatch=mbB,
                                seq_len=S, schedule="1f1b")
    spec_b = make_pipeline_spec(cfg, P=P_, v=1, m=m, microbatch=mbB,
                                seq_len=S, schedule="zb_h1")
    assert spec_b.table.has_w and not spec_a.table.has_w
    tol = 1e-5
elif pair == "recomp":
    spec_a = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos")
    spec_b = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos_recomp",
                                rho=1.0, recomp_chunks=1)
    assert spec_b.table.has_r and not spec_a.table.has_r
    tol = 0.0
elif pair == "tick_contract":
    spec_a = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos")
    spec_b = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos", overlap=True)
    assert not spec_a.table.overlap and spec_b.table.overlap
    assert spec_a.table.T < spec_b.table.T
    tol = 0.0
elif pair == "seq":
    spec_a = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos")
    spec_b = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos_seq",
                                n_seq=2)
    assert spec_b.n_seq == 2 and spec_b.table.n_seq == 2
    tol = 2e-5
elif pair == "vshape":
    spec_a = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos")
    spec_b = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="v_min")
    assert spec_b.table.placement_name == "vshape" and spec_b.table.has_w
    tol = 1e-5
elif pair in WIRE_PAIRS:
    wname, tol = WIRE_PAIRS[pair]
    spec_a = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos")
    spec_b = make_pipeline_spec(cfg, P=P_, v=2, m=m, microbatch=mbB,
                                seq_len=S, schedule="chronos", wire=wname)
    assert spec_a.wire == "fp32" and spec_b.wire == wname
elif pair in FUSED_PAIRS:
    kw = FUSED_PAIRS[pair]
    extra = {"n_seq": kw["n_seq"]} if "n_seq" in kw else {}
    spec_a = make_pipeline_spec(cfg, P=P_, v=kw["v"], m=m, microbatch=mbB,
                                seq_len=S, schedule=kw["schedule"],
                                kernels="xla", **extra)
    spec_b = make_pipeline_spec(cfg, P=P_, v=kw["v"], m=m, microbatch=mbB,
                                seq_len=S, schedule=kw["schedule"],
                                kernels="fused", **extra)
    assert spec_a.kernels == "xla" and spec_b.kernels == "fused"
    tol = 1e-4
else:
    raise SystemExit(f"unknown pair {pair!r}")
if pinned:
    assert spec_a.table.overlap and spec_b.table.overlap

params, _ = init_pipeline_params(jax.random.key(0), cfg, spec_a.layout)
params_b = params
if pair == "vshape":
    # same network under both placements: remap the interleaved-layout
    # blocks position-for-position into the V layout
    from repro.core.pipeline_runtime import remap_blocks
    params_b = dict(params, blocks=remap_blocks(
        params["blocks"], spec_a.layout, spec_b.layout))
tokens = jax.random.randint(jax.random.key(1), (m, mbB, S), 0,
                            cfg.vocab_size)
batch = {"tokens": tokens}
if pair == "seq" or FUSED_PAIRS.get(pair, {}).get("mask"):
    # also exercise the masked-loss path: the chunked executor must
    # normalize by the whole-sequence mask count, not the chunk's
    batch["loss_mask"] = (jax.random.uniform(
        jax.random.key(2), (m, mbB, S - 1)) > 0.3).astype(jnp.float32)

with shard_env(mesh, {}):
    g_a, met_a = jax.jit(make_train_grads_fn(spec_a, mesh))(params, batch)
    g_b, met_b = jax.jit(make_train_grads_fn(spec_b, mesh))(params_b,
                                                            batch)
if pair == "vshape":
    # map the V-layout block grads back so every position compares the
    # same global layer
    g_b = dict(g_b, blocks=remap_blocks(g_b["blocks"], spec_b.layout,
                                        spec_a.layout))

norm = pair in WIRE_PAIRS        # wire error scales with activations
errs = [abs(float(met_a["loss"]) - float(met_b["loss"]))
        / (abs(float(met_a["loss"])) if norm else 1.0)]
for a, b in zip(jax.tree.leaves(g_a), jax.tree.leaves(g_b)):
    err = float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
    if norm:
        err /= float(jnp.max(jnp.abs(a.astype(jnp.float32)))) + 1e-12
    errs.append(err)
maxerr = max(errs)
print(f"MAXERR={maxerr:.3e} pair={pair} loss_a={float(met_a['loss']):.6f} "
      f"loss_b={float(met_b['loss']):.6f}")
sys.exit(0 if maxerr <= tol else 1)
