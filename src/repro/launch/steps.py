"""Step builders + input specs for training and serving.

Everything here is AOT-friendly: ``input_specs`` returns
ShapeDtypeStructs (weak-type-correct, shardable, no allocation), and the
step builders return (fn, in_shardings, out_shardings) tuples ready for
``jax.jit(...).lower(...)`` — the dry-run path — or real execution.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelPlan,
                                ShapeConfig)
from repro.models import LM
from repro.models.sharding import ShardEnv, sanitize_spec, shard_env
from repro.optim import adamw_init, adamw_update, cast_like, zero_state_specs
from repro.optim.adamw import drop_fsdp

_log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def resolve_shardings(tree, logical_specs, mesh, rules,
                      shapes: Optional[Any] = None):
    """logical spec tree -> NamedSharding tree (divisibility-sanitized)."""
    env = ShardEnv(mesh, rules)

    def one(leaf, spec):
        pspec = env.resolve(spec) if spec is not None else P()
        shape = leaf.shape if hasattr(leaf, "shape") else None
        if shape is not None:
            pspec = sanitize_spec(pspec, shape, mesh)
        return NamedSharding(mesh, pspec)

    return jax.tree.map(
        one, tree, logical_specs,
        is_leaf=lambda x: isinstance(x, tuple) or x is None)


def _is_spec_leaf(x):
    return isinstance(x, tuple) or x is None


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                      plan: ParallelPlan, mesh, rules):
    """tokens [m, mb_global, S] (+ modality stubs)."""
    dp = _axes_size(mesh, rules.get("dp"))
    mb_global = plan.microbatch_size * dp
    m = max(1, shape.global_batch // mb_global)
    structs = {"tokens": jax.ShapeDtypeStruct(
        (m, mb_global, shape.seq_len), jnp.int32)}
    shardings = {"tokens": NamedSharding(mesh, sanitize_spec(
        P(None, _r(rules, "dp")), (m, mb_global, shape.seq_len), mesh))}
    if cfg.vision is not None:
        s = (m, mb_global, cfg.vision.num_patches, cfg.d_model)
        structs["patch_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        shardings["patch_embeds"] = NamedSharding(mesh, sanitize_spec(
            P(None, _r(rules, "dp")), s, mesh))
    if cfg.encdec is not None:
        s = (m, mb_global, cfg.encdec.num_frames, cfg.d_model)
        structs["frame_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        shardings["frame_embeds"] = NamedSharding(mesh, sanitize_spec(
            P(None, _r(rules, "dp")), s, mesh))
    return structs, shardings, m, mb_global


def _r(rules, k):
    return rules.get(k)


def _axes_size(mesh, phys):
    if phys is None:
        return 1
    if isinstance(phys, tuple):
        n = 1
        for a in phys:
            n *= mesh.shape[a]
        return n
    return mesh.shape[phys]


def cache_specs(cfg: ModelConfig, batch: int, seq: int, mesh, rules):
    """ShapeDtypeStructs + shardings for the KV/SSM cache.  Batch over dp
    when divisible; kv-sequence over sp (context sharding) otherwise;
    kv-heads over tp when divisible."""
    lm = LM(cfg)
    structs = jax.eval_shape(lambda: lm.init_cache(batch, seq))

    def spec_for(path_shape):
        shape = path_shape
        # heuristics by rank: [B, S, G, hd] kv / [B, W, C] conv /
        # [B, H, P, N] ssm state / [B, S_enc, G, hd] cross
        if len(shape) == 4 and shape[1] == seq:
            return P(_r(rules, "dp"), _r(rules, "sp"), _r(rules, "tp"),
                     None)
        if len(shape) == 4:                       # ssm state [B,H,P,N]
            return P(_r(rules, "dp"), _r(rules, "tp"), None, None)
        if len(shape) == 3:                       # conv cache
            return P(_r(rules, "dp"), None, _r(rules, "tp"))
        return P(_r(rules, "dp"))

    def one(leaf):
        # stacked period caches have a leading periods dim
        shape = leaf.shape
        if len(shape) == 5:
            inner = spec_for(shape[1:])
            pspec = P(None, *tuple(inner))
        else:
            pspec = spec_for(shape)
        pspec = sanitize_spec(pspec, shape, mesh)
        return NamedSharding(mesh, pspec)

    shardings = jax.tree.map(one, structs)
    return structs, shardings


# ---------------------------------------------------------------------------
# dp/tp (+FSDP=ZeRO-3) train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, shape: ShapeConfig,
                    plan: ParallelPlan, ocfg: OptimizerConfig, mesh, rules):
    """Returns (step_fn, example_args_structs, in_shardings,
    out_shardings).  step(params, opt_state, batch) -> (params, opt_state,
    metrics); grad accumulation over microbatches with Chronos-Recomp
    remat; ZeRO via sharding specs (stage 3 = params keep fsdp; stage 1/2
    = params replicated over dp, states fsdp-sharded)."""
    lm = LM(cfg)
    params_s = jax.eval_shape(lambda: lm.init(jax.random.key(0))[0])
    logical = _specs_only(cfg)

    p_logical = logical if plan.zero_stage >= 3 else drop_fsdp(logical)
    s_logical = zero_state_specs(logical, max(plan.zero_stage, 1))

    p_shard = resolve_shardings(params_s, p_logical, mesh, rules)
    opt_s = jax.eval_shape(adamw_init, params_s)
    o_shard = {
        "step": NamedSharding(mesh, P()),
        "mu": resolve_shardings(opt_s["mu"], s_logical, mesh, rules),
        "nu": resolve_shardings(opt_s["nu"], s_logical, mesh, rules),
        "master": resolve_shardings(opt_s["master"], s_logical, mesh,
                                    rules),
    }
    batch_s, b_shard, m, mbg = train_batch_specs(cfg, shape, plan, mesh,
                                                 rules)
    # grad-accumulation buffers live with the ZeRO state sharding; an
    # unconstrained carry would be replicated (= params-fp32 per device)
    g_shard = resolve_shardings(opt_s["mu"], s_logical, mesh, rules)
    g_pspecs = jax.tree.map(lambda s: s.spec, g_shard)

    def step(params, opt_state, batch):
        with shard_env(mesh, rules):
            def pin(g):
                return jax.tree.map(
                    lambda a, sp: jax.lax.with_sharding_constraint(a, sp),
                    g, g_pspecs)

            def mb_loss(p, mb):
                loss, metrics = lm.loss(p, mb, recomp=plan.recompute,
                                        num_chunks=plan.num_chunks)
                return loss, metrics

            def acc(carry, i):
                gsum, lsum = carry
                mb = jax.tree.map(lambda a: a[i], batch)
                (l, _), g = jax.value_and_grad(mb_loss,
                                               has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g)
                return (pin(gsum), lsum + l), None

            g0 = pin(jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params))
            (grads, loss), _ = jax.lax.scan(acc, (g0, 0.0), jnp.arange(m))
            grads = jax.tree.map(lambda g: g / m, grads)
            master, opt_state, om = adamw_update(grads, opt_state, ocfg)
            params = cast_like(master, params)
            metrics = {"loss": loss / m, **om}
            return params, opt_state, metrics

    in_shardings = (p_shard, o_shard, b_shard)
    out_shardings = (p_shard, o_shard,
                     jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                  {"loss": 0, "grad_norm": 0, "lr": 0}))
    return step, (params_s, opt_s, batch_s), in_shardings, out_shardings


def _specs_only(cfg: ModelConfig):
    """Logical specs without full param materialization (init traced via
    eval_shape; specs are produced alongside, shapes discarded)."""
    lm = LM(cfg)
    holder = {}

    def grab():
        p, s = lm.init(jax.random.key(0))
        holder["s"] = s
        return p

    jax.eval_shape(grab)
    return holder["s"]


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def make_serve_steps(cfg: ModelConfig, shape: ShapeConfig, mesh, rules):
    """Returns dict with 'prefill' and/or 'decode':
    (fn, arg_structs, in_shardings, out_shardings)."""
    lm = LM(cfg)
    params_s = jax.eval_shape(lambda: lm.init(jax.random.key(0))[0])
    logical = _specs_only(cfg)
    p_shard = resolve_shardings(params_s, logical, mesh, rules)
    B = shape.global_batch
    S = shape.seq_len
    # VLM prefill writes patch-prefix + text positions into the cache
    n_prefix = cfg.vision.num_patches if cfg.vision is not None else 0
    cache_s, cache_sh = cache_specs(cfg, B, S + n_prefix, mesh, rules)
    dp_spec = P(_r(rules, "dp"))
    out = {}

    extra_s: Dict[str, Any] = {}
    extra_sh: Dict[str, Any] = {}
    if cfg.vision is not None:
        s = (B, cfg.vision.num_patches, cfg.d_model)
        extra_s["patch_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        extra_sh["patch_embeds"] = NamedSharding(
            mesh, sanitize_spec(P(_r(rules, "dp")), s, mesh))
    if cfg.encdec is not None:
        s = (B, cfg.encdec.num_frames, cfg.d_model)
        extra_s["frame_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        extra_sh["frame_embeds"] = NamedSharding(
            mesh, sanitize_spec(P(_r(rules, "dp")), s, mesh))

    if shape.kind == "prefill":
        tok_s = jax.ShapeDtypeStruct((B, S), jnp.int32)
        tok_sh = NamedSharding(mesh, sanitize_spec(dp_spec, (B, S), mesh))

        def prefill(params, tokens, cache, extra):
            with shard_env(mesh, rules):
                logits, cache = lm.prefill(params, tokens, cache, **extra)
                return logits, cache

        out["prefill"] = (
            prefill, (params_s, tok_s, cache_s, extra_s),
            (p_shard, tok_sh, cache_sh, extra_sh),
            (NamedSharding(mesh, sanitize_spec(
                dp_spec, (B, cfg.vocab_size), mesh)), cache_sh))
    else:
        tok_s = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        tok_sh = NamedSharding(mesh, sanitize_spec(dp_spec, (B, 1), mesh))

        def decode(params, tokens, cache, extra):
            with shard_env(mesh, rules):
                # decode at the last cache position (cache pre-filled)
                logits, cache = lm.decode_step(params, tokens, cache,
                                               S - 1, **extra)
                return logits, cache

        out["decode"] = (
            decode, (params_s, tok_s, cache_s, extra_s),
            (p_shard, tok_sh, cache_sh, extra_sh),
            (NamedSharding(mesh, sanitize_spec(
                dp_spec, (B, cfg.vocab_size), mesh)), cache_sh))
    return out


def make_pipelined_serve_steps(cfg: ModelConfig, mesh, rules, lm_params,
                               *, chunk: int, max_seq: int,
                               n_slots: Optional[int] = None,
                               kernels: str = "xla"):
    """Pipelined serving on the production mesh: the model splits over
    ``rules['pp']`` stages (the "pod" axis in the multi-pod mesh — same
    placement as pipelined training: PP tolerates the thin inter-pod
    links) and requests stream through as seq-chunked prefill waves +
    steady-tick decode with continuous batching.

    Returns the constructed :class:`repro.serve.PipelinedEngine`; drive
    it with ``engine.serve(requests)`` (its per-tick step is jitted
    internally against ``mesh``).  ``lm_params`` are single-host
    ``LM.init`` parameters — the engine packs them into per-stage
    blocks, so serving and training checkpoints share one layout."""
    from repro.serve.engine import PipelinedEngine
    pp_axis = rules["pp"]
    return PipelinedEngine(cfg, lm_params, P=mesh.shape[pp_axis],
                           chunk=chunk, max_seq=max_seq, n_slots=n_slots,
                           mesh=mesh, axis=pp_axis, kernels=kernels)


# ---------------------------------------------------------------------------
# pipeline (multi-pod) train step
# ---------------------------------------------------------------------------

VSHAPE_SCHEDULES = ("v_min", "v_half", "v_zb")


def plan_schedule_kwargs(plan: ParallelPlan) -> Dict[str, Any]:
    """ParallelPlan -> schedule-generator kwargs beyond (P, m, v).

    ``chronos_recomp`` is driven by the plan's :class:`RecomputeConfig`
    (the ``num_recomp_chunks`` shallowest chunks replay, emitted as
    explicit ``R`` tasks); ``1f1b``/``gpipe`` take the uniform-recompute
    fraction (1F1B+R baseline); ``chronos_seq`` composes recompute with
    sequence chunking (``plan.seq_chunks`` rides separately through
    ``make_pipeline_spec(n_seq=...)``); the V-shape family
    (:data:`VSHAPE_SCHEDULES`) is a fixed v=2 construction carrying its
    own placement — the layer->device assignment then comes from the
    schedule's ``Placement`` (see ``StageLayout``), not the implicit
    interleaved stripe; other generators need nothing extra."""
    rc = plan.recompute
    if (plan.schedule == "chronos_recomp" and rc.mode != "none") or \
            (plan.schedule == "chronos_seq" and rc.mode == "chronos"
             and rc.num_recomp_chunks > 0):
        return {"recomp_chunks": min(rc.num_recomp_chunks,
                                     max(plan.num_chunks - 1, 1))}
    if plan.schedule in ("1f1b", "gpipe") and rc.mode == "uniform" \
            and rc.uniform_frac > 0:
        return {"recomp": rc.uniform_frac}
    return {}


def make_pipeline_train_step(cfg: ModelConfig, shape: ShapeConfig,
                             plan: ParallelPlan, ocfg: OptimizerConfig,
                             mesh, rules, extras: Optional[Dict] = None,
                             executor: Optional[str] = None):
    """ChronosPipe train step with pp mapped onto rules['pp'] (the "pod"
    axis in the production multi-pod mesh).  Returns the same 4-tuple as
    make_train_step.

    Chronos-Offload (``plan.offload.enabled``): the device optimizer
    state covers only the *shallow* chunks plus the shared params; the
    step then returns a 4-tuple ``(params, opt_state, metrics,
    deep_grads)`` where ``deep_grads`` are the gradients of the
    ``plan.offload.num_offload_chunks`` deepest chunks — the caller
    (``repro.launch.train.train``) submits them to a
    :class:`~repro.optim.offload.ChronosOffloadRunner`, whose host-side
    AdamW overlaps the pipeline's cooldown/warm-up bubbles, and uploads
    the refreshed bf16 deep weights before the next step's deep forward
    (Eq. (5)/(7) windows of the paper).  Pass ``extras`` (a dict) to
    receive the built ``PipelineSpec`` under ``extras["spec"]``.

    ``executor`` selects the compiled executor form ("phase", the
    default, or "legacy" — see
    :func:`repro.core.pipeline_runtime.make_train_grads_fn`).
    """
    import os
    from repro.core.pipeline_runtime import (EXECUTOR_ENV,
                                             init_pipeline_params,
                                             make_pipeline_spec,
                                             make_train_grads_fn,
                                             make_train_update_fn)
    from repro.optim import merge_deep_shallow, split_deep_shallow
    pp_axis = rules["pp"]
    P_ = mesh.shape[pp_axis]
    dp = _axes_size(mesh, rules.get("dp"))
    mbg = plan.microbatch_size * dp
    # plan.num_microbatches pins m explicitly — elastic restarts re-plan
    # at a different P but must keep the microbatch decomposition (and
    # hence the per-step global batch / loss trajectory) identical
    m = plan.num_microbatches or max(2, shape.global_batch // mbg)

    if plan.schedule in VSHAPE_SCHEDULES:
        assert plan.num_chunks == 2, \
            f"{plan.schedule} is a fixed v=2 V-shape construction, " \
            f"got num_chunks={plan.num_chunks}"
    psum_bits = {"none": None, "int8_ef": 8, "int16_ef": 16}[
        plan.grad_compression]
    if psum_bits and (plan.seq_chunks > 1 or plan.kernels == "fused"):
        raise ValueError(
            "grad_compression composes with the grads-fn pipeline step "
            "only (not seq-chunked or in-executor fused-AdamW runs)")
    spec = make_pipeline_spec(
        cfg, P=P_, v=plan.num_chunks, m=m, microbatch=mbg,
        seq_len=shape.seq_len, schedule=plan.schedule, pp_axis=pp_axis,
        n_seq=plan.seq_chunks, kernels=plan.kernels, wire=plan.wire,
        grad_psum_bits=psum_bits, **plan_schedule_kwargs(plan))
    _log.info("pipeline %s P=%d v=%d m=%d: %s wire, %d ticks",
              plan.schedule, P_, plan.num_chunks, m,
              "double-buffered" if spec.table.overlap else "synchronous",
              spec.table.T)
    if extras is not None:
        extras["spec"] = spec
    offload = plan.offload.enabled and plan.offload.num_offload_chunks > 0
    n_off = plan.offload.num_offload_chunks
    if offload:
        assert n_off < plan.num_chunks, \
            "offload must leave at least one shallow chunk on device"

    holder = {}

    def grab():
        p, s = init_pipeline_params(jax.random.key(0), cfg, spec.layout)
        holder["s"] = s
        return p

    params_s = jax.eval_shape(grab)
    logical = holder["s"]
    # XLA's SPMD partitioner CHECK-fails (spmd_partitioner_util.cc:504)
    # when pp-replicated operands enter the manual-over-pod region with an
    # fsdp("data") sharding, so shared params (embed/head/norm/encoder)
    # and their optimizer states shard over "model" only; block params
    # keep full FSDP x TP.
    logical = {k: (v if k == "blocks" else drop_fsdp(v))
               for k, v in logical.items()}
    # pipeline block leaves already carry the "pp" logical axis first
    p_shard = resolve_shardings(params_s, logical, mesh,
                                {**rules, "pp": pp_axis})
    vch = plan.num_chunks

    def _shallow_of(ptree):
        """Device-optimizer subset: shallow chunks + shared params (the
        deep chunks' master/momenta live on the host under offload)."""
        return {"blocks": split_deep_shallow(ptree["blocks"], vch,
                                             n_off)[0],
                **{k: ptree[k] for k in ptree if k != "blocks"}}

    opt_params_s = jax.eval_shape(_shallow_of, params_s) if offload \
        else params_s
    opt_s = jax.eval_shape(adamw_init, opt_params_s)
    s_logical = zero_state_specs(logical, max(plan.zero_stage, 1))
    s_logical = {k: (v if k == "blocks" else drop_fsdp(logical[k]))
                 for k, v in s_logical.items()}
    o_shard = {
        "step": NamedSharding(mesh, P()),
        "mu": resolve_shardings(opt_s["mu"], s_logical, mesh,
                                {**rules, "pp": pp_axis}),
        "nu": resolve_shardings(opt_s["nu"], s_logical, mesh,
                                {**rules, "pp": pp_axis}),
        "master": resolve_shardings(opt_s["master"], s_logical, mesh,
                                    {**rules, "pp": pp_axis}),
    }
    structs = {"tokens": jax.ShapeDtypeStruct((m, mbg, shape.seq_len),
                                              jnp.int32)}
    b_shard = {"tokens": NamedSharding(mesh, sanitize_spec(
        P(None, _r(rules, "dp")), (m, mbg, shape.seq_len), mesh))}
    if cfg.vision is not None:
        s = (m, mbg, cfg.vision.num_patches, cfg.d_model)
        structs["patch_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        b_shard["patch_embeds"] = NamedSharding(
            mesh, sanitize_spec(P(None, _r(rules, "dp")), s, mesh))
    if cfg.encdec is not None:
        s = (m, mbg, cfg.encdec.num_frames, cfg.d_model)
        structs["frame_embeds"] = jax.ShapeDtypeStruct(s, jnp.float32)
        b_shard["frame_embeds"] = NamedSharding(
            mesh, sanitize_spec(P(None, _r(rules, "dp")), s, mesh))

    # In-executor fused optimizer: split-backward schedules under the
    # fused compute backend run the AdamW step inside the pipeline
    # executor (kernels/fused_adamw after the tick scan) — no separate
    # optimizer phase.  Offload and sequence-chunked specs keep the
    # phase-separate update (their optimizer is structurally split).
    exe = executor if executor is not None else \
        os.environ.get(EXECUTOR_ENV, "phase")
    fuse_opt = (plan.kernels == "fused" and spec.table is not None
                and spec.table.has_w and not offload
                and plan.seq_chunks == 1 and exe == "phase")
    if fuse_opt:
        update_fn = make_train_update_fn(spec, mesh, ocfg, m,
                                         executor=exe)

        def step(params, opt_state, batch):
            with shard_env(mesh, rules):
                return update_fn(params, opt_state, batch)

        metric_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                 {"loss": 0, "n_microbatches": 0,
                                  "grad_norm": 0, "lr": 0})
        return (step, (params_s, opt_s, structs),
                (p_shard, o_shard, b_shard), (p_shard, o_shard, metric_sh))

    grads_fn = make_train_grads_fn(spec, mesh, executor=executor)

    def ship_deep(g_deep):
        """Deep-chunk gradients ride the host PCIe link quantized to the
        plan's grad_compression width (symmetric per-leaf scale; the
        one-shot shipment carries no error feedback — that belongs to
        the *repeated* shared-grad psum).  fp32 when uncompressed."""
        if not psum_bits:
            return g_deep
        from repro.optim.compression import quantize_int8
        if psum_bits > 8:             # int16 shipment
            def q16(g):
                g = g.astype(jnp.float32)
                s = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / 32767.0
                return (jnp.clip(jnp.round(g / s), -32767,
                                 32767).astype(jnp.int16), s)
            return jax.tree.map(q16, g_deep)
        return jax.tree.map(
            lambda g: quantize_int8(g.astype(jnp.float32)), g_deep)

    def step(params, opt_state, batch, psum_ef=None):
        with shard_env(mesh, rules):
            if psum_bits:
                grads, metrics, new_ef = grads_fn(params, batch, psum_ef)
            else:
                grads, metrics = grads_fn(params, batch)
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / m, grads)
            if not offload:
                with jax.named_scope("optimizer"):
                    master, opt_state, om = adamw_update(grads, opt_state,
                                                         ocfg)
                    params = cast_like(master, params)
                out = (params, opt_state, {**metrics, **om})
                return out + ((new_ef,) if psum_bits else ())
            # Chronos-Offload: device AdamW updates shallow chunks +
            # shared params; the deep chunks' gradients ship to the host
            # optimizer (caller drives the submit/collect overlap).
            g_shallow, g_deep = split_deep_shallow(grads["blocks"], vch,
                                                   n_off)
            g_dev = {"blocks": g_shallow,
                     **{k: grads[k] for k in grads if k != "blocks"}}
            master, opt_state, om = adamw_update(g_dev, opt_state, ocfg)
            p_shallow, p_deep = split_deep_shallow(params["blocks"], vch,
                                                   n_off)
            with jax.named_scope("optimizer"):
                new_shallow = cast_like(master["blocks"], p_shallow)
                shared_new = {k: cast_like(master[k], params[k])
                              for k in master if k != "blocks"}
            params = {"blocks": merge_deep_shallow(new_shallow, p_deep),
                      **shared_new}
            out = (params, opt_state, {**metrics, **om},
                   ship_deep(g_deep))
            return out + ((new_ef,) if psum_bits else ())

    metric_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                             {"loss": 0, "n_microbatches": 0,
                              "grad_norm": 0, "lr": 0})
    in_shardings = (p_shard, o_shard, b_shard)
    arg_structs = (params_s, opt_s, structs)
    out_shardings = [p_shard, o_shard, metric_sh]
    if offload:
        deep_s = jax.eval_shape(
            lambda p: split_deep_shallow(p["blocks"], vch, n_off)[1],
            params_s)
        deep_shard = resolve_shardings(deep_s, logical["blocks"], mesh,
                                       {**rules, "pp": pp_axis})
        if psum_bits:
            deep_shard = jax.tree.map(
                lambda s: (s, NamedSharding(mesh, P())), deep_shard)
        out_shardings.append(deep_shard)
    if psum_bits:
        from repro.core.pipeline_runtime import init_psum_ef
        ef_s = jax.eval_shape(
            functools.partial(init_psum_ef, spec), params_s)
        ef_shard = jax.tree.map(
            lambda s: NamedSharding(mesh, sanitize_spec(
                P(pp_axis), s.shape, mesh)), ef_s)
        arg_structs = arg_structs + (ef_s,)
        in_shardings = in_shardings + (ef_shard,)
        out_shardings.append(ef_shard)
    return step, arg_structs, in_shardings, tuple(out_shardings)
