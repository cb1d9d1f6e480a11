"""Production training driver.

Wires together: model zoo + Chronos-Recomp remat, data pipeline
(prefetching, checkpointable), AdamW (+ optional fused-kernel update and
Chronos-Offload host optimizer for deep chunks), checkpoint/restart
(async, atomic), health monitoring (straggler/watchdog), and elastic
re-planning hooks.

Single-host entry point; on a real cluster each host runs this under
jax.distributed with the same logic.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.data import DataPipeline, SyntheticLM
from repro.ft import Action, Checkpointer, HealthMonitor
from repro.ft.inject import DeviceLossError
from repro.launch.steps import (make_pipeline_train_step, make_train_step,
                                resolve_shardings, _specs_only)
from repro.models import LM
from repro.models.sharding import shard_env
from repro.optim import (ChronosOffloadRunner, adamw_init, adamw_update,
                         cast_like, split_deep_shallow, merge_deep_shallow)


def train(tc: TrainConfig, *, mesh=None, rules: Optional[Dict] = None,
          steps: Optional[int] = None,
          data_source=None, log: Callable[[str], None] = print):
    """Returns final metrics dict.  Restores from tc.checkpoint_dir if a
    checkpoint exists (crash recovery / elastic restart).

    When ``tc.plan.pp_axis`` is set the run dispatches to
    :func:`train_pipeline` — the ChronosPipe SPMD executor with optional
    Chronos-Offload host optimizer for the deepest chunks."""
    cfg, shape, plan, ocfg = tc.model, tc.shape, tc.plan, tc.optimizer
    if plan.pp_axis is not None:
        return train_pipeline(tc, mesh=mesh, rules=rules, steps=steps,
                              data_source=data_source, log=log)
    steps = steps or ocfg.total_steps
    from repro.models.sharding import make_mesh
    mesh = mesh or make_mesh((jax.device_count(),), ("data",))
    rules = rules if rules is not None else {"dp": "data", "fsdp": "data",
                                             "tp": None}

    lm = LM(cfg)
    mesh_ctx = jax.sharding.set_mesh(mesh)
    mesh_ctx.__enter__()
    with shard_env(mesh, rules):
        params, _ = lm.init(jax.random.key(tc.seed))
    opt_state = adamw_init(params)

    dp = mesh.shape.get("data", 1) if hasattr(mesh.shape, "get") else 1
    mbg = plan.microbatch_size * max(
        mesh.shape["data"] if "data" in mesh.axis_names else 1, 1)
    m = max(1, shape.global_batch // mbg)

    source = data_source or SyntheticLM(cfg.vocab_size, shape.seq_len,
                                        seed=tc.seed)
    pipe = DataPipeline(source, global_batch=mbg * m, microbatches=m,
                        prefetch=2).start()
    ck = Checkpointer(tc.checkpoint_dir, keep=tc.keep_checkpoints)
    monitor = HealthMonitor()

    start_step = 0
    latest = ck.latest_step()
    if latest is not None:
        restored, extra = ck.restore({"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        if "data" in extra:
            pipe.load_state(extra["data"])
        start_step = int(extra.get("step", latest))
        log(f"[train] restored checkpoint step {start_step}")

    def step_fn(params, opt_state, batch):
        with shard_env(mesh, rules):
            def mb_loss(p, mb):
                return lm.loss(p, mb, recomp=plan.recompute,
                               num_chunks=plan.num_chunks)[0]

            def acc(carry, i):
                gsum, lsum = carry
                mb = jax.tree.map(lambda a: a[i], batch)
                l, g = jax.value_and_grad(mb_loss)(params, mb)
                return (jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                     gsum, g), lsum + l), None

            g0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              params)
            (grads, loss), _ = jax.lax.scan(acc, (g0, 0.0),
                                            jnp.arange(m))
            grads = jax.tree.map(lambda g: g / m, grads)
            master, opt_state, om = adamw_update(grads, opt_state, ocfg)
            params = cast_like(master, params)
            return params, opt_state, {"loss": loss / m, **om}

    # NOTE: params and opt master alias when param_dtype == fp32 (cast is
    # a no-op), so donation would double-donate; donate nothing here.
    jit_step = jax.jit(step_fn)

    losses = []
    next_step = start_step
    t_start = time.time()
    for step in range(start_step, steps):
        t0 = time.time()
        batch = pipe.next()
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        next_step = step + 1
        dt = time.time() - t0
        action = monitor.record_step(dt)
        if step % tc.log_every == 0:
            log(f"[train] step {step} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} ({dt:.2f}s)")
        if action == Action.CHECKPOINT_NOW or (
                step and step % tc.checkpoint_every == 0):
            ck.save_async(step, {"params": params, "opt": opt_state},
                          extra={"step": step + 1,
                                 "data": pipe.state()})
        if action == Action.RESTART:
            log("[train] persistent straggler detected -> checkpoint + "
                "abort for elastic restart")
            break
    # final save at the step actually reached (an early RESTART abort
    # must not mislabel the checkpoint as having finished the run)
    ck.save(next_step, {"params": params, "opt": opt_state},
            extra={"step": next_step, "data": pipe.state()})
    pipe.stop()
    mesh_ctx.__exit__(None, None, None)
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "steps": len(losses),
            "wall_s": time.time() - t_start,
            "median_step_s": monitor.median_step}


def train_pipeline(tc: TrainConfig, *, mesh,
                   rules: Optional[Dict] = None,
                   steps: Optional[int] = None, data_source=None,
                   injector=None, watchdog=None,
                   log: Callable[[str], None] = print):
    """ChronosPipe training driver: the SPMD pipeline executor with
    optional Chronos-Offload (§5.1) for the deepest chunks.

    Fault-tolerance seams (``repro.ft``): every checkpoint records the
    pipeline layout (P, v, schedule, placement) so an elastic restart
    at a different device count can live-migrate the state
    (``remap_blocks_elastic``); ``injector`` (a
    :class:`repro.ft.inject.FaultInjector`) drives deterministic
    device-loss / hang / checkpoint-crash / straggler events through
    the loop, and ``watchdog`` (a :class:`repro.ft.health.Watchdog`)
    is armed around each step — a trip converts a hung collective into
    a :class:`~repro.ft.inject.DeviceLossError` the elastic driver
    recovers from.  The returned dict carries ``status`` ("complete" |
    "restart" | "preempted"), per-step losses (``loss_by_step``), and
    the first-step latency (``first_step_s``, the resume cost).

    Offload flow (double-buffered across step boundaries): the jitted
    step updates shallow chunks + shared params on device and returns
    the deep chunks' gradients; ``runner.submit`` copies them to the
    host (the paper's PCIe-down during the cooldown bubble) and kicks a
    background AdamW, which overlaps checkpointing / logging / the next
    batch fetch; ``runner.collect`` at the top of the next iteration
    uploads the refreshed bf16 deep weights before the deep chunks'
    forward needs them (Eq. (7) warm-up window).  The returned metrics
    carry an ``offload`` report validating the measured overlap against
    :class:`repro.core.analysis.OffloadTiming` Eqs. (5)/(7).

    Host master weights/momenta are rebuilt from the checkpointed params
    on restart (device opt state is checkpointed; host momenta are not).
    """
    cfg, shape, plan, ocfg = tc.model, tc.shape, tc.plan, tc.optimizer
    steps = steps or ocfg.total_steps
    from repro.core.pipeline_runtime import (init_pipeline_params,
                                             init_psum_ef)
    assert mesh is not None and plan.pp_axis in mesh.axis_names, \
        "train_pipeline needs a mesh carrying plan.pp_axis"
    rules = dict(rules) if rules is not None else {"dp": None, "tp": None,
                                                   "fsdp": None}
    rules["pp"] = plan.pp_axis

    extras: Dict = {}
    step_fn, arg_structs, in_sh, out_sh = \
        make_pipeline_train_step(cfg, shape, plan, ocfg, mesh, rules,
                                 extras=extras)
    structs = arg_structs[2]        # (params, opt, batch[, psum_ef])
    spec = extras["spec"]
    m, mbg = structs["tokens"].shape[:2]
    v = plan.num_chunks
    n_off = plan.offload.num_offload_chunks
    offload = plan.offload.enabled and n_off > 0

    mesh_ctx = jax.sharding.set_mesh(mesh)
    mesh_ctx.__enter__()
    with shard_env(mesh, rules):
        params, _ = init_pipeline_params(jax.random.key(tc.seed), cfg,
                                         spec.layout)
    # Compressed shared-grad psum (plan.grad_compression): the per-device
    # error-feedback residual is driver-held state threaded through every
    # step.  It is NOT checkpointed — a restart re-zeros it, which costs
    # one step of quantization error (bounded by the wire grid) and keeps
    # checkpoints layout-portable across compression settings.
    psum_bits = spec.grad_psum_bits
    psum_ef = init_psum_ef(spec, params) if psum_bits else None

    if offload:
        shallow0, deep0 = split_deep_shallow(params["blocks"], v, n_off)
        opt_state = adamw_init(
            {"blocks": shallow0,
             **{k: params[k] for k in params if k != "blocks"}})
        runner = ChronosOffloadRunner(deep0, ocfg)
    else:
        opt_state = adamw_init(params)
        runner = None

    source = data_source or SyntheticLM(cfg.vocab_size, shape.seq_len,
                                        seed=tc.seed)
    pipe = DataPipeline(source, global_batch=mbg * m, microbatches=m,
                        prefetch=2).start()
    ck = Checkpointer(tc.checkpoint_dir, keep=tc.keep_checkpoints)
    monitor = HealthMonitor()

    start_step = 0
    latest = ck.latest_step()
    if latest is not None:
        meta = ck.read_extra(latest).get("layout")
        if meta is not None and (meta["P"], meta["v"]) != (spec.table.P,
                                                          plan.num_chunks):
            raise RuntimeError(
                f"checkpoint step {latest} was written under layout "
                f"P={meta['P']} v={meta['v']} but this run uses "
                f"P={spec.table.P} v={plan.num_chunks}; migrate it "
                "first (repro.ft.elastic_pipeline.migrate_checkpoint)")
        restored, extra = ck.restore({"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        if "data" in extra:
            pipe.load_state(extra["data"])
        start_step = int(extra.get("step", latest))
        if runner is not None:
            runner = ChronosOffloadRunner(
                split_deep_shallow(params["blocks"], v, n_off)[1], ocfg)
        log(f"[train-pp] restored checkpoint step {start_step}")

    # the step's in_shardings (stage blocks split over pp) are checked
    # against committed arguments: place the fresh or restored state
    params = jax.device_put(params, in_sh[0])
    opt_state = jax.device_put(opt_state, in_sh[1])
    jit_step = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh)

    layout_meta = {"P": spec.table.P, "v": v, "schedule": plan.schedule,
                   "placement": getattr(spec.table, "placement_name",
                                        "interleaved")}

    def save_ckpt(save_step, next_step_, params_, opt_, *, sync=False):
        """Checkpoint with the layout stamped into ``extra`` and a
        synchronous durable retry when the (possibly fault-injected)
        writer dies — LATEST keeps resolving to a complete step."""
        if injector is not None:
            injector.arm_checkpoint_crash(save_step)
        tree = {"params": params_, "opt": opt_}
        extra = {"step": next_step_, "data": pipe.state(),
                 "layout": layout_meta}
        with jax.profiler.TraceAnnotation("checkpoint"):
            try:
                (ck.save if sync else ck.save_async)(save_step, tree,
                                                     extra=extra)
            except Exception as e:               # noqa: BLE001
                log(f"[train-pp] checkpoint write died ({e!r}) -> "
                    "synchronous retry")
                ck.save(save_step, tree, extra=extra)

    def fold_pending(params_):
        with jax.profiler.TraceAnnotation("offload_collect"):
            new_deep = runner.collect()       # bf16 upload (warm-up win)
            shallow, _ = split_deep_shallow(params_["blocks"], v, n_off)
            return jax.device_put(
                {**params_,
                 "blocks": merge_deep_shallow(shallow, new_deep)},
                in_sh[0])

    if latest is None:
        # durable step-0 snapshot: a failure before the first periodic
        # checkpoint then restores + migrates like any other (a cross-P
        # re-init would be a *different* network — per-position RNG
        # folding — and break step-count-exact recovery)
        save_ckpt(0, 0, params, opt_state, sync=True)

    losses = []
    loss_by_step = {}
    status = "complete"
    next_step = start_step
    first_step_s = None
    pending = False
    collect_wait_s = 0.0
    t_start = time.time()
    try:
        for step in range(start_step, steps):
            if injector is not None and injector.should_yield(step):
                # a lost device rejoined: publish a clean checkpoint and
                # hand control back for the warm scale-up restart
                if pending:
                    params, pending = fold_pending(params), False
                save_ckpt(step, step, params, opt_state, sync=True)
                status = "preempted"
                break
            if injector is not None:
                injector.on_step_start(step)
            with jax.profiler.StepTraceAnnotation("train",
                                                  step_num=step):
                t0 = time.time()
                with jax.profiler.TraceAnnotation("input"):
                    batch = {k: jnp.asarray(b)
                             for k, b in pipe.next().items()}
                if pending:
                    t_c = time.time()
                    params, pending = fold_pending(params), False
                    collect_wait_s += time.time() - t_c
                if watchdog is not None:
                    watchdog.arm()
                out = jit_step(params, opt_state, batch, psum_ef) \
                    if psum_bits else jit_step(params, opt_state, batch)
                if psum_bits:
                    *out, psum_ef = out
                if offload:
                    params, opt_state, metrics, deep_grads = out
                    if psum_bits:
                        # host shipment arrives quantized; the host AdamW
                        # wants fp32
                        from repro.optim.compression import dequantize_int8
                        deep_grads = jax.tree.map(
                            lambda t: dequantize_int8(*t), deep_grads,
                            is_leaf=lambda x: isinstance(x, tuple))
                    with jax.profiler.TraceAnnotation("offload_submit"):
                        runner.submit(deep_grads)  # grads down + host AdamW
                    pending = True
                else:
                    params, opt_state, metrics = out
                loss = float(metrics["loss"])     # blocks until step done
                if injector is not None:
                    injector.on_step_end(step, watchdog)
                if watchdog is not None:
                    if watchdog.check():
                        raise DeviceLossError(-1, "hung_collective", step)
                    watchdog.disarm()
                losses.append(loss)
                loss_by_step[step] = loss
                next_step = step + 1
                if first_step_s is None:
                    first_step_s = time.time() - t_start
                dt = time.time() - t0
            if injector is not None:
                dt = injector.step_time(step, dt)
            action = monitor.record_step(dt)
            if step % tc.log_every == 0:
                log(f"[train-pp] step {step} loss {loss:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"({dt:.2f}s)")
            if action == Action.CHECKPOINT_NOW or (
                    step and step % tc.checkpoint_every == 0):
                if pending:
                    # fold the in-flight host update in first —
                    # otherwise the checkpoint's deep chunks would be
                    # one step stale
                    params, pending = fold_pending(params), False
                save_ckpt(step, step + 1, params, opt_state)
            if action == Action.RESTART:
                log("[train-pp] persistent straggler -> checkpoint + "
                    "abort")
                status = "restart"
                break
    except BaseException as e:
        # device loss (real or injected) aborts the incarnation: stop
        # the prefetcher so it can't advance a shared source while the
        # elastic driver re-plans, then let the failure propagate —
        # carrying the completed steps' losses so the elastic driver
        # keeps the full trajectory
        if isinstance(e, DeviceLossError):
            e.loss_by_step = loss_by_step
            e.next_step = next_step
            e.first_step_s = first_step_s
        pipe.stop()
        mesh_ctx.__exit__(None, None, None)
        raise
    if pending:
        params = fold_pending(params)
    if status != "preempted":
        # final save at the step actually reached (a RESTART abort must
        # not mislabel the checkpoint as having finished the run)
        save_ckpt(next_step, next_step, params, opt_state, sync=True)
    pipe.stop()
    mesh_ctx.__exit__(None, None, None)

    tp = mesh.shape[rules["tp"]] if rules.get("tp") is not None else 1
    out = {"losses": losses, "loss_by_step": loss_by_step,
           "final_loss": losses[-1] if losses else None,
           "steps": len(losses), "start_step": start_step,
           "next_step": next_step, "status": status,
           "first_step_s": first_step_s,
           "wall_s": time.time() - t_start,
           "median_step_s": monitor.median_step,
           "schedule": spec.table.name}
    if offload:
        out["offload"] = offload_report(tc, spec, runner, tp=tp,
                                        collect_wait_s=collect_wait_s)
    return out


def offload_report(tc: TrainConfig, spec, runner, *, tp: int,
                   collect_wait_s: float) -> Dict:
    """Measured offload overlap vs the paper's Eq. (5)/(7) model."""
    from repro.core.analysis import offload_timing
    plan, shape = tc.plan, tc.shape
    P_ = spec.table.P
    ot = offload_timing(
        tc.model, seq_len=shape.seq_len, microbatch=spec.mbB,
        pp=P_, tp=tp, pcie_gbps=plan.offload.pcie_gbps,
        cpu_flops=plan.offload.cpu_flops,
        offload_frac=plan.offload.num_offload_chunks / plan.num_chunks)
    submits = max(int(runner.stats["submits"]), 1)
    return {
        "submits": int(runner.stats["submits"]),
        "overlapped": int(runner.stats["overlapped"]),
        "measured_overlap_frac": runner.stats["overlapped"] / submits,
        "collect_wait_s": collect_wait_s,
        "eq5_offload_ok": ot.offload_ok,
        "eq7_upload_ok": ot.upload_ok,
        "predicted_overlap_ratio": ot.overlap_ratio,
    }
