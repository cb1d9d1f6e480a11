"""One run of one training cell: set-up, the measured window, the check
against the reference, and the result line.

Set-up builds the compiled step and its state once, drives it through
the traffic's ``check_steps`` first steps (the readings the check
compares are taken there), and hands that same state to the window.
The window runs steps back to back, each ending in
``block_until_ready``, until ``seconds`` have passed; the next batch is
made on the host while the device runs the current step.  With
``trace`` the window runs under the profiler and the per-layer readers
in ``bench/metrics/`` turn it into metrics.
"""
from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

from bench import check, model as M, trace_reduce
from bench.synthetic import SyntheticLM

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
GIB = float(2 ** 30)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program, whatever the environment says."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def mem_stats(devices) -> dict:
    out = {}
    for d in devices:
        st = d.memory_stats() or {}
        out[d.id] = (st.get("bytes_in_use", 0), st.get("peak_bytes_in_use", 0))
    return out


class Ctx:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def check_steps(prog, m, traffic: dict, seed: int):
    """Parameters from the seed, then the traffic's first steps through
    the program's own call and feed, with the readings the check
    compares: ``(params, opt, data, readings)``."""
    params, opt = prog.init_state(seed)
    data = SyntheticLM(m.vocab, traffic["seq_len"] + 1, seed)
    rows = traffic["microbatches"] * traffic["microbatch_size"]
    losses, grad = [], None
    for i in range(traffic["check_steps"]):
        params, opt, met = prog.step(params, opt,
                                     prog.put_batch(data.next_batch(rows)))
        losses.append(float(met["loss"]))
        if i == 0:
            grad = prog.first_grad_norms(opt)
            sketch = prog.first_grad_sketch(opt, seed)
    mine = {"losses": losses, "grad": grad, "grad_sketch": sketch,
            "change": prog.change_norms(opt, seed)}
    return params, opt, data, mine


def run_cell(cell: dict, cfg: dict, traffic: dict, *, seed: int,
             seconds: float, trace: bool, devices, limits: dict,
             e2e: list, per_layer: list, t_start: float,
             build=None) -> dict:
    """Returns the result object.  ``build`` makes the program's step
    (``bench.program.Program`` unless a test plants a fault)."""
    import jax

    from bench import flops, reference
    from bench.program import Program
    m = M.from_config(cfg)
    tokens_per_step = (traffic["microbatches"] * traffic["microbatch_size"] *
                       traffic["seq_len"])
    prog = (build or Program)(m, traffic, devices)
    params, opt, data, mine = check_steps(prog, m, traffic, seed)
    losses = mine["losses"]
    rows = traffic["microbatches"] * traffic["microbatch_size"]
    batch = prog.put_batch(data.next_batch(rows))
    jax.block_until_ready((params, opt, batch))
    before = mem_stats(devices)
    setup_s = time.monotonic() - t_start
    log(f"setup {setup_s:.3f} s; check-step losses {losses}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    steps = 0
    with _span("window"):
        t0 = time.perf_counter()
        while True:
            with _span("dispatch"):
                params, opt, met = prog.step(params, opt, batch)
            with _span("input"):
                batch = prog.put_batch(data.next_batch(rows))
            with _span("wait"):
                jax.block_until_ready((params, opt, met))
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    tok_s = steps * tokens_per_step / wall
    after = mem_stats(devices)
    temp = prog.memory.temp_size_in_bytes
    peak_counter = max(p for _, p in after.values())
    peak_sum = max(b for b, _ in before.values()) + temp
    log(f"window {steps} steps in {wall:.4f} s; last loss "
        f"{float(met['loss']):.6f}")
    log(f"hbm readings: peak_bytes_in_use {peak_counter} B; bytes_in_use "
        f"before the window {max(b for b, _ in before.values())} B + step "
        f"temp_size {temp} B = {peak_sum} B")
    memory = prog.memory
    spec = prog.spec
    hlo_text = prog.compiled.as_text() if trace else ""
    del params, opt, met, batch, prog
    gc.collect()

    log(f"bytes_in_use before the reference: "
        f"{[b for b, _ in mem_stats(devices).values()]}")
    t_ref = time.monotonic()
    ref = reference.train(m, traffic, seed, devices, mode="f32")
    values = check.readings(mine, ref)
    correct, rows_checked = check.verdict(values, limits)
    log(f"readings (compared where the cell has a limit): {values}")
    log(f"reference {time.monotonic() - t_ref:.3f} s; losses {ref['losses']}")

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_counter}
    result = {"correct": correct, "attempted": steps, "failed": 0}
    if not trace:
        vals = {"train_tokens_per_s": (tok_s, "tokens/s"),
                "hbm_peak_gib": (max(peak_counter, peak_sum) / GIB, "GiB"),
                "setup_s": (setup_s, "s")}
        metrics = {e["name"]: {"value": vals[e["name"]][0],
                               "unit": e["unit"]}
                   for e in e2e if e["name"] in vals}
    else:
        red = trace_reduce.reduce(trace_reduce.load(tdir, hlo_text))
        del hlo_text
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = Ctx(model=m, traffic=traffic, cell=cell, memory=memory,
                  spec=spec, trace=red, train_tokens_per_s=tok_s,
                  steps=steps,
                  devices=devices, flops=flops,
                  peaks=flops.peaks(dev0.device_kind))
        metrics = {}
        for pm in per_layer:
            v = _reader(pm["name"]).read(ctx)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
        if red:
            busy = [d["busy_s"] for d in red["devices"].values()]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = red["window_s"]
            for dv, d in sorted(red["devices"].items()):
                log(f"trace tpu{dv}: busy {d['busy_s']:.6f} s of "
                    f"{red['window_s']:.6f} s; exposed collective "
                    f"{d['exposed_collective_s']:.6f} s; by class "
                    f"{json.dumps(d['class_s'])}")
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": _num(r["value"]), "limit": r["limit"]}
                        for k, r in rows_checked.items()}
    return result


def _num(v):
    return v if isinstance(v, float) and math.isfinite(v) else None
