"""Subprocess helper: the optimized HLO text of the phase executor's
training step, compiled on the CPU at a tiny size.

Builds the step through ``repro.launch.steps.make_pipeline_train_step``
(the path the benchmark drives: phase executor, then the gradient mean,
AdamW and the cast back outside the executor) on ``P`` host devices and
writes ``compiled.as_text()`` to ``out``.

Usage: python scope_hlo.py <schedule> <P> <out>
"""
import dataclasses
import os
import sys

schedule, P_, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P_}"

import jax  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.configs.base import (OptimizerConfig, ParallelPlan,  # noqa: E402
                                RecomputeConfig, ShapeConfig)
from repro.launch.steps import make_pipeline_train_step  # noqa: E402
from repro.models.sharding import make_mesh  # noqa: E402

v = 2
cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"),
                          num_layers=2 * P_ * v, param_dtype="bfloat16",
                          compute_dtype="bfloat16")
recompute = (RecomputeConfig(mode="chronos", num_recomp_chunks=1)
             if schedule == "chronos_recomp" else RecomputeConfig())
plan = ParallelPlan(dp_axes=(), tp_axis=None, pp_axis="pp",
                    schedule=schedule, num_chunks=v, num_microbatches=4,
                    microbatch_size=1, recompute=recompute)
mesh = make_mesh((P_,), ("pp",), devices=jax.devices()[:P_])
rules = {"pp": "pp", "dp": None, "tp": None, "fsdp": None}
fn, structs, in_sh, out_sh = make_pipeline_train_step(
    cfg, ShapeConfig("tiny", 17, 4, "train"), plan, OptimizerConfig(), mesh,
    rules)
compiled = jax.jit(fn, in_shardings=in_sh,
                   out_shardings=out_sh).lower(*structs).compile()
with open(out, "w") as f:
    f.write(compiled.as_text())
print("OK=1")
