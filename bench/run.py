"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's log on standard error, ending with each number that
decided ``correct`` beside its limit, and the result as one JSON object
on the last line of standard output.  Exits non-zero, with no result,
when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # only inside its checkout and its TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import check, harness, model
    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg = model.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    e2e = [e for e in bench["end_to_end"]
           if args.workload in e.get("workloads", [args.workload])]
    per_layer = [p for p in bench["per_layer"]
                 if args.workload in p.get("workloads", [args.workload])]

    harness.enable_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s): nothing was run",
              file=sys.stderr)
        return 3
    result = harness.run_cell(
        cell, cfg, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devs[:cell["chips"]],
        limits=check.load_limits(args.workload), e2e=e2e,
        per_layer=per_layer, t_start=T_START)
    for k, r in result["checks"].items():
        print(f"check {k}: {r['value']!r} limit {r['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
