"""The readings the check's limits are set from, on the chip, at a cell's
own size: for each seed the program's gaps to the float32 reference
(the lower reading), the control's (the reference one precision down,
float8 weights and operands), the planted fault of half the batch left
out, and with ``--noexchange`` the program with its boundary exchange
between chips left out.

    python bench/study.py --workload <cell> --seeds 11,12,13 [--modes fp8,half]

One process: the step compiles once and every seed reuses it.  Writes
``chiprun_out/study_<cell>.json`` and prints one line per seed.  The
benchmark's own runs do not run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="fp8,half")
    ap.add_argument("--noexchange", action="store_true",
                    help="also read the program with the boundary exchange "
                         "between chips left out (every ppermute a no-op)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # only inside its checkout and its TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import check, harness, model as M, reference
    from bench.program import Program
    harness.enable_cache()
    import jax
    devs = jax.devices()
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("no TPU, or too few chips", file=sys.stderr)
        return 3
    devs = devs[:cell["chips"]]
    m = M.from_config(M.load_config(cell["config"]))
    traffic = harness.load_traffic(cell["traffic"])
    prog = Program(m, traffic, devs)
    broken = None
    if args.noexchange:
        import repro.core.pipeline_runtime as PR
        os.environ["REPRO_EXCHANGE_AG_MAX"] = "0"
        PR._ppermute = lambda x, axis, perm: x
        broken = Program(m, traffic, devs)
    modes = [x for x in args.modes.split(",") if x]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        params, opt, _, mine = harness.check_steps(prog, m, traffic, seed)
        del params, opt
        gc.collect()
        t_prog = time.monotonic() - t
        ref = reference.train(m, traffic, seed, devs, mode="f32")
        t_ref = time.monotonic() - t - t_prog
        row = {"seed": seed, "program": check.readings(mine, ref),
               "losses": {"program": mine["losses"], "ref": ref["losses"]},
               "seconds": {"program": t_prog, "reference": t_ref}}
        for mode in modes:
            other = reference.train(m, traffic, seed, devs, mode=mode)
            row[mode] = check.readings(other, ref)
        if broken is not None:
            params, opt, _, bad = harness.check_steps(broken, m, traffic,
                                                      seed)
            del params, opt
            gc.collect()
            row["noexchange"] = check.readings(bad, ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"study_{args.workload}.json"),
              "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
