"""Device time by program scope: which part of the training step each
device op of a profiler trace belongs to.

The program names its phases with ``jax.named_scope`` (the list is in
``docs/ARCHITECTURE.md``, the SPMD executor); XLA keeps the name in each
instruction's ``metadata={op_name=...}``.  This module maps every op of
a trace's ``XLA Ops`` lines to one scope label through the optimized
HLO module of the traced program, and splits each device's busy time
among the labels:

- an op's instruction name is the ``%name`` of its trace line; its path
  is the ``op_name`` of that instruction, or, for a fusion without
  metadata, of its fused computation's root, else of the first
  instruction there that has one; an instruction with no metadata at
  all (XLA made it: a layout copy, a canonicalised dot, the ``done``
  half of an async pair) takes the scope of its first operand that has
  one;
- a path's scope is the innermost of ``PRIMARY`` on it, with two
  exceptions set by how JAX names a pullback: a path through
  ``rematted_computation`` (``jax.checkpoint`` re-running a forward
  inside the pullback) is ``replay``, and a path through ``transpose(``
  whose innermost scope is ``fwd`` or ``replay`` is ``bwd`` (JAX names
  the transposed ops after the forward they transpose);
- ``embed`` and ``head_loss`` are nested inside the phase scopes and
  label the op as ``<phase>/<nested>``;
- an op that none of this reaches is ``unscoped``.

Time is split so that each instant of a device's busy time counts once:
where ops overlap (an async collective under compute), the instant goes
to the op that started last.

    python bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs a cell's step through an untraced window and then a traced one,
and prints per device the seconds per scope, the ``unscoped`` seconds
and their top ops, the host threads' events in every device idle gap
over 20 ms, and the scope shares (``SHARES``) as one JSON line.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import heapq
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: the phase scopes: every op belongs to at most one
PRIMARY = ("fwd", "replay", "bwd", "ring", "grad_accum", "wire",
           "exchange", "grad_psum", "optimizer")
#: scopes nested inside a phase: they name a part of it
NESTED = ("embed", "head_loss")
UNSCOPED = "unscoped"
#: the scope shares a per-layer metric reads
SHARES = {"replay_share": ("replay",),
          "optimizer_share": ("optimizer",),
          "tick_bookkeeping_share": ("ring", "grad_accum", "wire")}
#: device idle gaps longer than this get the host threads' events logged
GAP_S = 0.020

INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.-]+)\s*=\s*(.*)$")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
OPCODE = re.compile(r"(?<![\w%.:-])([a-z][a-z0-9_-]*)\(")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
CALLS = re.compile(r"calls=%?([\w.-]+)")
OPERAND = re.compile(r"%([\w.-]+)")


def path_scope(path: str) -> Optional[str]:
    """The scope label of one ``op_name`` path, or None."""
    segs = path.split("/")
    prim = [s for s in segs if s in PRIMARY]
    if not prim:
        return None
    label = prim[-1]
    if "rematted_computation" in segs:
        label = "replay"
    elif label in ("fwd", "replay") and any(
            s.startswith("transpose(") for s in segs):
        label = "bwd"
    nested = [s for s in segs if s in NESTED]
    return f"{label}/{nested[-1]}" if nested else label


def phase(label: str) -> str:
    """``replay/head_loss`` -> ``replay``."""
    return label.split("/", 1)[0]


def _operands(rhs: str) -> List[str]:
    m = OPCODE.search(rhs)
    if not m:
        return []
    depth, i = 0, m.end() - 1
    for j in range(i, len(rhs)):
        if rhs[j] == "(":
            depth += 1
        elif rhs[j] == ")":
            depth -= 1
            if depth == 0:
                return OPERAND.findall(rhs[i:j])
    return OPERAND.findall(rhs[i:])


class Module:
    """The instructions of an HLO module's text: for each, its
    ``op_name`` (or None), the computation it calls (fusions) and its
    operands; for each computation, its root and instructions."""

    def __init__(self, hlo_text: str):
        self.instr: Dict[str, Tuple[Optional[str], Optional[str],
                                    List[str]]] = {}
        self.comps: Dict[str, List[str]] = {}
        self.root: Dict[str, str] = {}
        self._memo: Dict[str, Tuple[Optional[str],
                                    Optional[str]]] = {}
        cur = None
        for line in hlo_text.splitlines():
            m = COMPUTATION.match(line)
            if m and not line.startswith(" "):
                cur = m.group(1)
                self.comps[cur] = []
                continue
            m = INSTR.match(line)
            if cur is None or not m:
                continue
            is_root, name, rhs = m.groups()
            meta = OP_NAME.search(rhs)
            body = rhs.split(", metadata=", 1)[0]
            op = OPCODE.search(body)
            calls = CALLS.search(body) if op and op.group(1) == "fusion" \
                else None
            # instruction names are unique in a module XLA printed; a
            # fused computation's name never shadows an outer one
            self.instr.setdefault(name, (
                meta.group(1) if meta else None,
                calls.group(1) if calls else None, _operands(body)))
            self.comps[cur].append(name)
            if is_root:
                self.root[cur] = name

    def _path(self, name: str) -> Optional[str]:
        """The ``op_name`` an instruction carries, or for a fusion
        without one, that of its fused root, else of the first fused
        instruction that has one."""
        meta, calls, _ = self.instr[name]
        if meta is not None or calls is None:
            return meta
        inner = [self.root.get(calls)] + self.comps.get(calls, [])
        for n in inner:
            if n is not None and self.instr[n][0] is not None:
                return self.instr[n][0]
        return None

    def resolve(self, name: str) -> Tuple[Optional[str], Optional[str]]:
        """``(path, scope label)`` of an instruction: its own path, or
        that of its first operand that has a scope; ``(None, None)``
        for ``unscoped``."""
        if name in self._memo:
            return self._memo[name]
        if name not in self.instr:
            return None, None
        self._memo[name] = (None, None)     # cuts a cycle, if any
        path = self._path(name)
        out = (path, path_scope(path)) if path is not None else (None, None)
        if path is None:
            for op in self.instr[name][2]:
                if self.resolve(op)[1] is not None:
                    out = self.resolve(op)
                    break
        self._memo[name] = out
        return out

    def scope(self, name: str) -> Optional[str]:
        """The scope label of an instruction, or None (``unscoped``)."""
        return self.resolve(name)[1]


def instr_name(hlo_line: str) -> str:
    """``%fusion.78 = bf16[...] fusion(...)`` -> ``fusion.78``."""
    head = hlo_line.split(" = ", 1)[0].strip()
    if head.startswith("ROOT "):
        head = head[5:]
    return head.lstrip("%")


Op = Tuple[str, float, float]                # instruction name, start, end
HostEvent = Tuple[str, str, float, float]    # thread, name, start, end


def load(trace_dir: str) -> dict:
    """``{"devices": {id: [Op]}, "host": [HostEvent]}`` from the newest
    ``.xplane.pb`` under ``trace_dir``: each device's leaf ops of its
    ``XLA Ops`` line, and every event of every host thread."""
    from jax.profiler import ProfileData

    from bench.trace_reduce import leaves
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {"devices": {}, "host": []}
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            raw = [(e.name, float(e.start_ns), float(e.end_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices[int(m.group(1))] = [(instr_name(h), s, e)
                                        for h, s, e in leaves(raw)]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(line.name, e.name, float(e.start_ns),
                          float(e.end_ns)) for e in line.events]
    return {"devices": devices, "host": host}


def partition(ops: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds per label over ``(start_ns, end_ns, label)`` intervals,
    each busy instant counted once, for the op that started last."""
    evs = sorted(ops)
    points = sorted({x for s, e, _ in evs for x in (s, e)})
    out: Dict[str, float] = defaultdict(float)
    active: list = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(active, (-evs[i][0], i, evs[i][1], evs[i][2]))
            i += 1
        while active and active[0][2] <= a:
            heapq.heappop(active)
        if active:
            out[active[0][3]] += (b - a) * 1e-9
    return dict(out)


def reduce(trace: dict, hlo_text: str, window: Tuple[float, float],
           top: int = 5) -> dict:
    """Per device: seconds per scope label (``scope_s``, with
    ``unscoped``), the busy seconds, the ``top`` unscoped instructions
    by time, and the idle gaps over ``GAP_S`` with the host events that
    overlap each, longest first."""
    from bench.trace_reduce import _union
    mod = Module(hlo_text)
    lo, hi = window
    out = {}
    for dev, ops in sorted(trace["devices"].items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if e > lo and s < hi]
        labelled = [(s, e, mod.scope(n) or UNSCOPED) for n, s, e in ops]
        scope_s = partition(labelled)
        busy = _union([(s, e) for _, s, e in ops])
        un = defaultdict(float)
        for n, s, e in ops:
            if mod.scope(n) is None:
                un[n] += (e - s) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                if (e - s) * 1e-9 > GAP_S]
        out[dev] = {
            "scope_s": scope_s,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "unscoped_top": sorted(un.items(), key=lambda kv: -kv[1])[:top],
            "gaps": [{"start_s": (s - lo) * 1e-9, "gap_s": (e - s) * 1e-9,
                      "host": _overlapping(trace["host"], s, e)}
                     for s, e in gaps],
        }
    return out


def _overlapping(host: List[HostEvent], s: float, e: float,
                 top: int = 12) -> List[Tuple[str, str, float]]:
    """Host events overlapping ``[s, e]``: (thread, name, overlap s),
    longest overlap first."""
    hits = [(th, n, (min(e, he) - max(s, hs)) * 1e-9)
            for th, n, hs, he in host if he > s and hs < e]
    return sorted(hits, key=lambda h: -h[2])[:top]


def share(scope_s: Dict[int, Dict[str, float]], window_s: float,
          metric: str) -> Optional[float]:
    """``metric`` of ``SHARES``: the mean over devices of the seconds of
    the labels whose phase it names, over the window, in %; None when no
    op of the trace carries a scope (a program without them)."""
    if not scope_s or not any(lab != UNSCOPED for d in scope_s.values()
                              for lab in d):
        return None
    per = [sum(t for lab, t in d.items() if phase(lab) in SHARES[metric])
           for d in scope_s.values()]
    return 100.0 * sum(per) / len(per) / window_s


def log_reduction(red: dict, window_s: float, log) -> None:
    for dev, d in sorted(red.items()):
        by_phase = defaultdict(float)
        for lab, t in d["scope_s"].items():
            by_phase[phase(lab)] += t
        cover = 1.0 - by_phase.get(UNSCOPED, 0.0) / max(d["busy_s"], 1e-12)
        log(f"scopes tpu{dev}: busy {d['busy_s']:.6f} s of {window_s:.6f} "
            f"s; scoped {100 * cover:.3f} % of busy; by phase "
            f"{json.dumps(dict(sorted(by_phase.items())))}")
        log(f"scopes tpu{dev}: by label "
            f"{json.dumps(dict(sorted(d['scope_s'].items())))}")
        log(f"scopes tpu{dev}: unscoped {by_phase.get(UNSCOPED, 0.0):.6f} "
            f"s, top ops {json.dumps(d['unscoped_top'])}")
        for g in d["gaps"]:
            log(f"idle gap tpu{dev} at {g['start_s']:.6f} s, "
                f"{g['gap_s']:.6f} s; host events overlapping it "
                f"(thread, name, s): {json.dumps(g['host'])}")


# ---------------------------------------------------------------------------
# the chip run
# ---------------------------------------------------------------------------

def _window(prog, params, opt, batch, data, rows, seconds, span):
    """``harness.run_cell``'s measured window, with its host spans when
    ``span`` writes them: steps back to back, each ending in
    ``block_until_ready``, the next batch made while the device runs."""
    import jax
    steps = 0
    with span("window"):
        t0 = time.perf_counter()
        while True:
            with span("dispatch"):
                params, opt, met = prog.step(params, opt, batch)
            with span("input"):
                batch = prog.put_batch(data.next_batch(rows))
            with span("wait"):
                jax.block_until_ready((params, opt, met))
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
    return params, opt, batch, steps, wall


def measure(m, traffic: dict, devs, seed: int, seconds: float) -> dict:
    """The cell's step through an untraced window, then a traced one;
    the scope reduction of the traced one, logged, and the result."""
    import jax

    from bench import harness, trace_reduce
    from bench.metrics import (optimizer_share, replay_share,
                               tick_bookkeeping_share)
    from bench.program import Program
    from bench.synthetic import SyntheticLM
    prog = Program(m, traffic, devs)
    params, opt = prog.init_state(seed)
    data = SyntheticLM(m.vocab, traffic["seq_len"] + 1, seed)
    rows = traffic["microbatches"] * traffic["microbatch_size"]
    batch = prog.put_batch(data.next_batch(rows))
    for _ in range(2):
        params, opt, _ = prog.step(params, opt, batch)
    jax.block_until_ready((params, opt))

    runs, tdir = {}, tempfile.mkdtemp(prefix="scopes_trace_")
    for traced in (False, True):
        if traced:
            jax.profiler.start_trace(tdir)
        span = harness._span if traced else (
            lambda _: contextlib.nullcontext())
        params, opt, batch, steps, wall = _window(
            prog, params, opt, batch, data, rows, seconds, span)
        if traced:
            jax.profiler.stop_trace()
        key = "traced" if traced else "untraced"
        runs[key] = {"steps": steps, "wall_s": wall,
                     "train_tokens_per_s":
                         steps * rows * traffic["seq_len"] / wall}
        harness.log(f"{key} window: {steps} steps in {wall:.4f} s")
    hlo = prog.compiled.as_text()
    red = trace_reduce.reduce(trace_reduce.load(tdir, hlo))
    raw = load(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    result = {"device": {"kind": devs[0].device_kind, "count": len(devs)},
              "runs": runs, "hlo": hlo}
    if not red:
        harness.log("the trace holds no TPU ops: nothing to reduce")
        return result
    win = next((s, e) for _, n, s, e in raw["host"] if n == "window")
    sc = reduce(raw, hlo, win)
    log_reduction(sc, red["window_s"], harness.log)
    ctx = harness.Ctx(trace=red, scopes={d: v["scope_s"]
                                         for d, v in sc.items()})
    mod = Module(hlo)
    ops0 = defaultdict(float)
    for n, s, e in raw["devices"][min(raw["devices"])]:
        if e > win[0] and s < win[1]:
            ops0[n] += (min(e, win[1]) - max(s, win[0])) * 1e-9
    result.update(
        window_s=red["window_s"],
        metrics={r.__name__.rsplit(".", 1)[1]: r.read(ctx) for r in
                 (replay_share, optimizer_share, tick_bookkeeping_share)},
        scopes={d: {k: v[k] for k in ("busy_s", "scope_s", "unscoped_top",
                                      "gaps")} for d, v in sc.items()},
        ops=[(n, mod.scope(n) or UNSCOPED, t) for n, t in
             sorted(ops0.items(), key=lambda kv: -kv[1])[:300]])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, model as M
    harness.enable_cache()
    import jax
    devs = jax.devices()
    cell = {w["name"]: w for w in
            harness.load_benchmark()["workloads"]}[args.workload]
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("no TPU, or too few chips", file=sys.stderr)
        return 3
    res = measure(M.from_config(M.load_config(cell["config"])),
                  harness.load_traffic(cell["traffic"]),
                  devs[:cell["chips"]], args.seed, args.seconds)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"scopes_{args.workload}_{args.seed}")
    with open(stem + ".hlo.txt", "w") as f:
        f.write(res.pop("hlo"))
    with open(stem + ".json", "w") as f:
        json.dump(res, f)
    line = {k: res[k] for k in ("device", "runs", "window_s", "metrics")
            if k in res}
    line["scoped_share_of_busy"] = {
        d: 1.0 - v["scope_s"].get(UNSCOPED, 0.0) / v["busy_s"]
        for d, v in res.get("scopes", {}).items()}
    print(json.dumps(dict(line, workload=args.workload, seed=args.seed)),
          flush=True)
    return 0


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
