"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``load`` keeps, per device, the ops of its ``XLA Ops`` line, and the
benchmark's own host spans (``HOST_SPANS``).  ``reduce`` works on that
plain structure, so the arithmetic is tested on small recorded traces:

- busy time: the union of op intervals of a device inside the window;
- op time by name, summed over the window, and the same by class
  (``matmul``, ``collective``, ``other``); an op is a matmul when it is
  a convolution or dot, or a fusion whose computation holds one, as the
  optimized HLO module of the traced program says (``load``'s
  ``hlo_text``);
- exposed collective time: collective intervals of a device while no
  other op runs on it;
- idle gaps: the gaps between busy intervals, each named by the host
  span that covers most of it.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import List, Tuple

#: host spans the harness writes around its calls, as TraceAnnotations
HOST_SPANS = ("window", "input", "dispatch", "wait")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all|"
    r"send|recv|collective)", re.I)
#: an instruction whose opcode is a matmul
MATMUL_OP = re.compile(r"(?<![%\w.-])(convolution|dot)\(")
#: the computations an instruction calls
CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.-]+)")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")

Event = Tuple[str, float, float, str]        # name, start_ns, end_ns, class


def op_name(hlo: str) -> str:
    """The op's own name from the trace's HLO line: ``%fusion.78 = ...,
    kind=kOutput`` -> ``fusion:kOutput``; numeric suffixes dropped so
    that ops of one kind add up."""
    short = hlo.split(" = ")[0].lstrip("%")
    base = re.sub(r"(\.\d+)+(\.clone)*$", "", short)
    kind = re.search(r"kind=(k\w+)", hlo)
    return f"{base}:{kind.group(1)}" if kind else base


def _called(line: str) -> List[str]:
    out = CALLS.findall(line)
    for group in BRANCHES.findall(line):
        out += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
    return out


def matmul_computations(hlo_text: str) -> frozenset:
    """Names of the computations of an HLO module's text that hold a
    convolution or a dot, themselves or in a computation they call."""
    direct, calls, cur = set(), {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            calls[cur] = []
            continue
        if cur is None or " = " not in line:
            continue
        if MATMUL_OP.search(line.split(" = ", 1)[1]):
            direct.add(cur)
        calls[cur] += _called(line)
    found, changed = set(direct), True
    while changed:
        changed = False
        for c, callees in calls.items():
            if c not in found and any(x in found for x in callees):
                found.add(c)
                changed = True
    return frozenset(found)


def classify(hlo: str, matmul_comps=frozenset()) -> str:
    """``collective`` for the exchange ops; ``matmul`` for a convolution
    or dot, or an op that calls a computation in ``matmul_comps``; else
    ``other``.  ``hlo`` is the op's line in the trace."""
    if COLLECTIVE.search(op_name(hlo)):
        return "collective"
    rhs = hlo.split(" = ", 1)[-1]
    if MATMUL_OP.search(rhs) or any(c in matmul_comps
                                    for c in _called(hlo)):
        return "matmul"
    return "other"


def leaves(events):
    """Drop container events (``while``, ``conditional``) whose interval
    holds other events of the same line: only leaf ops count."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] = False
        stack.append(i)
        out.append(True)
    return [ev for ev, leaf in zip(evs, out) if leaf]


def load(trace_dir: str, hlo_text: str = "") -> dict:
    """{"devices": {id: [Event]}, "host": [(name, start, end)]} from the
    newest ``.xplane.pb`` under ``trace_dir``; ``hlo_text`` is the
    optimized HLO module of the traced program, which tells the matmuls
    apart (without it no op counts as one)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {"devices": {}, "host": []}
    pd = ProfileData.from_file(paths[-1])
    mm = matmul_computations(hlo_text)
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            raw = [(e.name, float(e.start_ns), float(e.end_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices[int(m.group(1))] = [
                (op_name(h), s, e, classify(h, mm))
                for h, s, e in leaves(raw)]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, float(e.start_ns),
                                     float(e.end_ns)))
    return {"devices": devices, "host": host}


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b):
    """Intervals of ``a`` not covered by ``b`` (both unions)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """Per-device busy, exposed-collective and class times, op times and
    idle gaps over the ``window`` host span (seconds)."""
    win = [(s, e) for n, s, e in trace["host"] if n == "window"]
    if not win or not trace["devices"]:
        return {}
    lo, hi = win[0]
    window = (hi - lo) * 1e-9
    per_dev, op_time = {}, defaultdict(float)
    gaps = []
    spans = [(n, s, e) for n, s, e in trace["host"] if n != "window"]
    for dev, evs in sorted(trace["devices"].items()):
        ops = [(n, max(s, lo), min(e, hi), c) for n, s, e, c in evs
               if e > lo and s < hi]
        busy = _union([(s, e) for _, s, e, _ in ops])
        coll = _union([(s, e) for _, s, e, c in ops if c == "collective"])
        comp = _union([(s, e) for _, s, e, c in ops if c != "collective"])
        cls = defaultdict(float)
        for n, s, e, c in ops:
            cls[c] += (e - s) * 1e-9
            op_time[n] += (e - s) * 1e-9 / len(trace["devices"])
        per_dev[dev] = {
            "busy_s": _length(busy) * 1e-9,
            "exposed_collective_s": _length(_minus(coll, comp)) * 1e-9,
            "class_s": dict(cls),
        }
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                best, cover = "none", 0.0
                for n, hs, he in spans:
                    c = min(e, he) - max(s, hs)
                    if c > cover:
                        best, cover = n, c
                gaps.append((f"{best}@tpu{dev}", (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {"window_s": window, "devices": per_dev,
            "device_ops": [[n, t] for n, t in ops_sorted[:top]],
            "idle_gaps": [[n, t] for n, t in gaps[:top]]}
