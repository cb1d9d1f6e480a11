"""The optimized HLO of a cell's training step, compiled for a TPU v5e
that is described and not attached, with every ``metadata={...}`` and
the debug tables (file names and stack frames) taken out and every name
made canonical: what is left is the program the chip runs.  Two
checkouts whose stripped texts are byte-identical differ only in names
and source locations, so scopes added to the program cost nothing when
tracing is off.

    JAX_PLATFORMS=cpu python bench/stripped_hlo.py --workload <cell> \\
        [--src <a checkout's src/>] --out <file>

``--src`` puts another checkout's program first on the path (the
benchmark's own modules stay this checkout's).  Prints the sha256 of the
stripped text.  The benchmark's own runs do not run this.
"""
import argparse
import hashlib
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METADATA = re.compile(r", metadata=\{[^}]*\}")
#: a debug table XLA prints between the module line and the first
#: computation: its header and its lines up to a blank one
TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                   r"\n(?:.+\n)*", re.M)


HEADER = re.compile(r"^(ENTRY )?%([\w.-]+) \(.*\) -> .*\{$")
NAME = re.compile(r"%([\w.-]+)")


def strip(hlo_text: str) -> str:
    """The text without metadata and debug tables, every computation and
    instruction renamed ``%v<k>`` in order of first appearance (XLA
    derives names from source locations, which named scopes change), and
    computation headers cut to their names (their parameters are
    declared again as instructions)."""
    names: dict = {}

    def canon(m):
        return names.setdefault(m.group(1), f"%v{len(names)}")

    out = []
    for line in TABLE.sub("", METADATA.sub("", hlo_text)).splitlines():
        h = HEADER.match(line)
        if h:
            line = f"{h.group(1) or ''}%{h.group(2)} {{"
        out.append(NAME.sub(canon, line))
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--src", default=os.path.join(ROOT, "src"))
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.src), ROOT]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench import harness, model as M
    from bench.program import Program
    jax.config.update("jax_enable_compilation_cache", False)
    cell = {w["name"]: w for w in
            harness.load_benchmark()["workloads"]}[args.workload]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    m = M.from_config(M.load_config(cell["config"]))
    prog = Program(m, harness.load_traffic(cell["traffic"]),
                   list(topo.devices[:cell["chips"]]))
    text = strip(prog.compiled.as_text())
    with open(args.out, "w") as f:
        f.write(text)
    print(f"{args.workload} {hashlib.sha256(text.encode()).hexdigest()} "
          f"{len(text)} bytes; temp {prog.memory.temp_size_in_bytes} B")
    return 0


if __name__ == "__main__":
    sys.exit(main())
