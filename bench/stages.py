"""The program's own stages in a profiler trace (``.xplane.pb``).

A TPU trace's ``XLA Modules`` line holds one event per module run,
named after its jit (``jit_simjoin_compact(<fingerprint>)``).  Its stage
is that name with ``jit_`` and the fingerprint stripped, and every op of
the ``XLA Ops`` line belongs to the module whose interval holds it on
the same device plane.  The program's host spans follow one naming
rule, ``<layer>.<stage>`` in lower case (``simjoin.sync``), which no
event of the JAX runtime matches.  Inside the benchmark's ``window``
span this gives:

* ``module_ns`` — device ns per stage, the union of its modules'
  intervals;
* ``device_ns`` — device ns per ``<stage>/<op>``, leaving out ops that
  only hold others, as ``bench/trace.py`` does;
* ``gaps`` — the stretches in which no device op ran (the same stretches
  ``bench/trace.py`` finds), each labelled with the program span that is
  innermost over most of it, or where no program span covers it, with
  the benchmark span ``bench/trace.py`` gives it.

The reduction reads the trace alone.  From the root of a checkout,

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

runs the cell once as ``bench/run.py ... --trace 1`` does, printing the
same lines, then one more, ``{"record": "stages", ...}``, reduced from
the same trace: per solve, the device ms of each stage, the device ms
of the ε-join's compaction and the idle ms behind its host steps; the
ten ops and gaps that take most time; how many gaps of 1 ms or more
each label holds; and the readings of the program's counters by
``bench/metrics/simjoin.tile_yield.py`` and ``simjoin.compact_yield.py``
(None where the program keeps no counters).

This is a second reducer beside ``bench/trace.py`` until the benchmark
itself attributes ops to modules and labels gaps with program spans;
it reaches the window's trace by standing in for ``trace.summarize``
while the run lasts, as ``bench/run.py`` offers no hook for it.
"""
from __future__ import annotations

import json
import pathlib
import re
import sys

if __package__ in (None, ""):
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

MODULES_LINE = "XLA Modules"
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*")
_FINGERPRINT = re.compile(r"\(\d+\)$")
COUNTER_METRICS = ("simjoin.tile_yield", "simjoin.compact_yield")


def stage(module: str) -> str:
    """``jit_simjoin_compact(123)`` -> ``simjoin_compact``."""
    name = _FINGERPRINT.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def is_program_span(name: str) -> bool:
    return PROGRAM_SPAN.fullmatch(name) is not None


def _label(bench, program, s, e) -> str:
    """The span innermost over most of [s, e).  At each instant the
    innermost open span is a program span where one is open (spans of
    one thread nest, so the latest to have started), else a benchmark
    span in ``bench/trace.py``'s order.  A gap no program span touches
    gets the label ``bench/trace.py`` gives it."""
    program = [p for p in program if p[1] < e and p[2] > s]
    if not program:
        return trace._label(bench, s, e)
    bench = [p for p in bench if p[1] < e and p[2] > s]
    cuts = sorted({s, e} | {t for _, a, b in bench + program for t in (a, b) if s < t < e})
    share: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mine = [(sa, name) for name, sa, sb in program if sa <= a and sb >= b]
        theirs = [name for name, sa, sb in bench if sa <= a and sb >= b]
        name = (max(mine)[1] if mine else
                min(theirs, key=trace.SPANS.index) if theirs else "host")
        share[name] = share.get(name, 0) + (b - a)
    return max(share, key=share.get)


def summarize(path: str) -> dict:
    """The stage view of one ``.xplane.pb`` (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops = {}, {}  # plane -> [(start, end, stage)], [(start, end, op)]
    bench_spans, program_spans = [], []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, stage(ev.name))
                        for ev in line.events)
                elif line.name == trace.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, trace._op_name(ev))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name in trace.SPANS:
                        bench_spans.append(span)
                    elif is_program_span(ev.name):
                        program_spans.append(span)
    (w0, w1), *_ = [(s, e) for n, s, e in bench_spans if n == trace.WINDOW_SPAN]
    inside = [(n, s, e) for n, s, e in bench_spans if n != trace.WINDOW_SPAN and e > w0 and s < w1]
    mine = [(n, s, e) for n, s, e in program_spans if e > w0 and s < w1]

    module_ns: dict = {}
    device_ns: dict = {}
    busy_first = None
    for plane in sorted(p for p in ops if ops[p]):
        by_stage: dict = {}
        for s, e, name in modules.get(plane, []):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                by_stage.setdefault(name, []).append((s, e))
        for name, ivs in by_stage.items():
            module_ns[name] = module_ns.get(name, 0) + sum(b - a for a, b in trace._union(ivs))
        mods = sorted(modules.get(plane, []))
        clipped = []
        for s, e, name in ops[plane]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if name.startswith(trace.CONTAINERS):
                continue
            owner = [m for a, b, m in mods if a <= s < b]
            key = f"{owner[-1] if owner else '?'}/{name}"
            device_ns[key] = device_ns.get(key, 0) + (e - s)
        if busy_first is None:
            busy_first = trace._union(clipped)

    gaps = []
    t = w0
    for s, e in busy_first + [[w1, w1]]:
        if s > t:
            gaps.append((_label(inside, mine, t, s), s - t))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    solves = sum(1 for n, s, _ in inside if n == "solve" and s >= w0)
    return {"window_ns": w1 - w0, "solves": solves, "module_ns": module_ns,
            "device_ns": device_ns, "gaps": gaps}


def report(st: dict) -> dict:
    """The ``stages`` line: per-solve figures of :func:`summarize`'s
    result, in ms."""
    n = max(st["solves"], 1)
    waits = sum(ns for label, ns in st["gaps"] if is_program_span(label))
    big: dict = {}
    for label, ns in st["gaps"]:
        if ns >= 1e6:
            big[label] = big.get(label, 0) + 1
    top = sorted(st["device_ns"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "record": "stages",
        "solves": st["solves"],
        "module_ms_per_solve": {k: v / 1e6 / n for k, v in
                                sorted(st["module_ns"].items(), key=lambda kv: -kv[1])},
        "simjoin.compact_ms": st["module_ns"].get("simjoin_compact", 0) / 1e6 / n,
        "simjoin.host_wait_ms": waits / 1e6 / n,
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": [[label, ns / 1e9] for label, ns in st["gaps"][:10]],
        "gaps_1ms_by_label": big,
    }


def main(argv: list[str] | None = None) -> int:
    from bench import harness, run

    found = []
    window_summary = trace.summarize

    def both(path):
        line = report(summarize(path))
        for name in COUNTER_METRICS:  # read at the window's end: the run's joins
            line[name] = harness.reader(name)({})
        found.append(line)
        return window_summary(path)

    args = sys.argv[1:] if argv is None else argv
    sys.argv = [run.__file__, *args, "--trace", "1"]
    trace.summarize = both
    try:
        rc = run.main()
    finally:
        trace.summarize = window_summary
    for line in found:
        print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
