"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

The traced window is the host span named ``window`` that the harness
wraps around its measured solves.  Inside it:

* ``busy_ns`` — the union of the intervals in which an operation ran on
  the device (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane),
  averaged over the device planes;
* ``device_ns`` — device time per HLO instruction name (a Pallas
  kernel's is the ``name`` its ``pallas_call`` was given, with a
  numeric suffix), leaving out ops that only hold others (a scan's
  ``while``), whose time is their body's;
* ``module_ns`` — device time per stage of the program: a TPU trace's
  ``XLA Modules`` line holds one event per module run, named after its
  jit (``jit_simjoin_compact(<fingerprint>)``), whose stage is that
  name with ``jit_`` and the fingerprint stripped; a stage's time is
  the union of its modules' intervals;
* ``stage_op_ns`` — ``device_ns`` split by stage, keyed
  ``<stage>/<op>``: every op belongs to the module whose interval holds
  its start on the same device plane (``?`` where none does);
* ``gaps`` — the stretches of the window in which no device operation
  ran, each labelled with the span innermost over most of it: a program
  span where one is open, else one of the benchmark's own spans
  (``solve``, ``make_data``, ``sync``, ...).

The program's host spans follow one naming rule, ``<layer>.<stage>`` in
lower case (``simjoin.sync``), which no event of the JAX runtime
matches.  Nothing here imports the program.  ``jax.profiler.ProfileData``
reads the file.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
# the benchmark's own host spans, innermost first when they nest
SPANS = ("sync", "make_data", "solve", "check", WINDOW_SPAN)
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*")
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float
    device_ns: dict  # op name -> device ns inside the window (summed over devices)
    gaps: list  # [(label, ns)], longest first
    n_devices: int
    spans: dict  # span name (benchmark's and program's) -> count inside the window
    module_ns: dict  # stage -> device ns inside the window (summed over devices)
    stage_op_ns: dict  # "<stage>/<op>" -> device ns inside the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def device_ns_matching(self, needle: str) -> float:
        """Device ns of every op whose name contains ``needle``."""
        return sum(ns for name, ns in self.device_ns.items() if needle in name)

    def gap_ns_labelled(self, prefix: str) -> float:
        """Idle ns of the gaps whose label starts with ``prefix``."""
        return sum(ns for label, ns in self.gaps if label.startswith(prefix))

    def top_ops(self, n: int = 10) -> list:
        items = sorted(self.stage_op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in items]

    def top_gaps(self, n: int = 10) -> list:
        return [[label, ns / 1e9] for label, ns in self.gaps[:n]]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def stage(module: str) -> str:
    """``jit_simjoin_compact(123)`` -> ``simjoin_compact``."""
    name = _FINGERPRINT.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def is_program_span(name: str) -> bool:
    return PROGRAM_SPAN.fullmatch(name) is not None


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


# ops that only hold others (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")


def _op_name(ev) -> str:
    """An op's HLO instruction name: a TPU trace names each op by its
    whole HLO text (``%kmeans_lloyd_fused.4 = (...) custom-call(...)``);
    the part before `` = `` holds the name a ``pallas_call`` was given."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def _owner(mods, starts, s) -> str:
    """The stage of the latest-starting module of ``mods`` (sorted) whose
    interval holds ``s``; ``?`` where none does."""
    for a, b, name in reversed(mods[:bisect.bisect_right(starts, s)]):
        if s < b:
            return name
    return "?"


def summarize(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    bench_spans, program_spans = [], []  # (name, start, end)
    device_events = {}  # plane name -> [(start, end, op name)]
    modules = {}  # plane name -> [(start, end, stage)]
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = device_events.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((ev.start_ns, ev.start_ns + ev.duration_ns, _op_name(ev))
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, stage(ev.name))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name in SPANS:
                        bench_spans.append(span)
                    elif is_program_span(ev.name):
                        program_spans.append(span)
    windows = [(s, e) for name, s, e in bench_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW_SPAN!r}")
    w0, w1 = windows[0]
    device_events = {k: v for k, v in device_events.items() if v}
    if not device_events:
        raise ValueError(f"{path}: no device op inside any {DEVICE_PREFIX}* plane")

    device_ns: dict = {}
    module_ns: dict = {}
    stage_op_ns: dict = {}
    busy_total = 0.0
    busy_first = None
    for plane in sorted(device_events):
        mods = sorted(modules.get(plane, []))
        starts = [a for a, _, _ in mods]
        by_stage: dict = {}
        for s, e, name in mods:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                by_stage.setdefault(name, []).append((s, e))
        for name, ivs in by_stage.items():
            module_ns[name] = module_ns.get(name, 0) + sum(b - a for a, b in _union(ivs))
        clipped = []
        for s, e, name in device_events[plane]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if not name.startswith(CONTAINERS):
                device_ns[name] = device_ns.get(name, 0.0) + (e - s)
                key = f"{_owner(mods, starts, s)}/{name}"
                stage_op_ns[key] = stage_op_ns.get(key, 0) + (e - s)
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        if busy_first is None:
            busy_first = merged
    n_dev = len(device_events)

    # idle gaps on the first device, labelled by the innermost host span
    inner = [(n, s, e) for n, s, e in bench_spans if n != WINDOW_SPAN and e > w0 and s < w1]
    mine = [(n, s, e) for n, s, e in program_spans if e > w0 and s < w1]
    gaps = []
    t = w0
    for s, e in busy_first + [[w1, w1]]:
        if s > t:
            gaps.append((_label(inner, t, s, mine), s - t))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    spans = {}
    for n, _, _ in inner + mine:
        spans[n] = spans.get(n, 0) + 1
    return TraceSummary(
        window_ns=float(w1 - w0), busy_ns=busy_total / n_dev, device_ns=device_ns,
        gaps=gaps, n_devices=n_dev, spans=spans, module_ns=module_ns,
        stage_op_ns=stage_op_ns,
    )


def _bench_label(spans, s, e) -> str:
    """The benchmark span that covers most of [s, e); among spans that
    nest, the innermost (``SPANS`` order) wins a tie.  ``host`` where
    none does."""
    best, best_key = "host", (0.0, -len(SPANS))
    for name, a, b in spans:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        key = (cover, -SPANS.index(name))
        if key > best_key:
            best, best_key = name, key
    return best


def _label(bench, s, e, program=()) -> str:
    """The span innermost over most of [s, e).  At each instant the
    innermost open span is a program span where one is open (spans of
    one thread nest, so the latest to have started), else a benchmark
    span in ``SPANS`` order.  A gap no program span touches gets the
    benchmark span that covers most of it (:func:`_bench_label`)."""
    program = [p for p in program if p[1] < e and p[2] > s]
    if not program:
        return _bench_label(bench, s, e)
    bench = [p for p in bench if p[1] < e and p[2] > s]
    cuts = sorted({s, e} | {t for _, a, b in bench + program for t in (a, b) if s < t < e})
    share: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        open_program = [(sa, name) for name, sa, sb in program if sa <= a and sb >= b]
        open_bench = [name for name, sa, sb in bench if sa <= a and sb >= b]
        name = (max(open_program)[1] if open_program else
                min(open_bench, key=SPANS.index) if open_bench else "host")
        share[name] = share.get(name, 0) + (b - a)
    return max(share, key=share.get)
