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
* ``gaps`` — the stretches of the window in which no device operation
  ran, each labelled with the innermost benchmark span (``solve``,
  ``make_data``, ``sync``, ...) that covers most of it.

Nothing here imports the program.  ``jax.profiler.ProfileData`` reads
the file.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
# the benchmark's own host spans, innermost first when they nest
SPANS = ("sync", "make_data", "solve", "check", WINDOW_SPAN)


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float
    device_ns: dict  # op name -> device ns inside the window (summed over devices)
    gaps: list  # [(label, ns)], longest first
    n_devices: int
    spans: dict  # span name -> count inside the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def device_ns_matching(self, needle: str) -> float:
        """Device ns of every op whose name contains ``needle``."""
        return sum(ns for name, ns in self.device_ns.items() if needle in name)

    def top_ops(self, n: int = 10) -> list:
        items = sorted(self.device_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in items]

    def top_gaps(self, n: int = 10) -> list:
        return [[label, ns / 1e9] for label, ns in self.gaps[:n]]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


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


def summarize(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans = []  # (name, start, end)
    device_events = {}  # plane name -> [(start, end, op name)]
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = device_events.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns, _op_name(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        host_spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW_SPAN!r}")
    w0, w1 = windows[0]
    device_events = {k: v for k, v in device_events.items() if v}
    if not device_events:
        raise ValueError(f"{path}: no device op inside any {DEVICE_PREFIX}* plane")

    device_ns: dict = {}
    busy_total = 0.0
    busy_first = None
    for plane in sorted(device_events):
        clipped = []
        for s, e, name in device_events[plane]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if not name.startswith(CONTAINERS):
                device_ns[name] = device_ns.get(name, 0.0) + (e - s)
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        if busy_first is None:
            busy_first = merged
    n_dev = len(device_events)

    # idle gaps on the first device, labelled by the innermost host span
    inner = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN and e > w0 and s < w1]
    gaps = []
    t = w0
    for s, e in busy_first + [[w1, w1]]:
        if s > t:
            gaps.append((_label(inner, t, s), s - t))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    spans = {}
    for n, _, _ in inner:
        spans[n] = spans.get(n, 0) + 1
    return TraceSummary(
        window_ns=float(w1 - w0), busy_ns=busy_total / n_dev, device_ns=device_ns,
        gaps=gaps, n_devices=n_dev, spans=spans,
    )


def _label(spans, s, e) -> str:
    """The span that covers most of [s, e); among spans that nest, the
    innermost (``SPANS`` order) wins a tie.  ``host`` where none does."""
    best, best_key = "host", (0.0, -len(SPANS))
    for name, a, b in spans:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        key = (cover, -SPANS.index(name))
        if key > best_key:
            best, best_key = name, key
    return best
