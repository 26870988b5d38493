"""One benchmark cell, run once: set up, measure, check, report.

The harness is driven by ``BENCHMARK.json`` and by files it finds by
name, so a new cell, configuration, traffic mix or metric is a new file
and never an edit here:

* ``<config file>`` named by the configuration's ``file`` entry: the
  deployment's sizes; its ``app`` key names ``bench/apps/<app>.py``,
  which makes the data, calls the entry point and compares its answers
  with the plain reference in ``bench/reference/<app>.py``;
* ``bench/traffic/<traffic>.json``: the job's parameters, how many
  answers the check samples, and the limit of each compared number;
* ``bench/metrics/<metric>.py``: one ``read(evidence)`` per metric,
  end-to-end or per layer, returning a number or ``None`` when it finds
  nothing to read; the evidence holds the window's times, the reduced
  trace (``bench/trace.py``) and the program's counters as the window's
  solves counted them;
* ``bench/peaks.json``: the chip's peaks by ``device_kind``.

A run is a closed loop, one job at a time, as a batch-job runner calls
the library: set-up makes the data on the device from the seed and runs
one solve of a job the window never uses (compiling, or reading JAX's
persistent cache), then solves back to back until ``seconds`` have
passed, each ending in ``block_until_ready`` on all it returned.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import pathlib
import random
import shutil
import sys
import tempfile
import time
import warnings

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The run would not measure the chip: no TPU, too few, an unknown
    kind, or kernels in interpret mode."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root=ROOT) -> dict:
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def resolve(spec: dict, workload: str, root=ROOT) -> dict:
    """The workload's entry, its configuration and traffic files, and the
    metrics it reports in a plain and in a traced run."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return {
        "workload": w,
        "config": load_json(pathlib.Path(root) / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def reader(metric: str):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict | None:
    return load_json(BENCH / "peaks.json")["devices"].get(kind)


# ---------------------------------------------------------------------------
# seeds, spans, counters
# ---------------------------------------------------------------------------

class Seeds:
    """Every random draw of a run, from ``--seed`` (any whole number) and
    a tag: the same seed gives the same data and the same jobs."""

    def __init__(self, seed: int):
        self.seed = int(seed)

    def _words(self, *tag):
        import numpy as np

        ent = [self.seed] + [t if isinstance(t, int) else int.from_bytes(str(t).encode(), "little")
                             for t in tag]
        return np.random.SeedSequence(ent).generate_state(2)

    def key(self, *tag):
        import jax

        return jax.random.wrap_key_data(self._words(*tag))

    def int31(self, *tag) -> int:
        return int(self._words(*tag)[0]) & 0x7FFFFFFF


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """JAX's own ``backend_compile_duration`` events (a compile, or a
    persistent-cache read in its place): how many, and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count, self.secs = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1
            self.secs += secs


def program_counters() -> dict:
    """A copy of the program's counters (``repro.core.tracing``), or {}
    where the program keeps none."""
    try:
        from repro.core.tracing import counters
    except ImportError:
        return {}
    return counters()


# ---------------------------------------------------------------------------
# readers' helpers
# ---------------------------------------------------------------------------

def roofline_percent(ev):
    """The solve's least time on this chip — the larger of its work's
    operations over peak FLOP/s and bytes over peak bandwidth — over the
    wall time per solve, in percent.  None without peaks or work."""
    pk, work, solve_s = ev.get("peaks"), ev.get("work"), ev.get("solve_s")
    if not pk or not work or not solve_s:
        return None
    least = max(work["flops"] / pk["flops_per_s"], work["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / solve_s if least > 0 else None


def device_ms_per_solve(ev, needle: str):
    """Device ms per solve of the ops whose name holds ``needle``."""
    tr = ev.get("trace")
    if tr is None or not ev.get("solves"):
        return None
    ns = tr.device_ns_matching(needle)
    return ns / 1e6 / ev["solves"] if ns > 0 else None


def module_ms_per_solve(ev, stage: str):
    """Device ms per solve in the program's stage ``stage`` (its jit's
    modules in the trace's ``XLA Modules`` line)."""
    tr = ev.get("trace")
    if tr is None or not ev.get("solves"):
        return None
    ns = tr.module_ns.get(stage, 0)
    return ns / 1e6 / ev["solves"] if ns > 0 else None


def counter_percent(ev, part: str, whole: str):
    """100 × the window's count of ``part`` over its count of ``whole``;
    None where the window counted no ``whole``."""
    c = ev.get("counters") or {}
    if not c.get(whole):
        return None
    return 100.0 * c.get(part, 0) / c[whole]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_stamp(chips: int, require_chip: bool):
    import jax

    from repro.kernels.launch import resolve_interpret

    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} and there is "
                         "no CPU fallback")
        if resolve_interpret(None):
            raise NoChip("the kernels would run in interpret mode")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
        if peaks(kind) is None:
            raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def _memory_peak(devs):
    peaks_in_use = []
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_in_use.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_in_use) if peaks_in_use else None


def _emit(record: dict, out) -> None:
    print(json.dumps(record), file=out, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: dict | None = None, overrides: dict | None = None,
             require_chip: bool = True, t0: float | None = None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Run ``workload`` once and return its result line (also printed
    last on ``out``).  ``overrides`` ({"config": {...}, "traffic":
    {...}}) and ``require_chip=False`` are for the CPU tests only."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = resolve(spec or load_spec(), workload)
    for part in ("config", "traffic"):
        cell[part].update((overrides or {}).get(part, {}))
    cfg, traffic = cell["config"], cell["traffic"]

    import jax

    devs, device = device_stamp(int(cell["workload"]["chips"]), require_chip)
    counter = CompileCounter()
    app_mod = importlib.import_module(f"bench.apps.{cfg['app']}")
    seeds = Seeds(seed)

    off_fused = 0
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", RuntimeWarning)
        with span("make_data"):
            app = app_mod.Cell(cfg, traffic, seeds)
        with span("solve"):
            jax.block_until_ready(app.solve(app.job("warmup")))
        setup_compiles, setup_compile_s = counter.count, counter.secs
        gc.collect()

        log_dir = None
        if trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            # let the profiler reach the device before the window opens
            jax.block_until_ready(jax.numpy.zeros(8) + 1)
            time.sleep(0.5)
        n0 = counter.count
        rng = random.Random(seeds.int31("sample"))
        keep_n = int(traffic["check_solves"])
        kept, sizes = [], []
        with span("window"):
            counts0 = program_counters()
            t_start = time.perf_counter()
            i = 0
            while True:
                with span("make_data"):
                    job = app.job(i)
                with span("solve"):
                    res = app.solve(job)
                    with span("sync"):
                        jax.block_until_ready(res)
                t_end = time.perf_counter()
                sizes.append(app.size(res))
                # reservoir sample of the window's answers, drawn from the seed
                if len(kept) < keep_n:
                    kept.append((job, res))
                else:
                    r = rng.randrange(i + 1)
                    if r < keep_n:
                        kept[r] = (job, res)
                del job, res
                i += 1
                if t_end - t_start >= seconds:
                    break
            counts1 = program_counters()
        counts = {k: v - counts0.get(k, 0) for k, v in counts1.items()}
        compiles_in_window = counter.count - n0
        summary = None
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as trace_mod

            summary = trace_mod.summarize(trace_mod.find_xplane(log_dir))
            shutil.rmtree(log_dir, ignore_errors=True)
        off_fused = sum("VMEM" in str(w.message) for w in caught
                        if issubclass(w.category, RuntimeWarning))
    device["memory_peak_bytes"] = _memory_peak(devs)
    n = len(sizes)
    setup_s = t_start - t0
    _emit({"record": "setup", "workload": workload, "seed": seed, "setup_s": setup_s,
           "compiles": setup_compiles, "compile_s": setup_compile_s}, out)
    _emit({"record": "window", "solves": n, "window_s": t_end - t_start,
           "compiles_in_window": compiles_in_window, "solves_off_fused_path": off_fused,
           "output_sizes": sorted({s for s in sizes if s is not None}),
           "counters": counts,
           "memory_peak_bytes": device["memory_peak_bytes"]}, out)

    ev = {
        "setup_s": setup_s, "window_s": t_end - t_start, "solves": n,
        "solve_s": (t_end - t_start) / n, "compiles_in_window": compiles_in_window,
        "work": app.work(sizes), "peaks": peaks(device["kind"]), "trace": summary,
        "counters": counts,
    }
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        v = reader(m["name"])(ev)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if summary is not None:
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9

    # the check: after the window, with the program's outputs but the sampled ones freed
    t_check = time.perf_counter()
    with span("check"):
        readings = [app.check(job, res) for job, res in kept]
    # a limit the configuration states (a guarantee's residual) holds in
    # every cell of it; the others are the traffic's, set from readings
    limits = {**cfg.get("limits", {}), **traffic["limits"]}
    worst: dict = {}
    failed = 0
    for per in readings:
        failed += any(name in limits and v > limits[name] for name, v in per.items())
        for name, v in per.items():
            worst[name] = max(worst.get(name, v), v)
    _emit({"record": "check", "sampled": len(kept), "readings": worst,
           "check_s": time.perf_counter() - t_check}, out)
    checks = {name: {"value": worst[name], "limit": limits[name]}
              for name in sorted(worst) if name in limits}
    missing = sorted(set(limits) - set(worst))
    correct = bool(kept) and failed == 0 and not missing
    result = {"correct": correct, "attempted": n, "failed": failed, "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
    result["checks"] = checks
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}", file=err, flush=True)
    for name in missing:
        print(f"check {name}: not read, limit {limits[name]!r} FAIL", file=err, flush=True)
    _emit(result, out)
    return result
