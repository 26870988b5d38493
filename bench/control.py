"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--control]

For each seed: make the cell's data as a run does, solve the first job
of the window through the entry point (the program) or, with
``--control``, through the plain reference computed one precision lower
(``"high"``, three bf16 passes, for the float32 the configurations
state), put in the program's place; then compare the answer as a run's
check does.  One JSON line per seed.  The benchmark's runs never call
this; it is the measurement behind each limit in ``bench/traffic``.
"""
import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_solver(app: str, cell):
    """The reference at ``"high"``, with the entry point's signature."""
    if app == "kmeans":
        from bench.reference import kmeans as ref

        return lambda seed: ref.lloyd(cell.x, cell.k, cell.iters, seed, precision="high")
    from bench.reference import simjoin as ref

    return lambda x: ref.pairs(x, cell.eps2, precision="high")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import importlib

    import jax

    from bench import harness

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.resolve(harness.load_spec(), args.workload)
    app = spec["config"]["app"]
    mod = importlib.import_module(f"bench.apps.{app}")
    for seed in (int(s) for s in args.seeds.split(",")):
        seeds = harness.Seeds(seed)
        cell = mod.Cell(spec["config"], spec["traffic"], seeds)
        solve = control_solver(app, cell) if args.control else cell.solve
        job = cell.job(0)
        t = time.perf_counter()
        out = jax.block_until_ready(solve(job))
        solve_s = time.perf_counter() - t
        t = time.perf_counter()
        reading = cell.check(job, out)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": "control" if args.control else "program",
                          "solve_s": solve_s, "check_s": time.perf_counter() - t,
                          "readings": reading}), flush=True)
        del cell, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
