"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit, also printed as the last lines of standard
error.  Earlier lines record set-up (compiles and their seconds), the
window (solves, compiles inside it, solves that left the fused path,
the program's counters over the window, peak device memory) and the
check's readings.

It measures the chip it runs on and nothing else: without a TPU, with
fewer chips than the cell asks for, on a device kind missing from
``bench/peaks.json``, or without the program beside it, it exits
non-zero and prints no result.  JAX's persistent compilation cache is
kept in ``.jax_cache`` at the root of the checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # before JAX starts: its compile cache inside the checkout, at a
    # fixed path, and the TPU runtime's logs off (their default is a
    # fixed directory outside it)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import jax

        from bench import harness
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
