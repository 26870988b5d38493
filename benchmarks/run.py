"""Benchmark harness — one module per paper table/figure.

  E1 bench_locality   — Fig. 1(e) cache-miss curves + reload economy
  E2 bench_codec      — §3/§5 coding & generation throughput
  E3 bench_matmul     — §1 matmul traffic model + kernel check
  E4 bench_apps       — §7 k-means / simjoin / FW / Cholesky
  E5 bench_attention  — §6.2 jump-over on causal attention
  E5b bench_mesh      — beyond-paper Hilbert ICI layout
  E6 bench_serving    — dense vs Hilbert-paged vs flash-paged decode
  E7 bench_apps_serving — streaming Lloyd / ε-join on the tick core
  E8 bench_autotune   — measured schedule choices: chosen vs default

Prints ``bench,name,value,derived`` CSV.  ``--json [PATH]`` additionally
records the rows as JSON (default ``BENCH_curves.json``) so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import sys
import time

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    from . import (
        bench_apps,
        bench_apps_serving,
        bench_attention,
        bench_autotune,
        bench_codec,
        bench_locality,
        bench_matmul,
        bench_mesh,
        bench_serving,
    )

    modules = [
        ("locality", bench_locality),
        ("codec", bench_codec),
        ("matmul", bench_matmul),
        ("apps", bench_apps),
        ("attention", bench_attention),
        ("mesh", bench_mesh),
        ("serving", bench_serving),
        ("apps_serving", bench_apps_serving),
        ("autotune", bench_autotune),
    ]
    args = sys.argv[1:]
    json_path = None
    if "--json" in args:
        # --json [PATH.json]: only a *.json token is taken as the path, so
        # a typo'd bench selector is never silently consumed as a filename
        i = args.index("--json")
        args.pop(i)
        json_path = "BENCH_curves.json"
        if i < len(args) and args[i].endswith(".json"):
            json_path = args.pop(i)
    selected = set(args)
    unknown = selected - {name for name, _ in modules}
    if unknown:
        print(f"# unknown bench(es): {sorted(unknown)}; "
              f"known: {[n for n, _ in modules]}", file=sys.stderr)
    print("bench,name,value,derived")
    t0 = time.time()
    collected: list[dict] = []
    run_counts: dict[str, int] = {}
    for name, mod in modules:
        if selected and name not in selected:
            continue
        n_before = len(collected)
        for row in mod.run():
            # every row carries the suite (module) that produced it — the
            # summary counts below are validated against this tag, so a
            # module emitting under a foreign "bench" label can't skew
            # another suite's trajectory silently
            row["suite"] = name
            collected.append(row)
            derived = str(row.get("derived", "")).replace(",", ";")
            print(f"{row['bench']},{row['name']},{row['value']},{derived}")
        run_counts[name] = len(collected) - n_before
    if json_path:
        if not collected:
            # an empty snapshot silently breaks the perf trajectory — fail
            # loudly instead of committing {"rows": []}
            print(f"# refusing to write {json_path}: 0 rows collected",
                  file=sys.stderr)
            sys.exit(1)
        # stable top-level summary so BENCH_*.json snapshots diff cleanly
        # across PRs: schema version, sorted suite names, per-suite row
        # counts.  "rows" stays the flat list earlier tooling reads.
        # Counted two independent ways — per module while running, and
        # from the per-row "suite" tags at write time — and the snapshot
        # is refused if they disagree (a row dropped, duplicated or
        # re-tagged between collection and serialisation).
        row_counts = {k: v for k, v in run_counts.items() if v}
        tag_counts: dict[str, int] = {}
        for row in collected:
            tag_counts[row["suite"]] = tag_counts.get(row["suite"], 0) + 1
        summary = {
            "schema_version": 4,
            "suites": sorted(row_counts),
            "row_counts": {k: row_counts[k] for k in sorted(row_counts)},
            "total_rows": len(collected),
        }
        if (
            tag_counts != summary["row_counts"]
            or sum(tag_counts.values()) != summary["total_rows"]
        ):
            print(f"# refusing to write {json_path}: summary/row mismatch "
                  f"{summary['row_counts']} vs {tag_counts}", file=sys.stderr)
            sys.exit(1)
        with open(json_path, "w") as f:
            json.dump({"summary": summary, "rows": collected}, f, indent=1)
        print(f"# wrote {json_path} ({len(collected)} rows, "
              f"{len(row_counts)} suites)", file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
