"""Device milliseconds per join in the ε-join's compaction (the stage
``simjoin_compact``: its jit's modules in the trace), from the trace."""
from bench.harness import module_ms_per_solve


def read(ev):
    return module_ms_per_solve(ev, "simjoin_compact")
