"""Device milliseconds per join in the ε-join's schedule build (the
stage ``simjoin_schedule``: its jit's modules in the trace), from the
trace.  None where no such stage ran."""
from bench.harness import module_ms_per_solve


def read(ev):
    return module_ms_per_solve(ev, "simjoin_schedule")
