"""Device milliseconds per join in pass 1 of the ε-join (the
``pallas_call`` named ``simjoin_hits``), from the trace."""
from bench.harness import device_ms_per_solve


def read(ev):
    return device_ms_per_solve(ev, "simjoin_hits")
