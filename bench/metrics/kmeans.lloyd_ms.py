"""Device milliseconds per fit in the fused Lloyd kernel (the
``pallas_call`` named ``kmeans_lloyd_fused``), from the trace."""
from bench.harness import device_ms_per_solve


def read(ev):
    return device_ms_per_solve(ev, "kmeans_lloyd_fused")
