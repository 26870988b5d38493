"""The exact join's least time on this chip (read the points, write the
pairs: ``bench/work/simjoin.py``) over the wall time per join."""
from bench.harness import roofline_percent


def read(ev):
    return roofline_percent(ev)
