"""Lloyd's least time on this chip, from the fit's shapes
(``bench/work/kmeans.py``) and the chip's peaks, over the wall time per
fit: a share of the roofline that no scheduling of the same work can
pass."""
from bench.harness import roofline_percent


def read(ev):
    return roofline_percent(ev)
