"""Share of the lower-triangle tile pairs that the ε-join's schedule
keeps (their bounding boxes lie within ε): the program's counters
``simjoin.tile_pairs`` over ``simjoin.tri_pairs``, as the window's joins
counted them.  None where the program keeps no such counters."""
from bench.harness import counter_percent


def read(ev):
    return counter_percent(ev, "simjoin.tile_pairs", "simjoin.tri_pairs")
