"""Share of pass 1's tile pairs that hold at least one pair: the
program's counters ``simjoin.tiles_live`` over ``simjoin.tile_pairs``,
as the window's joins counted them.  None where the program keeps no
such counters."""
from bench.harness import counter_percent


def read(ev):
    return counter_percent(ev, "simjoin.tiles_live", "simjoin.tile_pairs")
