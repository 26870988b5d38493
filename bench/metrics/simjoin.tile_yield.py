"""Share of pass 1's tile pairs that hold at least one pair: the
program's counters ``simjoin.tiles_live`` over ``simjoin.tile_pairs``.
The counters are read when the run ends, so the value covers every join
of the run, the warm-up's too, not the window's alone; in a cell whose
joins are one point set in other orders, the two are the same.  None
where the program keeps no such counters."""


def read(ev):
    try:
        from repro.core.tracing import counters
    except ImportError:
        return None
    c = counters()
    if not c.get("simjoin.tile_pairs"):
        return None
    return 100.0 * c.get("simjoin.tiles_live", 0) / c["simjoin.tile_pairs"]
