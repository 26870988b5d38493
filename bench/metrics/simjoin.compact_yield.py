"""Share of the mask cells the ε-join's compaction scans that hold a
pair: the program's counters ``simjoin.pairs_out`` over
``simjoin.mask_cells_scanned``, both counted by the compaction's own
call.  The counters are read when the run ends, so the value covers
every join of the run, the warm-up's too, not the window's alone; in a
cell whose joins are one point set in other orders, the two are the
same.  None where the program keeps no such counters."""


def read(ev):
    try:
        from repro.core.tracing import counters
    except ImportError:
        return None
    c = counters()
    if not c.get("simjoin.mask_cells_scanned"):
        return None
    return 100.0 * c.get("simjoin.pairs_out", 0) / c["simjoin.mask_cells_scanned"]
