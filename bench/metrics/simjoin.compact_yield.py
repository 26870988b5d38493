"""Share of the mask cells the ε-join's compaction scans that hold a
pair: the program's counters ``simjoin.pairs_out`` over
``simjoin.mask_cells_scanned``, both counted by the compaction's own
call, as the window's joins counted them.  None where the program keeps
no such counters."""
from bench.harness import counter_percent


def read(ev):
    return counter_percent(ev, "simjoin.pairs_out", "simjoin.mask_cells_scanned")
