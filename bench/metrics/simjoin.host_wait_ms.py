"""Idle device milliseconds per join in the gaps that a ``simjoin.*``
span of the program labels: the device waiting on the ε-join's host
steps (the Hilbert order, the schedule's build, the totals' download,
the emission table), from the trace.  None where no such span labels a
gap."""


def read(ev):
    tr = ev.get("trace")
    if tr is None or not ev.get("solves"):
        return None
    ns = tr.gap_ns_labelled("simjoin.")
    return ns / 1e6 / ev["solves"] if ns > 0 else None
