"""Device microseconds per pass-1 grid step of the ε-join: the trace's
time in the ``pallas_call`` named ``simjoin_hits`` over the program's
counter ``simjoin.pass1_steps`` (grid steps dispatched), both over
the window.  None without either."""


def read(ev):
    tr = ev.get("trace")
    steps = (ev.get("counters") or {}).get("simjoin.pass1_steps")
    if tr is None or not steps:
        return None
    ns = tr.device_ns_matching("simjoin_hits")
    return ns / 1e3 / steps if ns > 0 else None
