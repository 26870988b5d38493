"""Seconds from the start of the process to the start of the window:
importing, making the data, the warm-up solve and any compilation."""


def read(ev):
    return ev["setup_s"]
