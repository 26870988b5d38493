"""Wall time of the window (first solve's start to the last completed
solve's end) over the solves completed in it."""


def read(ev):
    return ev["window_s"] / ev["solves"]
