"""Programs JAX compiled (or read from its persistent cache) inside the
measured window: its ``backend_compile_duration`` events.  A warm run
reads 0."""


def read(ev):
    return ev.get("compiles_in_window")
