"""Device milliseconds of the vmapped sweep bucket (the ``jit_fn`` program
in the trace) per lockstep iteration (the bucket's most lane events)."""

from lib.trace import EXECUTABLES


def read(view):
    if view.trace is None:
        return None
    dev = view.trace["module_s"].get(EXECUTABLES["sweep_bucket"])
    iters = view.traced.get("lockstep_iters")
    if not dev or not iters:
        return None
    return dev / iters * 1e3
