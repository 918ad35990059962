"""Device milliseconds per lockstep iteration of the vmapped sweep bucket
(``jit_fn``) spent in one of the engine's named scopes: the leaf
operations whose innermost scope is the variant (``advance``,
``failures``, ``schedule``, ``place``, ``log``), over the buckets' most
lane events."""

from lib import program_trace, trace


def read(view):
    if view.raw_trace is None:
        return None
    by_scope = program_trace.scope_seconds(
        view.raw_trace, trace.EXECUTABLES["sweep_bucket"])
    iters = view.traced.get("lockstep_iters")
    if not by_scope or view.variant not in by_scope or not iters:
        return None
    return by_scope[view.variant] / iters * 1e3
