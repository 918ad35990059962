"""Device microseconds per simulated event of the one-shot engine
(``jit__simulate_jit``) spent in one of its named scopes: the leaf
operations whose innermost scope is the variant (``advance``,
``schedule``, ...), over the engine events of the traced questions."""

from lib import program_trace, trace


def read(view):
    if view.raw_trace is None:
        return None
    by_scope = program_trace.scope_seconds(
        view.raw_trace, trace.EXECUTABLES["engine_run"])
    events = view.traced.get("engine_events")
    if not by_scope or view.variant not in by_scope or not events:
        return None
    return by_scope[view.variant] / events * 1e6
