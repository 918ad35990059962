"""Device microseconds of the engine per simulated event of the traced
questions.  The variant names the engine's program in the trace:
``engine_us_per_event.run`` reads the one-shot engine
(``jit__simulate_jit``) of ``run()`` questions, ``engine_us_per_event.replay``
the replay round (``jit_step``, the engine's ``simulate_window``) summed over
every round of the window's replays."""

from lib.trace import EXECUTABLES


def read(view):
    if view.trace is None:
        return None
    dev = view.trace["module_s"].get(EXECUTABLES["engine_" + view.variant])
    events = view.traced.get("engine_events")
    if not dev or not events:
        return None
    return dev / events * 1e6
