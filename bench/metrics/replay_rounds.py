"""Windowed rounds per replay (``ReplayResult.n_rounds``), the mean over the
window's replays; each round is one host harvest, refill and device call."""


def read(view):
    rounds = view.counters.get("replay_rounds")
    replays = view.counters.get("replays")
    if not rounds or not replays:
        return None
    return rounds / replays
