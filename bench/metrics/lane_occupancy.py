"""Share of the sweep's lockstep lane-iterations that did work: the sum of
every lane's events over (lanes x the bucket's most events), over all
buckets of the window.  A count of the program's own results; it repeats
exactly for one seed."""


def read(view):
    busy = slots = 0
    for r in view.records:
        ev = r.get("events")
        if not isinstance(ev, list) or not ev:
            return None
        busy += sum(ev)
        slots += len(ev) * max(ev)
    return busy / slots if slots else None
