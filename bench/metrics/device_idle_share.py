"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals / window).  One reader for every
variant (``device_idle_share.jobs``, ``.query``, ...): the variant only
names the end-to-end metric the share moves."""


def read(view):
    if view.trace is None or view.trace["window_s"] <= 0:
        return None
    return 1.0 - view.trace["busy_s"] / view.trace["window_s"]
