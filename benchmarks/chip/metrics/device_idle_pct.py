"""Share of the profiled window in which no operation ran on the device:
1 − (union of device-op intervals) / window."""


def read(r):
    if r.profile is None or r.profile.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.profile.busy_s / r.profile.window_s)
