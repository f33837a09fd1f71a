"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / (window), in percent."""


def read(run):
    if run.profile is None or run.profile.window_ns <= 0:
        return None
    return 100.0 * run.profile.idle_share
