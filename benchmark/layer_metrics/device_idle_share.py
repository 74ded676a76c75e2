"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""


def read(run, params):
    red = run.reduction
    if not red.device_events or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
