"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 minus the union of device-op intervals over the window)."""


def read(run):
    r = run.reduced
    if r is None or r.window_s <= 0 or r.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
