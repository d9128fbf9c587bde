"""Share of the traced window in which no operation ran on the device:
1 - (union of the device ops' intervals / the window)."""


def read(obs):
    window = obs.trace.window_ns()
    if window <= 0 or not obs.trace.device_ops:
        return None
    return 100.0 * (1.0 - obs.trace.busy_ns() / window)
