"""Layer "device": share of the traced window in which no operation ran
on the device, in percent (1 − busy over the window)."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or not trace.n_devices or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
