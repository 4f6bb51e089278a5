"""Share of the traced window in which no operation ran on the device."""


def read(sources):
    trace = sources.get("trace")
    if trace is None or not trace.device_planes():
        return None
    busy, window = trace.busy_and_window()
    return 100.0 * (1.0 - busy / window)
