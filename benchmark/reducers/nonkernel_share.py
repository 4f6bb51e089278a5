"""Device time outside the Mosaic (Pallas) kernels, as a share of the
traced window: busy time minus the kernels' events."""


def read(sources):
    trace = sources.get("trace")
    if trace is None or not trace.device_planes():
        return None
    kernels = trace.kernels()
    if not kernels:
        return None  # no kernel found: nothing to read
    busy, window = trace.busy_and_window()
    return 100.0 * (busy - sum(t for _, _, t in kernels)) / window
