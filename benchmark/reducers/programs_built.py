"""Programs built during set-up: compiled, or fetched from the
persistent cache (each is a build the start pays for)."""


def read(sources):
    return float(sources["compile_log"].setup)
