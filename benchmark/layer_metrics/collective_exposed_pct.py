"""Time in which a collective runs on a device and nothing else does, over
the traced window; median over devices."""


def read(sources):
    tr = sources.get("trace")
    if not tr or sources.get("chips", 1) < 2 or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
