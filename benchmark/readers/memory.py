"""Device memory in use at its peak over the device's limit, percent."""


def read(ctx):
    m = ctx["memory"]
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["memory_peak_bytes"] / m["bytes_limit"]
