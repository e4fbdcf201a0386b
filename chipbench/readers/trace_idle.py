"""``device_idle_share``: 100 x (1 - busy / window) of the traced
interval: the union of the device's operation intervals over the
trace's length, averaged over the chips used."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("window_s") or "busy_s" not in t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
