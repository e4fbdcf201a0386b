"""From ``GET /debug/traces`` of the engine or the router: a percentile
over the ok requests that started in the window of one EVENT span's
milliseconds (summed where a request carries several of the name).
Event spans lie inside the phases and are not part of their sum; a
program that writes no such event reads as nothing."""

from _common import in_window, reduce_values


def read(run, event: str, reduction: str = "p50", side: str = "engine"):
    values = []
    for t in run[side + "_traces"]["traces"]:
        if t["status"] != "ok" or not in_window(run, t["started_at"]):
            continue
        ms = [s["duration_ms"] for s in t["spans"]
              if s["kind"] == "event" and s["name"] == event]
        if ms:
            values.append(sum(ms))
    return reduce_values(values, reduction)
