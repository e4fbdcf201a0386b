"""From ``GET /debug/traces`` of the engine or the router: a percentile
over the ok requests that started in the window of one phase's
milliseconds, or (``self_less``) of the request's total less the named
phases: a layer's self time."""

from _common import in_window, reduce_values


def read(run, side: str, reduction: str, phase: str = None,
         self_less=None):
    values = []
    for t in run[side + "_traces"]["traces"]:
        if t["status"] != "ok" or not in_window(run, t["started_at"]):
            continue
        phases = {}
        for s in t["spans"]:
            if s["kind"] == "phase":
                phases[s["name"]] = phases.get(s["name"], 0.0) \
                    + s["duration_ms"]
        if self_less is not None:
            values.append(t["duration_ms"]
                          - sum(phases.get(p, 0.0) for p in self_less))
        elif phase in phases:
            values.append(phases[phase])
    return reduce_values(values, reduction)
