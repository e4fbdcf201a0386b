"""One ring of ``GET /debug/perf`` (``windows``, ``steps``, ``loop``,
``compiles``): a reduction, times ``scale``, of one ``field`` over the
entries stamped (``at_unix``) inside the measured window. A program
that keeps no such ring, or no such field in it, reads as nothing."""

from _common import in_window, reduce_values


def read(run, ring: str, field: str, reduction: str = "p50",
         scale: float = 1.0):
    values = [e[field] for e in run["perf_close"].get(ring) or []
              if field in e and in_window(run, e["at_unix"])]
    value = reduce_values(values, reduction)
    return None if value is None else scale * value
