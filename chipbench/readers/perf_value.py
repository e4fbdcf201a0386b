"""One value of ``GET /debug/perf`` as it stood ``at`` the window's open
or close."""

from _common import dig


def read(run, path: str, at: str = "close"):
    v = dig(run["perf_" + at], path)
    return None if v is None else float(v)
