"""``hbm_peak_share``: 100 x peak bytes in use over the bytes limit, on
the fullest chip (``memory_stats()`` through ``GET /debug/perf``
``device``)."""


def read(run):
    shares = [100.0 * d["peak_bytes_in_use"] / d["bytes_limit"]
              for d in run["perf_close"]["device"]["engine_devices"]
              if d.get("peak_bytes_in_use") and d.get("bytes_limit")]
    return max(shares) if shares else None
