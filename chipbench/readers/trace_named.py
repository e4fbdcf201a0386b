"""Device seconds of the executables whose NAME contains ``contains``
(the program jits its step functions under names of their own:
``jit_prefill_chunk``, ``jit_decode_window``), as 100 x their share of
the device's busy seconds while traced. A program whose executables
carry no such name (they were all ``jit__unknown``) reads as
nothing."""


def read(run, contains: str):
    t = run.get("trace")
    if not t or not t.get("busy_s") or "modules" not in t:
        return None
    seconds = [m["total_s"] for name, m in t["modules"].items()
               if contains in name]
    if not seconds:
        return None
    # total_s sums over the device planes, busy_s is their average
    return 100.0 * sum(seconds) / t.get("device_planes", 1) / t["busy_s"]
