"""Device milliseconds of an executable, from the reduced device trace.

The executables are told apart by an operation they run, because the
program gives its step functions no name yet. Which operation, the
configuration's file says (``kind``: ``decode_step`` or
``prefill_dispatch`` of its ``harness`` key, chipbench/harness_key.py);
the readers of one family's rooflines name the operation themselves
(``module_ms``, ``modules_with``). ``per`` says of what:

``dispatch``  the median run of the executable (of the one with most
              runs, where several run the operation)
``step``      all runs' device seconds over all steps, where one step
              calls the operation ``calls_per_step`` times (the file's
              ``harness.decode_step.calls_per_step``; absent: once a
              layer, ``num_hidden_layers``): the mean over every
              geometry traced
"""

from chipbench import harness_key


def modules_with(run, op: str):
    if not run.get("trace") or "modules" not in run["trace"]:
        return []
    return [m for m in run["trace"]["modules"].values()
            if op in m.get("ops", {})]


def module_ms(run, op: str, per: str = "dispatch", calls_per_step=None):
    """``calls_per_step`` None: the configuration's, of its decode
    step."""
    mods = modules_with(run, op)
    if not mods:
        return None
    if per == "dispatch":
        return 1e3 * max(mods, key=lambda m: m["runs"])["median_s"]
    if calls_per_step is None:
        calls_per_step = harness_key.read(
            run["config_file"])["decode_step"]["calls_per_step"]
    steps = sum(m["ops"][op][0] for m in mods) / calls_per_step
    return 1e3 * sum(m["total_s"] for m in mods) / steps if steps else None


def read(run, kind: str, per: str = "dispatch"):
    spec = harness_key.read(run["config_file"])[kind]
    return module_ms(run, spec["op"], per, spec.get("calls_per_step"))
