"""Device milliseconds of an executable, from the reduced device trace.

The executables are told apart by the kernel they run (``kernel``: an
operation's base name, e.g. ``paged_decode_attention``), because the
program gives its step functions no name yet. ``per`` says of what:

``dispatch``  the median run of the executable (of the one with most
              runs, where several run the kernel)
``step``      all runs' device seconds over all steps, where one step
              calls the kernel once per layer (``num_hidden_layers`` of
              the configuration): the mean over every geometry traced
"""

import json


def modules_with(run, kernel: str):
    if not run.get("trace") or "modules" not in run["trace"]:
        return []
    return [m for m in run["trace"]["modules"].values()
            if kernel in m.get("ops", {})]


def read(run, kernel: str, per: str = "dispatch"):
    mods = modules_with(run, kernel)
    if not mods:
        return None
    if per == "dispatch":
        return 1e3 * max(mods, key=lambda m: m["runs"])["median_s"]
    with open(run["config_file"]) as f:
        layers = json.load(f)["num_hidden_layers"]
    steps = sum(m["ops"][kernel][0] for m in mods) / layers
    return 1e3 * sum(m["total_s"] for m in mods) / steps if steps else None
