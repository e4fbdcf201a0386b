"""What the harness has to know of a model's executables, and the
published ``config.json`` keys do not say: the optional ``harness`` key
of a configuration's file.

    "harness": {
      "kernel_tables": ["attention_paths"],
      "decode_step": {"op": "paged_decode_attention",
                      "calls_per_step": <num_hidden_layers>},
      "prefill_dispatch": {"op": "paged_attention"},
      "probe": {"logprob_gap_limit": 0.3, "mean_logprob_gap_limit": null}
    }

``kernel_tables``: the tables of ``GET /debug/perf`` ``device`` that
``correct`` holds every compiled executable to (``kernels_off``).
``decode_step`` / ``prefill_dispatch``: the operation that tells the
decode and the prefill executables apart in a device trace (the program
gives its step functions no name the trace reader sees), and how often
one decode step calls it (``readers/trace_module.py``).
``probe``: the limits ``correct`` holds the logit probe to
(``chipbench/reference.py``); ``null`` = that number is reported and not
compared, and one of the two has to be a number. ``logprob_gap_limit``:
the widest |served - reference| over ONE prompt's served top-20
log-probabilities. ``mean_logprob_gap_limit``: the mean of that gap over
every served log-probability of the run's prompts. A configuration
states its own where its two readings (``chipbench/probe_seeds.py``: the
program's largest over a dozen seeds, the lower-precision control's
smallest) do not stand three times apart on the default's number, or lie
elsewhere than 0.3 supposes; PERF.md section 2 gives the readings beside
each limit. Above are the
defaults, taken where the key or a part of it is absent: a softmax
attention call over a paged pool in every layer. THIS FILE IS THE ONLY
PLACE where the harness names an operation or a table of the program.
"""

import json
from typing import Dict

KINDS = ("decode_step", "prefill_dispatch")
# chipbench/reference.py says where the default came from (PR 21-23)
PROBE_GAP_LIMIT = 0.3


def defaults(hf: Dict) -> Dict:
    return {"kernel_tables": ["attention_paths"],
            "decode_step": {"op": "paged_decode_attention",
                            "calls_per_step": hf["num_hidden_layers"]},
            "prefill_dispatch": {"op": "paged_attention"},
            "probe": {"logprob_gap_limit": PROBE_GAP_LIMIT,
                      "mean_logprob_gap_limit": None}}


def on_kernel(table: str, path: str) -> bool:
    """Does ``path``, an executable's entry in ``table``, name a
    kernel? The program's names: ``ops/pallas_paged.attention_path``
    answers ``pallas_paged*`` or ``jnp_*``; every other table marks its
    ``jax.numpy`` fallback by a name that ends in ``_jnp``."""
    if table == "attention_paths":
        return path.startswith("pallas_paged")
    return not (path.startswith("jnp_") or path.endswith("_jnp"))


def of(hf: Dict) -> Dict:
    """The ``harness`` key of a configuration (the file's content),
    defaults filled in; ValueError on a key it does not know."""
    out, given = defaults(hf), hf.get("harness", {})
    unknown = set(given) - set(out)
    for kind in KINDS:
        part = given.get(kind, {})
        unknown |= {f"{kind}.{k}" for k in set(part)
                    - {"op", "calls_per_step"}}
        out[kind].update(part)
    probe = given.get("probe", {})
    unknown |= {f"probe.{k}" for k in set(probe) - set(out["probe"])}
    out["probe"].update(probe)
    if unknown:
        raise ValueError(f"harness key of {hf.get('name')!r}: unknown "
                         f"{sorted(unknown)}")
    limits = list(out["probe"].values())
    if not all(v is None or (isinstance(v, (int, float))
                             and not isinstance(v, bool) and v > 0)
               for v in limits) or limits == [None, None]:
        raise ValueError(f"harness key of {hf.get('name')!r}: probe "
                         f"{out['probe']!r} is no limit")
    tables = given.get("kernel_tables", out["kernel_tables"])
    if not tables or not all(isinstance(t, str) and t.endswith("_paths")
                             for t in tables):
        raise ValueError(f"harness key of {hf.get('name')!r}: "
                         f"kernel_tables {tables!r} names no table")
    out["kernel_tables"] = list(tables)
    return out


def read(config_file: str) -> Dict:
    with open(config_file) as f:
        return of(json.load(f))


def kernels_off(device: Dict, harness: Dict) -> Dict[str, str]:
    """What of ``correct``'s kernel clause does not hold ({} = it
    holds): the executables are the union of the keys of the program's
    path tables (the keys of ``device`` that end in ``_paths``); each
    must be named in every table of ``kernel_tables`` (absent = empty)
    by a path that is a kernel's. Keys say ``table[executable]``."""
    executables = sorted({name for key, table in device.items()
                          if key.endswith("_paths") for name in table})
    if not executables:
        return {"executables": "the program names none in any table"}
    off = {}
    for table in harness["kernel_tables"]:
        for name in executables:
            path = device.get(table, {}).get(name)
            if path is None:
                off[f"{table}[{name}]"] = "not named"
            elif not on_kernel(table, path):
                off[f"{table}[{name}]"] = path
    return off
