"""BENCHMARK.json, and how a name in it finds its file.

Whatever belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own:

    configuration  its ``file`` in the manifest (with the optional
                   ``harness`` key: chipbench/harness_key.py)
    traffic        chipbench/traffic/<traffic>.json
    cell           chipbench/cells/<workload>.json   (optional: rate,
                   restrictions of the warm-up)
    metric         chipbench/metrics/<metric>.json   (per-layer only)
    reader         chipbench/readers/<reader>.py
    reference      chipbench/references/<reference>.py

A name that finds no file is an error that names the file wanted.
``data_dirs`` (tests, rehearsals) are searched before ``chipbench/``.
"""

import json
import os
import re
from typing import Dict, List, Optional

from chipbench import harness_key

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def load(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"no such file: {path}") from None


def find(kind: str, name: str, data_dirs: List[str], ext: str = ".json",
         required: bool = True) -> Optional[str]:
    for base in [*data_dirs, HERE]:
        path = os.path.join(base, kind, name + ext)
        if os.path.exists(path):
            return path
    if required:
        raise ManifestError(
            f"{kind[:-1] if kind.endswith('s') else kind} {name!r}: "
            f"no file {kind}/{name}{ext} under "
            f"{[*data_dirs, 'chipbench']}")
    return None


class Cell:
    """One entry of ``workloads`` with everything its names resolve to."""

    def __init__(self, manifest: Dict, name: str, data_dirs: List[str],
                 root: str = ROOT):
        rows = {w["name"]: w for w in manifest["workloads"]}
        if name not in rows:
            raise ManifestError(f"no workload {name!r} in the manifest; "
                                f"it has {sorted(rows)}")
        row = rows[name]
        self.name, self.chips, self.why = name, row["chips"], row["why"]
        configs = {c["name"]: c for c in manifest["configs"]}
        if row["config"] not in configs:
            raise ManifestError(f"workload {name!r} names configuration "
                                f"{row['config']!r}, which the manifest "
                                f"does not list")
        self.config_entry = configs[row["config"]]
        self.config_file = os.path.join(root, self.config_entry["file"])
        self.config = load(self.config_file)
        try:    # a ``harness`` key the harness cannot read fails here
            harness_key.of(self.config)
        except ValueError as e:
            raise ManifestError(f"{self.config_entry['file']}: {e}") \
                from None
        self.traffic_name = row["traffic"]
        self.traffic = load(find("traffic", row["traffic"], data_dirs))
        cell_file = find("cells", name, data_dirs, required=False)
        self.params = load(cell_file) if cell_file else {}
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = []
        for m in manifest["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            spec = load(find("metrics", m["name"], data_dirs))
            for key in ("name", "unit", "layer", "moves", "source"):
                if spec.get(key) != m[key]:
                    raise ManifestError(
                        f"metrics/{m['name']}.json says {key}="
                        f"{spec.get(key)!r}, the manifest {m[key]!r}")
            find("readers", spec["reader"], data_dirs, ext=".py")
            self.per_layer.append(spec)
        find("references", self.config["reference"], data_dirs, ext=".py")


def problems(manifest: Dict, data_dirs: List[str], root: str = ROOT
             ) -> List[str]:
    """What in the manifest breaks the contract's form, or names a file
    that is not there ([] = nothing). The driver checks the same before
    any run; this is for the tests and for a builder adding a cell."""
    out = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        out.append(f"keys {sorted(manifest)} are not {sorted(want)}")
        return out
    names = [m["name"] for m in manifest["end_to_end"]
             + manifest["per_layer"]]
    for group in (names, [w["name"] for w in manifest["workloads"]],
                  [c["name"] for c in manifest["configs"]]):
        out += [f"name {n!r} twice" for n in set(group)
                if group.count(n) > 1]
    for n in names + [x for w in manifest["workloads"]
                      for x in (w["name"], w["config"], w["traffic"])]:
        if not NAME.fullmatch(n):
            out.append(f"name {n!r} has a character that is not allowed")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.fullmatch(m["unit"]):
            out.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"better={m['better']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            out.append(f"source {m['source']!r} of {m['name']}")
    for m in manifest["end_to_end"]:
        if not 0 < m["bound"] <= 0.1:
            out.append(f"bound {m['bound']} of {m['name']}")
    cells = [w["name"] for w in manifest["workloads"]]
    where = {m["name"]: set(m.get("workloads", cells))
             for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves {m['moves']!r}, which is no "
                       f"end-to-end metric")
        elif not set(m.get("workloads", cells)) <= where[m["moves"]]:
            out.append(f"{m['name']} is reported in cells where "
                       f"{m['moves']}, which it moves, is not")
    for cell in cells:
        if len([n for n, ws in where.items() if cell in ws]) < 2:
            out.append(f"cell {cell!r} reports no end-to-end metric "
                       f"besides setup_s")
    used = {w["config"] for w in manifest["workloads"]}
    out += [f"configuration {c['name']!r} is used by no cell"
            for c in manifest["configs"] if c["name"] not in used]
    for c in manifest["configs"]:
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in manifest["paths"]):
            out.append(f"file of {c['name']!r} is not under paths")
    for w in manifest["workloads"]:
        try:
            Cell(manifest, w["name"], data_dirs, root)
        except ManifestError as e:
            out.append(str(e))
    return out
