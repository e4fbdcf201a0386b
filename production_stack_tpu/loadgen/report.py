"""Aggregation and reporting.

Two committed shapes:

- BENCH-schema JSON (the shape of the root-level ``BENCH_*.json`` records):
  ``{"metric", "value", "unit", "platform", "detail": {...}}`` — one
  headline number plus full methodology in ``detail``.
- ``SCALEOUT_*.json`` — the replicas → aggregate tokens/s curve with
  per-point summaries and scaling efficiency vs N=1 (BASELINE config 2).
"""

import json
import platform as _platform
import time
from typing import Dict, Iterable, List, Optional, Sequence

from production_stack_tpu.loadgen.client import RequestRecord


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile on an unsorted sequence; 0.0 if empty."""
    if not values:
        return 0.0
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return s[idx]


class LatencyRecordSet:
    """Mergeable raw-sample latency set: merge-then-quantile.

    The one legal way to combine latency measurements from multiple
    phases or workers is to merge the RAW samples and take quantiles of
    the union — averaging per-worker percentiles is statistically
    meaningless (the mean of two p99s is not the p99 of anything).
    This class is the enforcement point: workers ship their samples
    (``to_dict``/``from_dict`` round-trip through worker JSONL),
    coordinators ``merge`` and only then read ``quantiles``.

    Samples accumulate via ``add``/``add_samples`` (streaming: a
    coordinator can fold worker record files in one pass without
    holding RequestRecords), and quantiles are computed on demand with
    the same nearest-rank ``percentile`` every committed record uses.
    """

    def __init__(self) -> None:
        self.ttft_s: List[float] = []
        self.itl_s: List[float] = []
        self.e2e_s: List[float] = []
        self.count = 0                   # ok records folded in

    @classmethod
    def from_records(cls, records: Iterable[RequestRecord]
                     ) -> "LatencyRecordSet":
        s = cls()
        for r in records:
            s.add(r)
        return s

    def add(self, rec: RequestRecord) -> None:
        """Fold one OK record's raw samples in (errors/aborts carry no
        latency truth and are counted elsewhere)."""
        if not rec.ok:
            return
        self.count += 1
        self.ttft_s.append(rec.ttft_s)
        self.e2e_s.append(rec.e2e_s)
        self.itl_s.extend(rec.itl_s)

    def add_samples(self, *, ttft_s: Sequence[float] = (),
                    itl_s: Sequence[float] = (),
                    e2e_s: Sequence[float] = (), count: int = 0) -> None:
        self.ttft_s.extend(ttft_s)
        self.itl_s.extend(itl_s)
        self.e2e_s.extend(e2e_s)
        self.count += count

    def merge(self, other: "LatencyRecordSet") -> "LatencyRecordSet":
        """Fold another worker/phase's raw samples in (in place)."""
        self.add_samples(ttft_s=other.ttft_s, itl_s=other.itl_s,
                         e2e_s=other.e2e_s, count=other.count)
        return self

    def quantiles(self) -> Dict:
        """The percentile sub-dicts every summary/record shape carries —
        computed from the merged raw samples, never from per-shard
        percentiles."""
        ttfts, itls, e2es = self.ttft_s, self.itl_s, self.e2e_s
        return {
            "ttft_s": {"mean": round(sum(ttfts) / len(ttfts), 4)
                       if ttfts else 0.0,
                       "p50": round(percentile(ttfts, 50), 4),
                       "p90": round(percentile(ttfts, 90), 4),
                       "p99": round(percentile(ttfts, 99), 4)},
            "itl_s": {"mean": round(sum(itls) / len(itls), 4)
                      if itls else 0.0,
                      "p99": round(percentile(itls, 99), 4)},
            "e2e_s": {"p50": round(percentile(e2es, 50), 4),
                      "p99": round(percentile(e2es, 99), 4)},
        }

    def to_dict(self) -> Dict:
        """Raw-sample transport shape (worker -> coordinator). Ships
        samples, not summaries, so the receiver can merge-then-quantile."""
        return {"count": self.count,
                "ttft_s": [round(v, 6) for v in self.ttft_s],
                "itl_s": [round(v, 6) for v in self.itl_s],
                "e2e_s": [round(v, 6) for v in self.e2e_s]}

    @classmethod
    def from_dict(cls, d: Dict) -> "LatencyRecordSet":
        s = cls()
        s.add_samples(ttft_s=d.get("ttft_s", ()),
                      itl_s=d.get("itl_s", ()),
                      e2e_s=d.get("e2e_s", ()),
                      count=int(d.get("count", 0)))
        return s


def aggregate(records: List[RequestRecord],
              window_start: Optional[float] = None,
              window_end: Optional[float] = None) -> Dict:
    """Summary metrics over records launched inside the window
    (semantics match benchmarks/multi_round_qa/summary.py: offered QPS
    counts launches; throughput counts finished tokens over the wall
    window)."""
    if window_start is None:
        window_start = min((r.launch_time for r in records), default=0.0)
    if window_end is None:
        window_end = max((r.finish_time for r in records),
                         default=window_start)
    in_window = [r for r in records
                 if window_start <= r.launch_time <= window_end]
    ok = [r for r in in_window if r.ok and r.finish_time <= window_end]
    errors = [r for r in in_window if r.error is not None]
    aborted = [r for r in in_window if r.aborted]
    cancelled = [r for r in in_window if r.cancelled]
    duration = max(window_end - window_start, 1e-9)
    latencies = LatencyRecordSet.from_records(ok)
    kinds: Dict[str, int] = {}
    for r in in_window:
        kinds[r.kind] = kinds.get(r.kind, 0) + 1
    # first few distinct error strings: a run that produced only zeros
    # must explain itself in its own report
    error_samples: List[str] = []
    seen = set()
    for r in errors:
        key = (r.error or "")[:120]
        if key not in seen:
            seen.add(key)
            error_samples.append(key)
        if len(error_samples) >= 5:
            break
    return {
        "duration_s": round(duration, 3),
        "launched": len(in_window),
        "finished": len(ok),
        "errors": len(errors),
        "http_5xx": len([r for r in errors if r.status >= 500]),
        "aborted_injected": len(aborted),
        "cancelled_by_harness": len(cancelled),
        "offered_qps": round(len(in_window) / duration, 4),
        "processed_qps": round(len(ok) / duration, 4),
        "input_tokens_per_s": round(
            sum(r.prompt_tokens for r in ok) / duration, 2),
        "output_tokens_per_s": round(
            sum(r.output_tokens for r in ok) / duration, 2),
        "total_output_tokens": sum(r.output_tokens for r in ok),
        **latencies.quantiles(),
        "requests_by_kind": kinds,
        "error_samples": error_samples,
    }


def bench_schema(metric: str, agg: Dict, *, platform: str = "cpu",
                 detail: Optional[Dict] = None) -> Dict:
    """Wrap an aggregate into the BENCH_*.json record shape so driver
    tooling that scrapes those records can scrape loadgen output
    unchanged."""
    d = dict(agg)
    d.update(detail or {})
    return {
        "metric": metric,
        "value": agg["output_tokens_per_s"],
        "unit": "out_tok/s",
        "platform": platform,
        "detail": d,
    }


def scaleout_record(*, engine: str, routing: str, workload: str,
                    points: List[Dict], platform: str = "cpu",
                    notes: str = "") -> Dict:
    """The SCALEOUT_*.json shape: one point per replica count, each
    carrying its full aggregate; efficiency is tokens/s relative to
    perfect linear scaling from the N=1 point."""
    base = next((p for p in points if p["replicas"] == 1), None)
    for p in points:
        if base and base["output_tokens_per_s"] > 0:
            ideal = base["output_tokens_per_s"] * p["replicas"]
            p["scaling_efficiency"] = round(
                p["output_tokens_per_s"] / ideal, 4)
        else:
            p["scaling_efficiency"] = None
    return {
        "metric": "aggregate output tokens/s vs replicas "
                  "(DP scale-out through the router)",
        "engine": engine,
        "routing": routing,
        "workload": workload,
        "platform": platform,
        "host": _platform.node(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "points": points,
        "notes": notes,
    }


def write_json(path: str, obj: Dict) -> str:
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)
        f.write("\n")
    return path
