"""Engine efficiency accounting: hardware-level attribution for the
step loop (roofline accounting, compile tracking, window waste).

The aggregate loops this stack has closed measure *requests*; r13's
tracing attributes *request* wall time to phases. What neither says is
where the **device's** time and bandwidth go: a fused decode window
always computes ``max_num_seqs x decode_window`` token positions, but
only the live, still-generating rows' positions are useful — parked
slots (padding rows), finished rows' tails, and rejected speculative
drafts burn the same HBM traffic and emit nothing. This module is the
measure-before-optimize substrate for the roofline push (ROADMAP item
2) and the fragmentation work (item 3): every decode window and prefill
dispatch is classified into real / pad / dead token-steps, rolled into
effective-bandwidth and MBU estimates against the device's HBM peak
(HBM_PEAK_GBPS, by ``device_kind``), and every XLA compile is stamped (kind, window, kv bucket, duration) so
a compile-stalled serving window is attributable instead of invisible.

Design constraints (the r13 rules, verbatim):

- **Hot-loop cost ~zero.** The engine calls ``note_window`` once per
  fused window and ``note_prefill`` once per prefill bucket group —
  plain-int adds and one bounded-ring append per *window* (never per
  token), under a lock that is only ever held for those adds (never
  across a compile or dispatch). No prometheus objects anywhere near
  the loop: the exposition reads totals at scrape time and advances
  counters by deltas (``EngineMetrics.sync_eff``).
- **Bounded.** Window breakdowns and compile events live in
  ``collections.deque(maxlen=...)`` rings served on ``GET /debug/perf``.
- **Lock-free-ish reads.** ``perf_block()`` (the ``/load`` ``perf``
  block) must answer while the engine lock is held across a
  multi-second compile — it takes only this module's micro-lock.

The byte model is deliberately simple and documented (docs/engine.md
"Efficiency telemetry"): one decode step streams the full weight set
once plus, for every batch row, the KV prefix up to the window's kv
bucket; a mixture of experts less the experts its steps' lists left
out (``note_window(experts_read=)``; ops/moe.py, the list path). Effective bytes are total bytes scaled by the window's live
fraction; MBU is effective bytes/s over the device's peak — looked up
by ``device_kind`` (or ``--hbm-peak-gbps``), and reported as absent
(``None``) for a device the table does not know: a CPU's or an unknown
chip's bytes/s are never divided by another device's peak. The
*fractions* (live/pad/dead) are exact on any device.
"""

import collections
import os
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from production_stack_tpu import IMPORTED_UNIX as _IMPORTED_UNIX

# XLA compile durations (seconds): compiles are seconds-scale events,
# not milliseconds — a distinct bucket ladder from PHASE_BUCKETS
COMPILE_BUCKETS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

# Peak HBM bandwidth in GB/s by ``jax.Device.device_kind`` — the ONE
# peaks table; a kind that is not here has no MBU. Source: Google Cloud
# documentation, "TPU v5e" system architecture (819 GB/s per chip).
HBM_PEAK_GBPS: Dict[str, float] = {
    "TPU v5 lite": 819.0,
}


# KV-pool occupancy observed at allocation time (fraction of non-trash
# blocks held by live sequences)
OCCUPANCY_BUCKETS: Tuple[float, ...] = (
    0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)

# The step timeline's phases (docs/observability.md "Step timeline"):
# every second of the engine thread, from its first step on, is booked
# under exactly one of them. The first thirteen partition one step()
# (``housekeeping`` also takes what a step spends outside any other
# phase); ``between_steps`` and ``no_work`` lie outside the engine
# lock; ``compile`` is what an XLA compile took out of the phase it
# fell in.
STEP_PHASES: Tuple[str, ...] = (
    "expire", "schedule", "drain_sync", "drain_process",
    "prefill_host", "prefill_dispatch", "prefill_sync", "prefill_process",
    "decode_host", "decode_dispatch", "decode_sync", "decode_process",
    "housekeeping", "between_steps", "no_work", "compile")


# The phases in which the engine thread does its own work (the host's
# share of a step; chipbench's step_host_work_share sums the same):
# every phase but the three syncs, the wait for work and the compiles.
# Off-processor seconds inside them are seconds the thread wanted to
# run and could not; inside the others they are the device's.
HOST_WORK_PHASES: Tuple[str, ...] = tuple(
    p for p in STEP_PHASES
    if not p.endswith("_sync") and p not in ("no_work", "compile"))

# How often at most the timeline asks the device whether it has run dry
# (_look): on the chip one answer costs several microseconds, and a
# step closes thirty phases, most of them a few of those long
LOOK_EVERY_S = 0.001

# ``totals.step.dispatch_depth``: how many entries of the engine's
# device queue the device had not finished when a dispatch was made
DEPTH_KEYS: Tuple[str, ...] = ("0", "1", "2", "3_or_more")


# Why a prefill dispatch waited for the device queue to empty where it
# could not join it (engine._prefill_drains; docs/engine.md "The
# in-flight queue"): each names state that only the host holds.
DRAIN_REASONS: Tuple[str, ...] = (
    "guided", "shaped", "resume", "speculation", "reshape", "pressure")

# What a build is made of (docs/observability.md "Start-up and builds"):
# the ``jax.monitoring`` events JAX emits while it traces, lowers and
# compiles OR loads an executable, by the key their seconds are booked
# under. ``backend_s`` is the compile, or on a hit of the persistent
# cache the key, the read and the deserialisation.
BUILD_PARTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s"}
# the persistent cache was asked for an executable, and held it. (JAX's
# ``cache_misses`` says an entry was WRITTEN, which the cache's
# thresholds of seconds and bytes decide: asked and not held is a miss.)
CACHE_EVENTS: Dict[str, str] = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hits"}
CACHE_SECONDS: Dict[str, str] = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
# the seconds of ``totals.builds``
BUILD_SECONDS: Tuple[str, ...] = (
    "wall_s", "trace_s", "lower_s", "backend_miss_s", "backend_hit_s",
    "cache_load_s", "saved_s", "other_s")
# the marks of a start, in the order a server reaches them
STARTUP_MARKS: Tuple[str, ...] = (
    "main", "engine_built", "serving", "first_request")


def process_start(imported_unix: float) -> Tuple[float, str]:
    """When the operating system started this process, as a unix time,
    and where that was read: ``proc_stat`` (``/proc/self/stat``'s start
    time in clock ticks since boot, against CLOCK_BOOTTIME now, so no
    whole-second ``btime`` is in it). A launcher may import JAX and
    ask for the devices before the server's ``main`` runs, so no stamp
    taken by Python code can stand for it. Where the platform gives no
    start time, or one after ``imported_unix`` (the package's first
    import, which the process can only have reached later), the answer
    is that import and ``package_import``."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])         # field 22: starttime
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        started = time.time() - age
        if ticks > 0 and age >= 0 and started <= imported_unix + 0.5:
            return started, "proc_stat"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return imported_unix, "package_import"


class BuildEvents:
    """Where ``jax.monitoring``'s events go: the three functions the
    engine registers with it once a process (this module stays off
    JAX), and which accounting each event is booked to. While a build
    is open on the calling thread (EngineEffAccounting.compile_started
    to compile_finished, which ModelRunner._observed calls) what
    arrives belongs to that build; with none open it goes to
    ``unattributed`` of every accounting alive (a server has one), and
    while none is alive it is kept for the next (an engine draws its
    weights before its accounting exists).

    JAX's durations nest (tracing ``f`` traces the jitted functions
    ``f`` calls; lowering traces again), and a sum of them would count
    a second twice or more. JAX says when each begins (a scalar of the
    same name) before it says how long it took, so a stack a thread
    keeps what lay inside each: a part is booked its OWN seconds, and
    the parts of a build add up to no more than its wall.

    ``calls`` counts the calls JAX made into the three functions (by
    a plain add: near enough where two threads build at once, and
    exact where nothing builds): a step served from the runner's table
    makes none."""

    def __init__(self):
        self.calls = 0
        self._here = threading.local()
        self._live: "weakref.WeakSet[EngineEffAccounting]" = \
            weakref.WeakSet()
        # what arrived while no accounting was alive (an engine makes
        # its weights before its accounting): the next one's
        self._early = _new_unattributed()
        self._lock = threading.Lock()

    def follow(self, acct: "EngineEffAccounting") -> None:
        """``acct`` takes the unattributed events from now on, and
        those that arrived while nobody did."""
        with self._lock:
            early, self._early = self._early, _new_unattributed()
            self._live.add(acct)
        for key, amount in early.items():
            acct.unattributed[key] += amount

    def open(self, acct: "EngineEffAccounting") -> None:
        self._here.build = (acct, _new_build())
        self._here.stack = []

    def close(self, acct: "EngineEffAccounting") -> Dict[str, float]:
        """The parts of the build ``acct`` opened on this thread (all
        zero where it opened none)."""
        acct_open, build = getattr(self._here, "build", None) or (None, None)
        if acct_open is not acct:
            return _new_build()
        self._here.build = None
        return build

    def _book(self, key: str, amount: float) -> None:
        open_build = getattr(self._here, "build", None)
        if open_build is not None:
            open_build[1][key] += amount
            return
        with self._lock:
            takers = list(self._live)
            if not takers:
                _book_unattributed(self._early, key, amount)
        for acct in takers:
            acct._unattributed(key, amount)

    # -- jax.monitoring's listeners --------------------------------------

    def began(self, event: str, value=None, **_) -> None:
        """Scalar listener: a timed part begins on this thread."""
        self.calls += 1
        if event in BUILD_PARTS:
            stack = self._here.__dict__.setdefault("stack", [])
            stack.append([event, 0.0])

    def lasted(self, event: str, seconds: float, **_) -> None:
        """Duration listener: a timed part ends, or the cache says what
        a load took and saved."""
        self.calls += 1
        part = BUILD_PARTS.get(event)
        if part is None:
            key = CACHE_SECONDS.get(event)
            if key is not None:
                self._book(key, seconds)
            return
        stack = getattr(self._here, "stack", None)
        inside = 0.0
        if stack and stack[-1][0] == event:
            inside = stack.pop()[1]
        if stack:
            stack[-1][1] += seconds
        self._book(part, max(0.0, seconds - inside))

    def happened(self, event: str, **_) -> None:
        """Event listener: the persistent cache was asked, or held
        what it was asked for."""
        self.calls += 1
        key = CACHE_EVENTS.get(event)
        if key is not None:
            self._book(key, 1)


def _new_build() -> Dict[str, float]:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_load_s": 0.0, "saved_s": 0.0, "asked": 0, "hits": 0}


def _new_unattributed() -> Dict[str, float]:
    return {"events": 0, "seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "hits": 0, "misses": 0}


def _book_unattributed(row: Dict[str, float], key: str,
                       amount: float) -> None:
    if key == "hits":
        row["hits"] += 1
        row["misses"] -= 1
    elif key == "asked":
        row["misses"] += 1      # until the cache says it held it
    elif key in ("trace_s", "lower_s", "backend_s"):
        row["events"] += 1
        row["seconds"] += amount
        row[key] += amount


def _build_row(build: Dict[str, float], wall_s: float) -> Dict[str, object]:
    """What a row of ``totals.compiles`` and an entry of the
    ``compiles`` ring say of one build beside its wall seconds.
    ``cache_hit``: the persistent cache held the executable (true), did
    not (false: a miss among several decides), or was not asked (None:
    no cache). ``other_s`` is the wall less the three parts: the
    runner's ``make_fn``, its choice of path, its log line."""
    asked = build["asked"]
    return {"trace_s": build["trace_s"], "lower_s": build["lower_s"],
            "backend_s": build["backend_s"],
            "cache_hit": build["hits"] == asked if asked else None,
            "cache_load_s": build["cache_load_s"],
            "saved_s": build["saved_s"],
            "other_s": wall_s - build["trace_s"] - build["lower_s"]
            - build["backend_s"]}


def _rounded(row: Dict[str, object]) -> Dict[str, object]:
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in row.items()}


def _less(now: Dict, before: Dict) -> Dict:
    """``now`` less ``before``, two reports of ``totals.builds``."""
    return {k: _less(v, before[k]) if isinstance(v, dict)
            else round(v - before[k], 6) for k, v in now.items()}


# the process's: jax.monitoring's registry is the process's too
BUILD_EVENTS = BuildEvents()


class _Span:
    """One entry of one phase on the engine thread: a context manager
    made by ``EngineEffAccounting.phase``. Spans nest; a span's own
    seconds (``self_s``) are its elapsed time less the spans and
    compiles inside it, so nested phases never count a second twice.
    After exit ``t0``/``t1`` are its monotonic stamps: the step loop
    reads them instead of keeping a clock of its own. ``c0``/``c1``
    are the thread's processor seconds (``time.thread_time``), read
    inside the wall stamps."""

    __slots__ = ("eff", "name", "label", "dispatches", "ann",
                 "t0", "t1", "inner_s", "self_s", "c0", "c1", "inner_cpu")

    def __init__(self, eff: "EngineEffAccounting", name: str,
                 label: str, dispatches: bool):
        self.eff, self.name, self.label = eff, name, label
        self.dispatches = dispatches
        self.ann = None
        self.t0 = self.t1 = self.inner_s = self.self_s = 0.0
        self.c0 = self.c1 = self.inner_cpu = 0.0

    @property
    def elapsed_s(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "_Span":
        eff = self.eff
        if eff.annotate is not None:
            self.ann = eff.annotate(self.label)
            self.ann.__enter__()
        self.t0 = eff._now()
        self.c0 = eff._cpu()
        eff._open(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.c1 = self.eff._cpu()
        self.t1 = self.eff._now()
        self.eff._close(self)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class _LoopSpan:
    """One piece of the event loop's work for a stream, timed into one
    total of LoopAccounting; ``label`` also puts it on the host plane
    of a profiler capture. ``t1`` is its exit stamp."""

    __slots__ = ("acct", "total", "label", "ann", "t0", "t1")

    def __init__(self, acct: "LoopAccounting", total: str,
                 label: Optional[str]):
        self.acct, self.total, self.label = acct, total, label
        self.ann = None
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_LoopSpan":
        acct = self.acct
        if self.label is not None and acct.annotate is not None:
            self.ann = acct.annotate(self.label)
            self.ann.__enter__()
        self.t0 = acct._now()
        return self

    def __exit__(self, *exc) -> bool:
        acct = self.acct
        self.t1 = acct._now()
        acct._seconds[self.total] += self.t1 - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class LoopAccounting:
    """The event-loop thread's account (``totals.loop`` and the
    ``loop`` ring of ``GET /debug/perf``; docs/observability.md "Loop
    timeline"): the thread that parses requests and carries every
    token from the engine's outputs to the socket. Written by the loop
    thread alone, so it takes no lock; a reader on another thread reads
    each number whole, and the sums of one payload possibly half made.

    ``wall_s`` / ``cpu_s`` advance once a sample of the lag probe
    (``sample``; AsyncLLMEngine runs the task): the loop thread's
    processor seconds over the same wall seconds. ``dispatch_s``,
    ``serialize_s`` and ``write_s`` are the three pieces of a token's
    way out: AsyncLLMEngine._dispatch handing outputs to the requests'
    queues, the server building a payload's JSON, and the payload's
    ``write``; over ``payloads`` they are the loop's seconds a token.
    """

    def __init__(self, *, ring_entries: int = 256,
                 now_fn: Callable[[], float] = time.monotonic,
                 wall_fn: Callable[[], float] = time.time,
                 cpu_fn: Callable[[], float] = time.thread_time,
                 annotate: Optional[Callable[[str], object]] = None):
        self._now, self._wall, self._cpu = now_fn, wall_fn, cpu_fn
        self.annotate = annotate
        self.wall_s = self.cpu_s = 0.0
        self._seconds = {"dispatch_s": 0.0, "serialize_s": 0.0,
                         "write_s": 0.0}
        self.payloads = 0
        self._ring: "collections.deque[dict]" = collections.deque(
            maxlen=max(1, ring_entries))
        self._last: Optional[Tuple[float, float]] = None    # (now, cpu)

    def dispatching(self) -> _LoopSpan:
        return _LoopSpan(self, "dispatch_s", "pstpu.loop.dispatch")

    def serializing(self) -> _LoopSpan:
        return _LoopSpan(self, "serialize_s", None)

    def writing(self) -> _LoopSpan:
        """Around one payload's ``write``, call to return."""
        self.payloads += 1
        return _LoopSpan(self, "write_s", "pstpu.loop.write")

    def sample(self, slept_s: Optional[float]) -> None:
        """The lag probe woke from a sleep of ``slept_s`` that it began
        at the previous sample: what the wake-up overshot is how long a
        callback that became ready waited for the loop. None: the
        probe starts here, nothing is booked."""
        now, cpu = self._now(), self._cpu()
        if slept_s is not None and self._last is not None:
            wall, on_cpu = now - self._last[0], cpu - self._last[1]
            self.wall_s += wall
            self.cpu_s += on_cpu
            self._ring.append({
                "at": now, "at_unix": round(self._wall(), 4),
                "lag_s": round(max(0.0, wall - slept_s), 6),
                "cpu_s": round(on_cpu, 6)})
        self._last = (now, cpu)

    def report(self) -> Dict[str, object]:
        return {"wall_s": round(self.wall_s, 6),
                "cpu_s": round(min(self.cpu_s, self.wall_s), 6),
                **{k: round(v, 6) for k, v in self._seconds.items()},
                "payloads": self.payloads}

    def recent(self, limit: int = 50) -> List[dict]:
        return list(self._ring)[-max(1, limit):]


class EngineEffAccounting:
    """Plain-int efficiency totals + bounded rings.

    ``kv_position_bytes`` is the HBM bytes one cache position costs one
    attention read (2 x layers x kv-heads x head-dim x itemsize, plus
    scales for the int8 cache); ``weight_bytes`` the full parameter
    set. ``compile_hist`` is an optional PhaseHistograms with labels
    ``(kind, window, kv_bucket)`` fed at compile completion (the
    metrics layer owns it so the family is registered standalone).

    ``now_fn`` is injectable for deterministic tests; ``wall_fn``
    (wall clock) stamps ring entries with an ``at_unix`` timestamp so
    an external reader — the obsplane flight recorder — can align
    engine windows/compiles with trace spans and other processes'
    rings without sharing this process's monotonic epoch.

    ``annotate`` (the engine passes ``jax.profiler.TraceAnnotation``)
    makes a context manager from a name: every phase of the step
    timeline enters one named ``pstpu.<phase>``, so the same intervals
    lie on the host plane of a profiler capture, on the device trace's
    clock. This module itself stays off JAX.

    ``queue_depth`` (the engine passes ``LLMEngine._device_queue_depth``)
    answers without blocking how many entries of the engine's device
    queue the device has NOT finished (``jax.Array.is_ready``): the
    timeline asks it at the open of every dispatching phase
    (``dispatch_depth``) and, while it holds the device busy, at the
    close of every phase and the open of every step (the starved
    seconds). ``cpu_fn`` is the calling
    thread's processor clock, injectable as ``now_fn`` is.

    ``process_start_unix`` is ``(unix time, source)`` of the process's
    start (``process_start``; a test injects one): the zero of the
    ``startup`` block's marks. What JAX says of a build while one is
    open (BuildEvents) lands in that build's row; the engine registers
    BUILD_EVENTS' three functions with ``jax.monitoring``.
    """

    def __init__(self, *, weight_bytes: int = 0,
                 kv_position_bytes: int = 0,
                 hbm_peak_bytes_per_s: Optional[float] = None,
                 ring_entries: int = 256,
                 compile_hist=None, expert_bytes: int = 0,
                 loop: Optional[Dict[str, int]] = None,
                 now_fn: Callable[[], float] = time.monotonic,
                 wall_fn: Callable[[], float] = time.time,
                 annotate: Optional[Callable[[str], object]] = None,
                 queue_depth: Optional[Callable[[], int]] = None,
                 cpu_fn: Callable[[], float] = time.thread_time,
                 process_start_unix: Optional[Tuple[float, str]] = None):
        self.weight_bytes = int(weight_bytes)
        self.kv_position_bytes = int(kv_position_bytes)
        # a looped model (``loop``, kept as ``looped``: passes, weight_layers, pool_layers,
        # looped_weight_bytes, head_bytes; None for every other): a
        # decode step reads the layers' weights once a PASS, so a
        # step's weight bytes are the whole set and (passes - 1) times
        # the looped part more; kv_position_bytes is of every pool
        # layer already. passes_run / row_steps / exit_mass: what the
        # decode windows' steps counted on the device (note_window)
        self.looped = dict(loop) if loop else None
        self.step_weight_bytes = self.weight_bytes + (
            (self.looped["passes"] - 1)
            * self.looped["looped_weight_bytes"] if self.looped else 0)
        self.loop_passes_run = 0
        self.loop_row_steps = 0
        self.loop_exit_mass = [0.0] * (self.looped["passes"]
                                       if self.looped else 0)
        # None = no known peak for this device: MBU is not reported
        self.hbm_peak_bytes_per_s = hbm_peak_bytes_per_s
        self.compile_hist = compile_hist
        self._now = now_fn
        self._wall = wall_fn
        self._cpu = cpu_fn
        self._started_at = now_fn()
        # decode-window token-step classification (cumulative ints).
        # token_steps_total accumulates batch*steps*positions in a
        # separate adder from the kind counters. NOTE the engine
        # derives `dead` by subtraction, so for the real engine the
        # effwatch sum-to-1 gate is a *plumbing* check (every adder,
        # the /load serialization, the scrape deltas — and it is
        # falsifiable, via the fake's skew knob), not a
        # classification proof; classification truth is held by the
        # client-reconciliation gate (real vs tokens received) and
        # the unit tests.
        self.decode_real = 0
        self.decode_pad = 0
        self.decode_dead = 0
        self.decode_token_steps_total = 0
        self.decode_windows = 0
        self.decode_busy_s = 0.0
        # prefill bucket-padding waste (spare rows + right padding),
        # and the dispatches by the rows they ran
        self.prefill_real = 0
        self.prefill_pad = 0
        self.prefill_dispatches = 0
        self.prefill_by_rows: Dict[int, int] = {}
        # dispatched chunks by the attention path of the executable
        # that ran them (ops/pallas_paged.attention_path)
        self.prefill_chunks_by_path: Dict[str, int] = {}
        # prefill dispatches that went behind the windows in flight,
        # and those that drained the queue first, by reason
        self.prefill_behind = 0
        self.prefill_drained: Dict[str, int] = dict.fromkeys(
            DRAIN_REASONS, 0)
        # MoE decode steps: experts whose weights were read, and what
        # reading every expert would have read (note_window); reported
        # as ``totals.moe`` by a MoE engine alone. expert_bytes: one
        # expert's weights in one layer, 0 for a dense model
        self.expert_bytes = int(expert_bytes)
        self.experts_read = 0
        self.experts_resident = 0
        # MoE prefill dispatches: the rows their experts multiplied and
        # the rows routed, real tokens x top-k x layers; where the
        # layers hold a share of their router's experts, the
        # assignments that landed here and the rounds that worked
        # through them (note_expert_rows); in ``totals.prefill`` of a
        # MoE engine
        self.prefill_expert_rows = 0
        self.prefill_routed_rows = 0
        self.prefill_held_rows = 0
        self.prefill_expert_rounds = 0
        # learned sparse attention (note_sparse): over every query a
        # decode step or a prefill chunk computed, the keys at or
        # before it, those its indexer scored and those it attended;
        # ``totals.sparse`` of an engine whose model selects
        self.sparse: Dict[str, Dict[str, int]] = {}
        # state pages (note_state; ``totals.state`` of an engine whose
        # model has Gated DeltaNet or power retention layers): real
        # prefill positions through the chunked rule and live row-steps
        # through the recurrent one, per layer; the decode steps
        # dispatched and the bytes of state pages the dispatches of
        # either kind read and wrote, all layers; ``state_pages`` (set
        # by the engine) reads the block manager's page counters
        self.scan_tokens = 0
        self.prefill_keys = 0
        self.step_rows = 0
        self.state_steps = 0
        self.state_step_bytes = 0
        self.state_scan_bytes = 0
        self.state_pages = None
        # a model whose prefill runs in two depths (note_depths;
        # ``self_positions`` / ``cross_positions`` of ``totals.prefill``):
        # the positions its first layers and its later layers ran, a
        # dispatch's rows x chunk bucket against one a finishing row;
        # and ``totals.shared_kv`` (note_shared_kv): the paged calls
        # that read a pool layer they did not append to, and the keys
        # at or before their queries. None: every layer owns its K/V
        self.depth_positions = None
        self.shared_kv = None
        # modeled HBM traffic (decode windows only — see module doc)
        self.bytes_total = 0
        self.bytes_effective = 0
        # XLA compile tracking:
        # (kind, window, kv, batch) -> [count, total_s, parts], parts
        # the build's seconds by what JAX spent them on (_build_row)
        self.compiles: Dict[Tuple[str, int, int, int], List] = {}
        self.compiles_total = 0
        self.compile_s_total = 0.0
        self.compile_in_flight = 0
        self.last_compile_at: Optional[float] = None
        # ``totals.builds``: the same builds summed by part, the builds
        # by whether the persistent cache held them, and what JAX
        # traced, lowered or compiled with no build open
        self.builds: Dict[str, float] = {
            "count": 0, "hits": 0, "misses": 0,
            **dict.fromkeys(BUILD_SECONDS, 0.0)}
        self.unattributed: Dict[str, float] = _new_unattributed()
        # the ``startup`` block: marks in unix time (None: not reached),
        # the runner's spans, ``totals.builds`` as it stood at
        # ``serving``
        self.process_start_unix, self.process_start_source = (
            process_start_unix or process_start(_IMPORTED_UNIX))
        self.startup_marks: Dict[str, Optional[float]] = dict.fromkeys(
            STARTUP_MARKS)
        self.startup_spans: Dict[str, Optional[float]] = {
            "weights_s": None, "cache_alloc_s": None}
        self._builds_before_serving: Optional[Dict] = None
        self._windows: "collections.deque[dict]" = collections.deque(
            maxlen=max(1, ring_entries))
        # (start_mono, dur_s, kind, window, kv, batch, start_unix, parts)
        self._compile_events: "collections.deque[tuple]" = \
            collections.deque(maxlen=128)
        self._lock = threading.Lock()
        BUILD_EVENTS.follow(self)
        # step timeline (phase/step below). Totals and the ring are
        # read under the micro-lock; everything with a leading
        # underscore below is the engine thread's alone and is folded
        # into the totals once per step.
        self.annotate = annotate
        self.queue_depth = queue_depth
        self.steps = 0
        self.step_wall_s = 0.0
        self.phase_s: Dict[str, float] = dict.fromkeys(STEP_PHASES, 0.0)
        # the engine thread's processor seconds of each phase; what is
        # left of phase_s it spent off the processor (report())
        self.cpu_s: Dict[str, float] = dict.fromkeys(STEP_PHASES, 0.0)
        self.starved_s: Dict[str, float] = {}
        # dispatches by the depth of the device queue they found,
        # indexed as DEPTH_KEYS
        self.dispatch_depth: List[int] = [0] * len(DEPTH_KEYS)
        # exit stamp of the latest ``*_sync`` phase: a pipelined
        # window's seconds start here, not at its dispatch
        self.synced_at = 0.0
        self._steps: "collections.deque[dict]" = collections.deque(
            maxlen=max(1, ring_entries))
        self._stack: List[_Span] = []
        self._cur: Dict[str, float] = {}        # phase -> s, this step
        self._cur_cpu: Dict[str, float] = {}    # ... on the processor
        self._cur_starved: Dict[str, float] = {}
        self._cur_prefill: Dict[str, int] = {}  # dispatches, this step
        self._cur_depth: List[int] = [0] * len(DEPTH_KEYS)
        self._root_end: Optional[float] = None  # last outermost exit
        self._root_cpu_end = 0.0                # ... on the thread's clock
        self._compile_c0 = 0.0      # the thread's clock at compile_started
        # since when the device has had nothing of ours outstanding
        # while work waited (None: it is busy). Set where the timeline
        # begins, by device_idle(), at the first close of a phase (or
        # open of a step) at which queue_depth() says 0, and moved up
        # past a wait for work
        self._idle_since: Optional[float] = None
        self._looked_at = float("-inf")     # when _look last asked
        # the event loop's account (LoopAccounting; ``totals.loop``)
        self.loop = LoopAccounting(
            ring_entries=ring_entries, now_fn=now_fn, wall_fn=wall_fn,
            cpu_fn=cpu_fn, annotate=annotate)

    # -- step-loop writes ------------------------------------------------

    def note_window(self, *, steps: int, positions: int, batch: int,
                    live_rows: int, kv_len: int, real: int, pad: int,
                    dead: int, window_s: float, host_s: float = 0.0,
                    sync_s: float = 0.0, experts_read: int = 0,
                    experts_resident: int = 0, loop=None) -> None:
        """One fused decode window: ``batch * steps * positions``
        token-step computations, of which ``real`` emitted tokens the
        client keeps, ``pad`` ran on parked rows, and ``dead`` ran on
        finished rows' tails / discarded rows / rejected draft
        positions. ``window_s`` runs from its dispatch (or the sync
        before it, if later) to its own sync's return; ``host_s`` is
        what the host itself spent on it (preparing and making the
        dispatch, walking its tokens) and ``sync_s`` what it spent
        blocked on the sync. A MoE model: over the window's steps and
        layers ``experts_read`` experts' weights were fetched, of the
        ``experts_resident`` (steps x layers x experts) that steps
        reading every expert fetch (ops/moe.py, the list path); the
        bytes of the others are not in the window's. A looped model:
        ``loop`` = (passes run, row-steps, exit mass a pass) as the
        window's steps summed them on the device."""
        total = batch * steps * positions
        useful = real / total if total else 0.0
        win_bytes = (steps * (self.step_weight_bytes
                              + batch * self.kv_position_bytes * kv_len)
                     - (experts_resident - experts_read)
                     * self.expert_bytes)
        eff_bytes = int(win_bytes * useful)
        entry = {
            "at": self._now(),
            "at_unix": round(self._wall(), 4),
            "steps": steps,
            "positions": positions,
            "batch": batch,
            "live_rows": live_rows,
            "kv_len": kv_len,
            "real": real,
            "pad": pad,
            "dead": dead,
            "window_s": round(window_s, 6),
            "host_s": round(host_s, 6),
            "sync_s": round(sync_s, 6),
            "bytes": win_bytes,
            "effective_bytes": eff_bytes,
        }
        with self._lock:
            self.decode_real += real
            self.decode_pad += pad
            self.decode_dead += dead
            self.decode_token_steps_total += total
            self.decode_windows += 1
            self.decode_busy_s += window_s
            self.bytes_total += win_bytes
            self.bytes_effective += eff_bytes
            self.experts_read += experts_read
            self.experts_resident += experts_resident
            if loop is not None:
                self.loop_passes_run += int(loop[0])
                self.loop_row_steps += int(loop[1])
                self.loop_exit_mass = [a + float(b) for a, b in zip(
                    self.loop_exit_mass, loop[2])]
            self._windows.append(entry)

    def note_prefill(self, *, bucket: int, batch: int,
                     real_tokens: int,
                     drained: Optional[str] = None,
                     chunks: int = 0, attention_path: str = "") -> None:
        """One prefill bucket group, dispatched at ``batch`` rows:
        ``batch * bucket`` token positions were computed;
        ``real_tokens`` were actual prompt-chunk tokens, the rest
        bucket right-padding and spare parked rows. ``drained``: why
        the device queue was emptied before it (a name of
        DRAIN_REASONS); None where it went behind the queue.
        ``chunks`` prompt chunks rode in it, on the executable's
        ``attention_path``."""
        total = batch * bucket
        path = "prefill_behind" if drained is None else "drained_" + drained
        self._cur_prefill[path] = self._cur_prefill.get(path, 0) + 1
        with self._lock:
            self.prefill_real += real_tokens
            self.prefill_pad += max(0, total - real_tokens)
            self.prefill_dispatches += 1
            self.prefill_by_rows[batch] = (
                self.prefill_by_rows.get(batch, 0) + 1)
            if chunks:
                self.prefill_chunks_by_path[attention_path] = (
                    self.prefill_chunks_by_path.get(attention_path, 0)
                    + chunks)
            if drained is None:
                self.prefill_behind += 1
            else:
                self.prefill_drained[drained] += 1

    def note_expert_rows(self, expert_rows: int, held_rows: int,
                         rounds: int, routed_rows: int) -> None:
        """One prefill dispatch of a MoE engine: its experts multiplied
        ``expert_rows`` rows, summed over the layers, where
        ``routed_rows`` were routed (real tokens x top-k x layers);
        ``held_rows`` of those named an expert held on this chip and
        were worked through in ``rounds`` rounds (ops/moe.Work; both 0
        where the layers hold every expert their routers score)."""
        with self._lock:
            self.prefill_expert_rows += expert_rows
            self.prefill_routed_rows += routed_rows
            self.prefill_held_rows += held_rows
            self.prefill_expert_rounds += rounds

    def note_state(self, scan_tokens: int = 0, prefill_keys: int = 0,
                   step_rows: int = 0, steps: int = 0,
                   step_bytes: int = 0, scan_bytes: int = 0) -> None:
        """One dispatch of a model with state pages: the real prefill
        positions a chunk carried through the chunked rule
        (``prefill_keys``: the keys at or before them, summed, which
        its attention layers' causal products run over), or the live
        row-steps a decode window carried through the recurrent one
        (of ONE layer: every such layer does the same); ``steps`` the
        window's decode steps, ``step_bytes`` / ``scan_bytes`` the
        bytes of state pages the dispatch read and wrote, every layer
        and every row of its batch (a row that is not real moves the
        trash page)."""
        with self._lock:
            self.scan_tokens += scan_tokens
            self.prefill_keys += prefill_keys
            self.step_rows += step_rows
            self.state_steps += steps
            self.state_step_bytes += step_bytes
            self.state_scan_bytes += scan_bytes

    def note_depths(self, self_positions: int,
                    cross_positions: int) -> None:
        """One prefill dispatch of a model whose plan has two depths:
        the positions its first layers ran (rows x chunk bucket) and
        those its later layers ran (one a row whose prompt ended in
        the chunk, where any did; else none)."""
        with self._lock:
            at = self.depth_positions or [0, 0]
            self.depth_positions = [at[0] + self_positions,
                                    at[1] + cross_positions]

    def note_shared_kv(self, reads: int, keys_read: int) -> None:
        """One dispatch of a model with layers that read ANOTHER
        layer's K/V: ``reads`` paged calls of such layers (a call a
        layer and step), ``keys_read`` the keys at or before their
        queries, summed over calls, rows and positions (reckoned here,
        from the positions, as ``note_sparse`` reckons)."""
        with self._lock:
            at = self.shared_kv or [0, 0]
            self.shared_kv = [at[0] + reads, at[1] + keys_read]

    def note_sparse(self, kind: str, first, queries: int, topk: int,
                    selects: bool) -> None:
        """One dispatch of a model that selects what it attends
        (ops/dsa.py): ``kind`` "decode" or "prefill"; for each row the
        position ``first`` of its first query and ``queries``
        consecutive ones. A query at position p has p + 1 keys in its
        context; where the executable selects (its kv bucket holds
        more than ``topk`` positions) all are scored and min(p + 1,
        topk) attended, else none is scored and all are attended. Per
        query of ONE layer: every layer does the same."""
        ctx = (np.asarray(first, np.int64)[:, None] + 1
               + np.arange(queries, dtype=np.int64)[None, :])
        in_context = int(ctx.sum())
        moved = {"queries": int(ctx.size), "keys_in_context": in_context,
                 "keys_scored": in_context if selects else 0,
                 "keys_attended": int(np.minimum(ctx, topk).sum())
                 if selects else in_context}
        with self._lock:
            row = self.sparse.setdefault(kind, dict.fromkeys(moved, 0))
            for key, n in moved.items():
                row[key] += n

    # -- step timeline (engine thread only) ------------------------------

    def phase(self, name: str, dispatches: bool = False) -> _Span:
        """Context manager for one phase of the step loop (a name of
        STEP_PHASES). ``dispatches`` marks the phases that hand the
        device work (the ``runner.decode`` / ``runner.prefill`` calls):
        the device stops counting as starved when one returns."""
        return _Span(self, name, "pstpu." + name, dispatches)

    def step(self) -> _Span:
        """Context manager around one whole ``step()``: closes the
        step's record into the totals and the ``steps`` ring. What the
        step spends outside any other phase is ``housekeeping``."""
        return _Span(self, "housekeeping", "pstpu.step", False)

    def device_idle(self) -> None:
        """The sync that just returned left nothing outstanding on the
        device: from that stamp on, while work waits, it is starved."""
        self._idle_since = self.synced_at

    def _starve(self, name: str, until: float) -> None:
        if self._idle_since is not None:
            self._cur_starved[name] = (self._cur_starved.get(name, 0.0)
                                       + until - self._idle_since)
            self._idle_since = until

    def _look(self, at: float) -> None:
        """A span boundary of the engine thread at which work exists
        (the close of a phase, the open of a step): where the timeline
        holds the device busy and the device says it has finished all
        it was given, the starved seconds start here, late by at most
        the span that just ended or LOOK_EVERY_S. Not asked at the
        open of a nested phase, which follows a close or its parent's
        first lines by microseconds."""
        if (self._idle_since is None and self.queue_depth is not None
                and at - self._looked_at >= LOOK_EVERY_S):
            self._looked_at = at
            if self.queue_depth() == 0:
                self._idle_since = at

    def _book(self, name: str, wall: float, cpu: float) -> None:
        """``wall`` seconds of phase ``name``, of them ``cpu`` on the
        processor, as the thread's clock read them. That clock may
        tick coarsely (10 ms under gVisor, where the chip's machines
        run: a span of 2 ms then reads 0 or 10 ms), so nothing is
        clamped span by span: a tick lands in the phase that was
        running, and over many spans a phase's sum is right. report()
        holds the totals to ``cpu_s <= phase_s``."""
        self._cur[name] = self._cur.get(name, 0.0) + wall
        self._cur_cpu[name] = self._cur_cpu.get(name, 0.0) + cpu

    def _open(self, span: _Span) -> None:
        if self._stack:
            self._starve(self._stack[-1].name, span.t0)
        else:
            if self._root_end is None:
                self._idle_since = span.t0      # nothing dispatched yet
            else:
                self._book("between_steps", span.t0 - self._root_end,
                           span.c0 - self._root_cpu_end)
            self._starve("between_steps", span.t0)
            if span.name != "no_work":
                self._look(span.t0)
        self._stack.append(span)
        if span.dispatches and self.queue_depth is not None:
            depth = self.queue_depth()
            self._cur_depth[min(depth, len(DEPTH_KEYS) - 1)] += 1
            if depth == 0 and self._idle_since is None:
                self._idle_since = span.t0

    def _close(self, span: _Span) -> None:
        self._stack.pop()
        elapsed = span.t1 - span.t0
        on_cpu = span.c1 - span.c0
        span.self_s = elapsed - span.inner_s
        self._book(span.name, span.self_s, on_cpu - span.inner_cpu)
        if span.name != "no_work":
            self._starve(span.name, span.t1)
        elif self._idle_since is not None:
            # nothing waited, so nobody starved; what arrives now does
            self._idle_since = span.t1
        if span.dispatches:
            self._idle_since = None
        elif span.name != "no_work":
            self._look(span.t1)
        if span.name.endswith("_sync"):
            self.synced_at = span.t1
        if self._stack:
            self._stack[-1].inner_s += elapsed
            self._stack[-1].inner_cpu += on_cpu
            return
        # outermost span (a step, or the wait for work): fold into the
        # totals what was booked since the last one closed. The wall
        # is taken from the stamps, not from the sum of the phases
        wall = span.t1 - (span.t0 if self._root_end is None
                          else self._root_end)
        self._root_end, self._root_cpu_end = span.t1, span.c1
        cur, cur_cpu, starved = self._cur, self._cur_cpu, self._cur_starved
        prefills, self._cur_prefill = self._cur_prefill, {}
        depths, self._cur_depth = self._cur_depth, [0] * len(DEPTH_KEYS)
        self._cur, self._cur_cpu, self._cur_starved = {}, {}, {}
        if depths[0]:
            prefills["dry_dispatches"] = depths[0]
        is_step = span.label == "pstpu.step"
        with self._lock:
            for k, v in cur.items():
                self.phase_s[k] += v
                self.cpu_s[k] += cur_cpu[k]
            for k, v in starved.items():
                self.starved_s[k] = self.starved_s.get(k, 0.0) + v
            for i, n in enumerate(depths):
                self.dispatch_depth[i] += n
            self.step_wall_s += wall
            if is_step:
                self.steps += 1
                self._steps.append({
                    "at": span.t0,
                    "at_unix": round(self._wall() - elapsed, 4),
                    "wall_s": round(elapsed, 6),
                    "phase_s": {k: round(v, 6) for k, v in cur.items()},
                    "starved_s": round(sum(starved.values()), 6),
                    # the thread off the processor in the phases of
                    # its own work (HOST_WORK_PHASES); one step is
                    # only as fine as the thread clock's tick
                    "offcpu_s": round(max(0.0, sum(
                        cur[k] - cur_cpu[k] for k in HOST_WORK_PHASES
                        if k in cur)), 6),
                    # prefill dispatches of the step (``prefill_behind``
                    # or ``drained_<reason>``) and the dispatches that
                    # found the device queue finished
                    # (``dry_dispatches``), where it made any
                    **prefills})

    # -- compile observer (ModelRunner hook) -----------------------------

    def compile_started(self, kind: str, window: int, kv_len: int,
                        batch: int = 0) -> None:
        self._compile_c0 = self._cpu()
        BUILD_EVENTS.open(self)
        with self._lock:
            self.compile_in_flight += 1

    def compile_finished(self, kind: str, window: int, kv_len: int,
                         started_at: float, dur_s: float,
                         batch: int = 0) -> None:
        key = (kind, int(window), int(kv_len), int(batch))
        parts = _build_row(BUILD_EVENTS.close(self), dur_s)
        if self._stack:
            # a compile inside a phase of the step loop (they happen
            # on the engine thread, in the dispatch phases) is taken
            # out of that phase and booked as ``compile``
            top = self._stack[-1]
            on_cpu = self._cpu() - self._compile_c0
            top.inner_s += dur_s
            top.inner_cpu += on_cpu
            self._book("compile", dur_s, on_cpu)
            self._starve(top.name, started_at)
            self._starve("compile", started_at + dur_s)
        with self._lock:
            self.compile_in_flight = max(0, self.compile_in_flight - 1)
            slot = self.compiles.setdefault(key, [0, 0.0, None])
            slot[0] += 1
            slot[1] += dur_s
            slot[2] = parts if slot[2] is None else {
                k: v if k == "cache_hit" else slot[2][k] + v
                for k, v in parts.items()}
            self.compiles_total += 1
            self.compile_s_total += dur_s
            self.last_compile_at = started_at + dur_s
            builds, hit = self.builds, parts["cache_hit"]
            builds["count"] += 1
            if hit is not None:
                builds["hits" if hit else "misses"] += 1
            builds["wall_s"] += dur_s
            builds["backend_hit_s" if hit else "backend_miss_s"] += \
                parts["backend_s"]
            for k in ("trace_s", "lower_s", "cache_load_s", "saved_s",
                      "other_s"):
                builds[k] += parts[k]
            # wall-clock stamp of the compile START (this call runs at
            # compile END, so subtract the duration)
            self._compile_events.append(
                (started_at, dur_s, kind, int(window), int(kv_len),
                 int(batch), round(self._wall() - dur_s, 4), parts))
        if self.compile_hist is not None:
            self.compile_hist.observe(kind, str(window), str(kv_len),
                                      dur_s)

    def _unattributed(self, key: str, amount: float) -> None:
        """BuildEvents: JAX traced, lowered, compiled or asked its
        cache with no build open (the weights' init and quantise jits,
        the embeddings and prompt-logprobs functions, the KV extract
        and inject functions, a compile an executable makes at its
        first call, another thread's jit)."""
        with self._lock:
            _book_unattributed(self.unattributed, key, amount)

    # -- the start, by its marks -----------------------------------------

    def mark(self, name: str, at_unix: Optional[float] = None) -> None:
        """The start reached ``name`` (of STARTUP_MARKS) now, or at
        ``at_unix`` (``main`` is stamped before this object exists). A
        mark is set once and never moves. At ``serving`` the builds so
        far are frozen as ``before_serving``."""
        with self._lock:
            if self.startup_marks[name] is not None:
                return
            self.startup_marks[name] = (self._wall() if at_unix is None
                                        else at_unix)
            if name == "serving":
                self._builds_before_serving = self._builds_report()

    def _builds_report(self) -> Dict[str, object]:
        """``totals.builds`` (under the lock). Rounded before the
        subtraction, so that what is reported adds up: ``wall_s`` is
        the four parts and ``other_s``."""
        b = _rounded(self.builds)
        b["other_s"] = round(
            b["wall_s"] - b["trace_s"] - b["lower_s"]
            - b["backend_miss_s"] - b["backend_hit_s"], 6)
        b["unattributed"] = _rounded(self.unattributed)
        return b

    def startup_report(self) -> Dict[str, object]:
        """The ``startup`` block of GET /debug/perf
        (docs/observability.md "Start-up and builds"): the process's
        start, the marks in seconds since it (None until reached), the
        runner's spans, ``totals.builds`` as frozen at ``serving`` and
        what has been built since (both None before it)."""
        with self._lock:
            now, before = self._builds_report(), self._builds_before_serving
            marks = {k: None if v is None
                     else round(v - self.process_start_unix, 4)
                     for k, v in self.startup_marks.items()}
            spans = dict(self.startup_spans)
        return {
            "process_start_unix": round(self.process_start_unix, 4),
            "process_start_source": self.process_start_source,
            "marks": marks, "spans": spans,
            "before_serving": before,
            "after_serving": None if before is None
            else _less(now, before)}

    def startup_sentence(self) -> str:
        """The ``startup`` block in one log line, for the server to
        say once ``serving`` is marked."""
        r = self.startup_report()
        b, m, sp = r["before_serving"], r["marks"], r["spans"]
        return (
            f"start: serving {m['serving']} s after the process began "
            f"({r['process_start_source']}; main at {m['main']} s, engine "
            f"built at {m['engine_built']} s); weights "
            f"{sp['weights_s']} s, cache {sp['cache_alloc_s']} s; "
            f"{b['count']} builds in {b['wall_s']} s "
            f"({b['hits']} loaded, {b['misses']} compiled): trace "
            f"{b['trace_s']} s, lower {b['lower_s']} s, compile "
            f"{b['backend_miss_s']} s, load {b['backend_hit_s']} s, "
            f"other {b['other_s']} s; outside any build "
            f"{b['unattributed']['seconds']} s")

    # -- reads (off the hot path) ----------------------------------------

    def loop_report(self) -> Optional[Dict[str, object]]:
        """The ``loop`` block of GET /debug/perf ``device`` (a looped
        model's; None for every other): ``passes`` the layer stack runs
        a token, over ``weight_layers`` layers and ``pool_layers`` pool
        layers; ``passes_run`` and ``row_steps`` as the decode windows'
        steps counted them on the device (their ratio is ``passes``
        while every row runs every pass); ``exit_mass``: the mean share
        of a row-step's exit distribution a pass took."""
        if not self.looped:
            return None
        with self._lock:
            return self._loop_counts()

    def _loop_counts(self) -> Dict[str, object]:
        """loop_report's block (``totals.looped`` too, which the
        scrape-time sync reads); the caller holds the lock."""
        rows = self.loop_row_steps
        return {"passes": self.looped["passes"],
                "weight_layers": self.looped["weight_layers"],
                "pool_layers": self.looped["pool_layers"],
                "passes_run": self.loop_passes_run,
                "row_steps": rows,
                "exit_mass": [round(m / rows, 6) if rows else None
                              for m in self.loop_exit_mass]}

    def step_bytes_report(self) -> Dict[str, int]:
        """``totals.step_bytes`` (a looped model's): the byte model's
        decode step by its parts: ``weights`` every weight but the head once, the looped
        part once a pass; ``head``; ``kv_per_position`` K and V of one
        cached position in every pool layer, which a step reads for
        every row and position of its kv bucket."""
        head = self.looped["head_bytes"]
        return {"weights": self.step_weight_bytes - head, "head": head,
                "kv_per_position": self.kv_position_bytes,
                "passes": self.looped["passes"]}

    def report(self) -> Dict[str, object]:
        """Cumulative totals (the scrape-time delta-sync source, and
        ``totals`` of GET /debug/perf). ``step`` is the step timeline
        (docs/observability.md "Step timeline"): ``steps``, ``wall_s``,
        ``phase_s`` / ``cpu_s`` / ``offcpu_s`` (all STEP_PHASES keys
        each; processor and off-processor seconds add up to the phase's
        seconds), ``starved_s`` and ``starved_by_phase``,
        ``dispatch_depth`` (all DEPTH_KEYS), ``prefill_behind`` and
        ``prefill_drained`` (all DRAIN_REASONS). ``loop`` is the event
        loop's account (LoopAccounting.report). ``builds`` sums the
        builds by their parts, and a row of ``compiles`` holds its
        own (docs/observability.md "Start-up and builds")."""
        with self._lock:
            moe = {"moe": {"experts_read": self.experts_read,
                           "experts_resident": self.experts_resident}
                   } if self.expert_bytes else {}
            moe_rows = {"expert_rows": self.prefill_expert_rows,
                        "routed_rows": self.prefill_routed_rows,
                        "held_rows": self.prefill_held_rows,
                        "expert_rounds": self.prefill_expert_rounds
                        } if self.expert_bytes else {}
            # rounded before the subtraction, so that what is reported
            # adds up: cpu_s + offcpu_s == phase_s, phase by phase
            phase_s = {k: round(v, 6) for k, v in self.phase_s.items()}
            cpu_s = {k: min(max(round(v, 6), 0.0), phase_s[k])
                     for k, v in self.cpu_s.items()}
            return {
                "decode": {"real": self.decode_real,
                           "pad": self.decode_pad,
                           "dead": self.decode_dead,
                           "token_steps_total":
                               self.decode_token_steps_total,
                           "windows": self.decode_windows,
                           "busy_s": round(self.decode_busy_s, 4)},
                "prefill": {"real": self.prefill_real,
                            "pad": self.prefill_pad,
                            "dispatches": self.prefill_dispatches,
                            "by_rows": {
                                str(r): n for r, n in
                                sorted(self.prefill_by_rows.items())},
                            "chunks_by_path": dict(sorted(
                                self.prefill_chunks_by_path.items())),
                            **({"self_positions": self.depth_positions[0],
                                "cross_positions":
                                    self.depth_positions[1]}
                               if self.depth_positions else {}),
                            **moe_rows},
                **moe,
                **({"shared_kv": {"reads": self.shared_kv[0],
                                  "keys_read": self.shared_kv[1]}}
                   if self.shared_kv else {}),
                **({"sparse": {
                    **{key: sum(row[key] for row in self.sparse.values())
                       for key in ("keys_in_context", "keys_scored",
                                   "keys_attended")},
                    **{kind: dict(row)
                       for kind, row in self.sparse.items()}}}
                   if self.sparse else {}),
                **({"state": {**self.state_pages(),
                              "scan_tokens": self.scan_tokens,
                              "prefill_keys": self.prefill_keys,
                              "step_rows": self.step_rows,
                              "steps": self.state_steps,
                              "step_bytes": self.state_step_bytes,
                              "scan_bytes": self.state_scan_bytes}}
                   if self.state_pages is not None else {}),
                "bytes_total": self.bytes_total,
                "bytes_effective": self.bytes_effective,
                "compiles_total": self.compiles_total,
                "compile_s_total": round(self.compile_s_total, 4),
                "compile_in_flight": self.compile_in_flight,
                "compiles": {f"{k}|{w}|{kv}|{b}":
                             {"count": c[0],
                              "seconds": round(c[1], 4),
                              **_rounded(c[2])}
                             for (k, w, kv, b), c in
                             self.compiles.items()},
                "builds": self._builds_report(),
                "weight_bytes": self.weight_bytes,
                "kv_position_bytes": self.kv_position_bytes,
                **({"step_bytes": self.step_bytes_report(),
                    "looped": self._loop_counts()}
                   if self.looped else {}),
                "hbm_peak_bytes_per_s": self.hbm_peak_bytes_per_s,
                "step": {
                    "steps": self.steps,
                    "wall_s": round(self.step_wall_s, 6),
                    "phase_s": phase_s,
                    "cpu_s": cpu_s,
                    "offcpu_s": {k: round(phase_s[k] - cpu_s[k], 6)
                                 for k in phase_s},
                    "starved_s": round(sum(self.starved_s.values()), 6),
                    "starved_by_phase": {
                        k: round(v, 6)
                        for k, v in self.starved_s.items()},
                    "dispatch_depth": dict(zip(DEPTH_KEYS,
                                               self.dispatch_depth)),
                    "prefill_behind": self.prefill_behind,
                    "prefill_drained": dict(self.prefill_drained)},
                "loop": self.loop.report(),
            }

    def rates(self, horizon_s: float = 10.0,
              now: Optional[float] = None) -> Dict[str, float]:
        """Ring-derived recent rates: effective/total bytes per
        wall-clock second over the last ``horizon_s`` (idle time counts
        against the rate — this is what a roofline comparison wants),
        MBU against the device's peak (None when that is unknown), and
        the recent live fraction.

        The divisor is clamped to what the ring can actually witness:
        uptime when younger than the horizon, and — on a busy engine
        whose ring evicts entries faster than the horizon drains —
        the age of the oldest resident entry. Without the clamp a
        full ring would sum only its resident windows while dividing
        by the whole horizon, understating every rate by the eviction
        ratio."""
        if now is None:
            now = self._now()
        window = min(horizon_s, max(1e-9, now - self._started_at))
        eff = tot = real = pad = dead = 0
        with self._lock:
            if (self._windows
                    and len(self._windows) == self._windows.maxlen):
                oldest = self._windows[0]["at"]
                window = min(window, max(1e-9, now - oldest))
            cutoff = now - window
            for e in self._windows:
                if e["at"] >= cutoff:
                    eff += e["effective_bytes"]
                    tot += e["bytes"]
                    real += e["real"]
                    pad += e["pad"]
                    dead += e["dead"]
        all_steps = real + pad + dead
        eff_rate = eff / window
        return {
            "horizon_s": round(window, 3),
            "effective_bytes_per_s": round(eff_rate, 1),
            "total_bytes_per_s": round(tot / window, 1),
            "mbu_perc": round(100.0 * eff_rate
                              / self.hbm_peak_bytes_per_s, 4)
            if self.hbm_peak_bytes_per_s else None,
            "live_fraction": round(real / all_steps, 6)
            if all_steps else 0.0,
            "decode_tokens_per_s": round(real / window, 3),
        }

    def perf_block(self, horizon_s: float = 10.0) -> Dict[str, object]:
        """The ``/load`` ``perf`` block: totals + recent rates, cheap
        and engine-lock-free (signals.EngineLoad parses this)."""
        r = self.report()
        out = {
            "token_steps": r["decode"],
            "prefill_tokens": r["prefill"],
            "bytes_total": r["bytes_total"],
            "bytes_effective": r["bytes_effective"],
            "compiles_total": r["compiles_total"],
            "compile_s_total": r["compile_s_total"],
            "compile_in_flight": r["compile_in_flight"],
            "weight_bytes": r["weight_bytes"],
        }
        out.update(self.rates(horizon_s))
        return out

    def recent_windows(self, limit: int = 50) -> List[dict]:
        with self._lock:
            return list(self._windows)[-max(1, limit):]

    def recent_steps(self, limit: int = 50) -> List[dict]:
        with self._lock:
            return list(self._steps)[-max(1, limit):]

    def recent_compiles(self, limit: int = 50) -> List[dict]:
        with self._lock:
            events = list(self._compile_events)[-max(1, limit):]
        return [{"at": round(t, 4), "at_unix": wall,
                 "duration_s": round(d, 4),
                 "kind": k, "window": w, "kv_bucket": kv, "batch": b,
                 **_rounded(parts)}
                for t, d, k, w, kv, b, wall, parts in events]

    def compile_events_between(self, t0: float, t1: float
                               ) -> List[Tuple[float, float, str, int,
                                               int, int]]:
        """Compile events overlapping the monotonic interval
        ``[t0, t1]`` — the trace seal hook that makes a compile-stalled
        request visible in ``/debug/traces``. Rows are
        ``(start_mono, dur_s, kind, window, kv, batch)`` — the ring's
        wall-clock stamp is an exporter concern, not a span one."""
        with self._lock:
            events = list(self._compile_events)
        return [e[:6] for e in events
                if e[0] < t1 and e[0] + e[1] > t0]
