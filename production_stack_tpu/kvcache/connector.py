"""Engine-side KV connector: moves KV chunks between TPU HBM and the tiers.

The reference engine gets this via vLLM's `--kv-transfer-config
'{"kv_connector":"LMCacheConnector","kv_role":"kv_both"}'` flag (reference:
helm/templates/deployment-vllm-multi.yaml:94-99); roles kv_producer /
kv_consumer split prefill and decode pods for disaggregated prefill
(reference: README.md:56 roadmap). Same contract here, TPU-native flow:

  consumer path: ``prefetch()`` runs on the server thread at request-add
    time — chain-hash the prompt, walk the tiers until the first miss, and
    materialize hits as host numpy arrays. ``on_admit()`` (engine loop, at
    slot assignment) only dispatches per-chunk device_put +
    dynamic_update_slice into the slot — no host I/O on the hot loop — and
    rewinds ``num_prefilled`` so prefill skips the cached prefix.

  producer path: ``on_finish()`` dispatches per-chunk slices out of the
    donated cache *synchronously* (XLA orders them before the next donating
    step, so slot reuse can't clobber the read) and hands the device arrays
    to a writer thread that blocks on D2H and writes through the tiers.

Chunk value layout: k_bytes + v_bytes, each [L, chunk, Hkv, D] in the
engine's kv dtype, C-order. The key namespace (chunks.model_fingerprint)
pins model geometry + dtype, so replicas sharing a remote tier interoperate
only when they'd produce byte-identical KV.
"""

import dataclasses
import queue
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from production_stack_tpu.kvcache.chunks import (ChunkHasher,
                                                 model_fingerprint)
from production_stack_tpu.kvcache.store import KVStore, make_store
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


@dataclasses.dataclass
class KVTransferConfig:
    """Parsed form of the engine's --kv-transfer-config JSON."""
    kv_role: str = "kv_both"            # kv_producer | kv_consumer | kv_both
    chunk_size: int = 256
    local_cpu_gb: float = 0.0           # LMCACHE_MAX_LOCAL_CPU_SIZE equiv
    local_disk_path: Optional[str] = None
    local_disk_gb: float = 16.0
    remote_url: Optional[str] = None    # tpukv://host:port
    # remote-tier failure bounds: a dead/hung cache server must degrade
    # to recompute, never stall admission — per-op socket timeouts plus
    # a breaker that short-circuits every remote call after
    # `remote_breaker_threshold` consecutive failures for
    # `remote_breaker_cooldown_s` (kvcache/store.RemoteStore)
    remote_connect_timeout_s: float = 2.0
    remote_io_timeout_s: float = 5.0
    remote_breaker_threshold: int = 3
    remote_breaker_cooldown_s: float = 10.0
    # hard wall-clock budget for one prefetch's tier walk: past it the
    # walk stops and the request prefills the rest (bounds TTFT under a
    # slow tier; the per-op timeouts bound each individual chunk read).
    # The budget is accounted per remaining chunk (chunk i of n must
    # land by budget*(i+1)/n), so one stalled chunk is cut at roughly
    # its fair share instead of consuming the whole wall and starving
    # every later fetch.
    prefetch_timeout_s: float = 2.0
    # pipelined prefetch: up to `prefetch_workers` chunk reads in
    # flight while earlier chunks are still being consumed (tier
    # latency overlaps tier latency instead of serializing into TTFT).
    # `prefetch_pipeline: false` falls back to one read at a time —
    # the fair-share deadline accounting applies either way.
    prefetch_pipeline: bool = True
    prefetch_workers: int = 4
    # per-tier codec choice, e.g. {"disk": "int8", "remote": "int4"}
    # (kvcache/codec.py: raw | int8 | int4 | fp8). Unmapped tiers stay
    # raw byte-exact. Encoded payloads are checksummed POST-encode, so
    # torn values still read as misses, never as dequantized garbage.
    tier_codecs: Optional[dict] = None

    @classmethod
    def from_dict(cls, d: dict) -> "KVTransferConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        ignored = {k: v for k, v in d.items() if k not in known}
        if ignored:
            logger.warning("kv_transfer_config: ignoring keys %s",
                           sorted(ignored))
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def enabled(self) -> bool:
        return (self.local_cpu_gb > 0 or bool(self.local_disk_path)
                or bool(self.remote_url))

    @property
    def is_producer(self) -> bool:
        return self.kv_role in ("kv_producer", "kv_both")

    @property
    def is_consumer(self) -> bool:
        return self.kv_role in ("kv_consumer", "kv_both")


@dataclasses.dataclass
class Prefetch:
    """Host-side KV for a prompt's cached prefix, ready to inject."""
    keys: List[bytes]
    chunks: List[Tuple[np.ndarray, np.ndarray]]   # per-chunk (k, v)
    cached_tokens: int                            # capped, == num_prefilled
    # wall seconds the tier walk took (the kv_prefetch trace span —
    # the request paid this before it could even queue)
    wait_s: float = 0.0


class KVConnector:
    def __init__(self, runner, model_cfg, engine_cfg, cfg: KVTransferConfig,
                 store: Optional[KVStore] = None):
        self.runner = runner
        self.cfg = cfg
        if runner.cache.layout != "kv_heads":
            raise ValueError(
                f"KV transfer: the KV pool of {model_cfg.name} has the "
                f"layout {runner.cache.layout!r} (latent attention: one "
                f"[c | k_rope] vector a token); tiers, codecs and the "
                f"wire hold K and V per kv head and cannot carry it")
        self.chunk_size = cfg.chunk_size
        # namespace by the WIRE dtype, not the pool dtype: an int8 pool
        # extracts/injects full-precision (bf16) chunks, so int8 and
        # bf16 engines of the same model share one tier namespace —
        # the documented mixed-kvCacheDtype producer/consumer handoff
        wire_dtype = ("bfloat16" if runner.cache.quantized
                      else engine_cfg.kv_dtype)
        self.hasher = ChunkHasher(
            cfg.chunk_size,
            namespace=model_fingerprint(model_cfg, wire_dtype))
        self.store = store if store is not None else make_store(
            local_cpu_bytes=int(cfg.local_cpu_gb * (1 << 30)),
            local_disk_path=cfg.local_disk_path,
            local_disk_bytes=int(cfg.local_disk_gb * (1 << 30)),
            remote_url=cfg.remote_url,
            remote_connect_timeout_s=cfg.remote_connect_timeout_s,
            remote_io_timeout_s=cfg.remote_io_timeout_s,
            remote_breaker_threshold=cfg.remote_breaker_threshold,
            remote_breaker_cooldown_s=cfg.remote_breaker_cooldown_s)
        if self.store is None:
            raise ValueError("KV transfer enabled but no tier configured")
        # (a chunk holds every POOL layer: a layer and pass of a looped
        # model, ModelConfig.pool_layers)
        shape = (model_cfg.pool_layers, cfg.chunk_size,
                 model_cfg.num_kv_heads, model_cfg.head_dim_)
        self._chunk_shape = shape
        # bf16 numpy dtype comes from ml_dtypes (jax dependency)
        import ml_dtypes
        dtype_map = {"bfloat16": np.dtype(ml_dtypes.bfloat16),
                     "float32": np.dtype(np.float32)}
        # int8 pools extract/inject FULL-PRECISION chunks (the runner
        # dequantizes out and re-quantizes in, runner.extract_chunk /
        # inject_chunk) — tiers always hold portable bf16/f32 bytes
        kv_dtype = ("bfloat16" if runner.cache.quantized
                    else str(runner.cache.k.dtype))
        if kv_dtype not in dtype_map:
            raise ValueError(f"KV tiering does not support kv dtype "
                             f"{kv_dtype!r} (supported: {list(dtype_map)})")
        self._np_dtype = dtype_map[kv_dtype]
        self._chunk_bytes = int(np.prod(shape)) * self._np_dtype.itemsize
        if cfg.tier_codecs and store is None:
            # wrap each configured tier with its codec (kvplane): the
            # wrap happens on the tiers the connector itself built; an
            # injected test store is used as-is
            from production_stack_tpu.kvcache.codec import \
                apply_tier_codecs
            self.store = apply_tier_codecs(
                self.store, dict(cfg.tier_codecs),
                np_dtype=self._np_dtype,
                head_dim=model_cfg.head_dim_,
                chunk_body_bytes=2 * self._chunk_bytes)
        # shared pool for pipelined chunk reads (consumer role only)
        self._fetcher = None
        if cfg.is_consumer:
            from production_stack_tpu.kvcache.pipeline import \
                PipelinedFetcher
            self._fetcher = PipelinedFetcher(
                workers=cfg.prefetch_workers if cfg.prefetch_pipeline
                else 1)
        # writer thread: (keys, [(k_dev, v_dev)]) tuples; bounded so a slow
        # remote tier backpressures into drops, never into the engine loop
        self._save_q: "queue.Queue" = queue.Queue(maxsize=64)
        self._inflight = threading.Event()   # a popped item is being written
        self._stop = threading.Event()
        self._writer = threading.Thread(target=self._writer_loop,
                                        name="kv-writer", daemon=True)
        self._writer.start()
        # engine-thread dedup of keys already queued/saved this process
        self._seen_keys: "dict[bytes, None]" = {}
        self._seen_cap = 65536
        self.queries = 0
        self.query_tokens = 0
        self.hit_tokens = 0
        # hits on chunks this process never published or fetched before:
        # another replica produced them (the cross-replica share the
        # kvshare rig measures). Re-fetches of a chunk this process has
        # already seen count as plain hits only.
        self.foreign_hit_tokens = 0
        self.chunk_hits = 0
        self.chunk_misses = 0       # walk-terminating misses
        self.bytes_loaded = 0       # tier bytes materialized by prefetch
        self.bytes_saved = 0        # tier bytes written through
        self.published_chunks = 0   # producer: chunks written through
        self.progress_published_chunks = 0   # ...of which mid-prefill
        self.rejected_chunks = 0    # size/checksum-invalid values
        self.prefetch_deadline_hits = 0
        # walks cut because ONE chunk blew its fair-share slice (the
        # per-remaining-chunk deadline accounting)
        self.prefetch_chunk_deadline_hits = 0
        # chunk reads issued while an earlier chunk was still being
        # consumed (pipelined overlap evidence)
        self.pipelined_fetches = 0
        self.dropped_saves = 0
        # chunk hits by the tier that served them (cpu / disk / remote)
        self.tier_hits: "dict[str, int]" = {}
        # kvplane migration accounting: chunks published by migrate_out
        # on this (source) replica / chunks pulled warm by the admin
        # warm endpoint on this (destination) replica
        self.migrated_chunks = 0
        self.warmed_chunks = 0
        # phase-latency sink (tracing.PhaseHistograms, ("phase",) keyed)
        # — the owning engine attaches its metrics.engine_phases so
        # kv_prefetch / kv_publish durations land next to the request
        # phases; None (tests constructing a bare connector) records
        # nothing
        self.phase_recorder = None

    # -- consumer path --------------------------------------------------

    def prefetch(self, prompt_tokens: Sequence[int],
                 salt: str = "") -> Optional[Prefetch]:
        """Fetch the longest cached chunk-prefix into host memory.

        Runs off the engine loop (server thread at request-add time). The
        last prompt token is never served from cache — prefill must compute
        at least one position to produce first-token logits — so hits are
        capped at len(prompt)-1. ``salt`` keys KV variants (LoRA adapter
        name) so adapter-colored chunks never serve other models.
        """
        if not self.cfg.is_consumer:
            return None
        import time
        n = len(prompt_tokens)
        self.queries += 1
        self.query_tokens += n
        keys = self.hasher.chunk_keys(prompt_tokens, salt=salt)
        chunks: List[Tuple[np.ndarray, np.ndarray]] = []
        hit_keys: List[bytes] = []
        foreign: List[bool] = []
        # hard budget on the whole walk, accounted per remaining chunk
        # and pipelined across `prefetch_workers` concurrent tier reads
        # (kvcache/pipeline.py): a slow tier costs bounded overlap, not
        # serialized TTFT, and one stalled chunk can no longer consume
        # the budget every later chunk was owed
        t0 = time.monotonic()
        fetched, walk = self._fetcher.fetch_walk(
            keys, self.store.get_with_tier,
            self.cfg.prefetch_timeout_s)
        if walk.deadline_hits or walk.chunk_deadline_hits:
            self.prefetch_deadline_hits += 1
            self.prefetch_chunk_deadline_hits += walk.chunk_deadline_hits
        elif len(fetched) < len(keys):
            self.chunk_misses += 1
        self.pipelined_fetches += walk.pipelined_fetches
        for key, val, tier in fetched:
            kv = self._deserialize(key, val)
            if kv is None:
                break
            self.chunk_hits += 1
            if tier:
                self.tier_hits[tier] = self.tier_hits.get(tier, 0) + 1
            self.bytes_loaded += len(val)
            foreign.append(key not in self._seen_keys)
            chunks.append(kv)
            hit_keys.append(key)
        wait_s = time.monotonic() - t0
        if self.phase_recorder is not None:
            self.phase_recorder.observe("kv_prefetch", wait_s)
        if not chunks:
            return None
        cached = min(len(chunks) * self.chunk_size, n - 1)
        self.hit_tokens += cached
        for i, is_foreign in enumerate(foreign):
            if is_foreign:
                self.foreign_hit_tokens += max(
                    0, min(self.chunk_size, cached - i * self.chunk_size))
        return Prefetch(keys=hit_keys, chunks=chunks, cached_tokens=cached,
                        wait_s=wait_s)

    def inject(self, prefetch: Prefetch, slot: int) -> None:
        """Dispatch cached chunks into the slot (engine loop; device work
        is async, ordered before the next cache-donating step)."""
        for i, (k, v) in enumerate(prefetch.chunks):
            self.runner.inject_chunk(slot, i * self.chunk_size, k, v)
        self.mark_seen(prefetch.keys)

    def mark_seen(self, keys) -> None:
        """Record keys the tier already holds (skip re-publish at
        finish) — also used when the HBM prefix pool wins admission and
        the prefetched chunks are dropped without injection."""
        for key in keys:
            self._mark_seen(key)

    # -- producer path --------------------------------------------------

    def on_prefill_progress(self, seq, salt: str = "") -> None:
        """Publish full PROMPT chunks as soon as they are prefilled.

        Disaggregated prefill overlap: the decode engine can start
        pulling the prefix while the producer is still chunk-prefilling
        a long prompt — without this, KV only became visible at
        ``on_finish``, serializing the two pools. Chunk keys dedup via
        _seen_keys, so the later on_finish pass skips everything
        published here.
        """
        if not self.cfg.is_producer:
            return
        self._publish(seq, seq.prompt_tokens[:seq.num_prefilled],
                      getattr(seq, "slot", -1), salt, progress=True)

    def on_finish(self, seq, salt: str = "") -> None:
        """Queue full-chunk KV of a finished sequence for write-through.

        The final sampled token is excluded: decode writes KV for its
        *input* token, and a finished sequence's last token is never fed
        back — its KV position was never computed, so a chunk covering it
        would poison the shared cache with stale slot contents.
        """
        if not self.cfg.is_producer:
            return
        self._publish(seq, (seq.prompt_tokens + seq.output_tokens)[:-1],
                      getattr(seq, "slot", -1), salt)

    def on_migrate(self, seq, salt: str = "") -> List[bytes]:
        """Publish a LIVE sequence's computed full chunks for kvplane
        migration and return every key of that computed range (already
        published ones included — the destination warms them all).

        Mid-prefill victims publish only their prefilled prompt
        prefix; decoding victims publish like ``on_finish`` (the last
        sampled token's KV position was never computed). Runs on the
        engine loop under the engine lock, same as
        ``on_prefill_progress`` — the write-through itself happens on
        the writer thread, and ``flush()`` afterwards makes it tier-
        visible before the planner re-homes routing."""
        if not self.cfg.is_producer:
            return []
        if seq.num_prefilled < len(seq.prompt_tokens):
            tokens = seq.prompt_tokens[:seq.num_prefilled]
        else:
            tokens = (seq.prompt_tokens + seq.output_tokens)[:-1]
        n_chunks = self.hasher.num_full_chunks(len(tokens))
        if n_chunks == 0:
            return []
        keys = self.hasher.chunk_keys(tokens, salt=salt)[:n_chunks]
        self._publish(seq, tokens, getattr(seq, "slot", -1), salt)
        self.migrated_chunks += len(keys)
        return keys

    def _publish(self, seq, tokens, slot: int, salt: str,
                 progress: bool = False) -> None:
        n_chunks = self.hasher.num_full_chunks(len(tokens))
        if n_chunks == 0 or slot < 0:
            return
        # the key chain is cached on the sequence and extended
        # incrementally — progressive publish runs once per prefill
        # chunk, and restarting the chain each time would be quadratic
        state = getattr(seq, "kv_publish_state", None)
        start_chunk = state[0] if state else 0
        new_keys, state = self.hasher.chain_keys(tokens, salt=salt,
                                                 state=state)
        seq.kv_publish_state = state
        work = []
        for i, key in enumerate(new_keys, start=start_chunk):
            if key in self._seen_keys:
                continue
            k_dev, v_dev = self.runner.extract_chunk(
                slot, i * self.chunk_size, self.chunk_size)
            # the progress flag rides to the writer: a chunk only
            # counts as progress-published once its put SUCCEEDS (a
            # dropped batch or failed save must not satisfy the
            # overlap evidence the disagg rig gates on)
            work.append((key, k_dev, v_dev, progress))
            self._mark_seen(key)
        if not work:
            return
        try:
            self._save_q.put_nowait(work)
        except queue.Full:
            self.dropped_saves += len(work)
            for key, _, _, _ in work:   # allow a retry on a later finish
                self._seen_keys.pop(key, None)

    def _writer_loop(self) -> None:
        while not self._stop.is_set():
            try:
                work = self._save_q.get(timeout=0.2)
            except queue.Empty:
                continue
            self._inflight.set()
            import time as _time
            t0 = _time.monotonic()
            try:
                for key, k_dev, v_dev, progress in work:
                    try:
                        val = self._serialize(k_dev, v_dev)
                        if self.store.put(key, val):
                            self.bytes_saved += len(val)
                            self.published_chunks += 1
                            if progress:
                                # tier-visible while later chunks were
                                # still prefilling (disagg overlap)
                                self.progress_published_chunks += 1
                    except Exception as e:   # never kill the writer
                        logger.warning("KV save failed: %s", e)
            finally:
                self._inflight.clear()
                if self.phase_recorder is not None:
                    # publish latency per write-through batch: D2H sync
                    # + serialization + tier puts, on the writer thread
                    # — the cost a slow tier charges the publish path
                    self.phase_recorder.observe(
                        "kv_publish", _time.monotonic() - t0)

    # -- serialization ---------------------------------------------------

    # trailing full-chunk integrity digest: a torn or bit-flipped value
    # surfacing from any tier (a killed replica mid-publish, a corrupt
    # disk file) must read as a MISS, never inject garbage KV
    _DIGEST_BYTES = 8

    @staticmethod
    def _digest(data) -> bytes:
        import hashlib
        return hashlib.blake2b(
            data, digest_size=KVConnector._DIGEST_BYTES).digest()

    def _serialize(self, k_dev, v_dev) -> bytes:
        k = np.asarray(k_dev)     # blocks until D2H completes
        v = np.asarray(v_dev)
        body = k.tobytes() + v.tobytes()
        return body + self._digest(body)

    def _deserialize(self, key: bytes, val: bytes) -> \
            Optional[Tuple[np.ndarray, np.ndarray]]:
        want = 2 * self._chunk_bytes + self._DIGEST_BYTES
        if len(val) != want:
            logger.warning("KV chunk size mismatch: %d != %d (evicting "
                           "%s)", len(val), want, key.hex()[:16])
            self._reject(key)
            return None
        body, digest = val[:-self._DIGEST_BYTES], val[-self._DIGEST_BYTES:]
        if self._digest(body) != digest:
            logger.warning("KV chunk checksum mismatch (evicting %s)",
                           key.hex()[:16])
            self._reject(key)
            return None
        k = np.frombuffer(val, self._np_dtype, count=int(
            np.prod(self._chunk_shape))).reshape(self._chunk_shape)
        v = np.frombuffer(val, self._np_dtype, offset=self._chunk_bytes,
                          count=int(np.prod(self._chunk_shape))).reshape(
                              self._chunk_shape)
        return k, v

    def _reject(self, key: bytes) -> None:
        """Invalid tier value: count it and delete the poisoned key so
        the next producer pass can republish a good copy."""
        self.rejected_chunks += 1
        try:
            self.store.delete(key)
        except Exception:      # deletion is best-effort cleanup
            pass
        self._seen_keys.pop(key, None)

    # -- misc ------------------------------------------------------------

    def _mark_seen(self, key: bytes) -> None:
        self._seen_keys[key] = None
        while len(self._seen_keys) > self._seen_cap:
            self._seen_keys.pop(next(iter(self._seen_keys)))

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.query_tokens if self.query_tokens \
            else 0.0

    def remote_breaker_open(self) -> bool:
        """True while the remote tier (if any) is being skipped."""
        from production_stack_tpu.kvcache.store import (RemoteStore,
                                                        TieredStore)
        stores = self.store.tiers if isinstance(self.store, TieredStore) \
            else [self.store]
        # a codec-wrapped tier hides the RemoteStore one level down
        stores = [getattr(s, "inner", s) for s in stores]
        return any(s.breaker_open() for s in stores
                   if isinstance(s, RemoteStore))

    def codec_stats(self) -> list:
        """Per-tier codec accounting ({tier, codec, bytes_in/out,
        rejects}) — empty when no tier_codecs are configured."""
        from production_stack_tpu.kvcache.codec import codec_stats_of
        return codec_stats_of(self.store)

    def warm_keys(self, keys: List[bytes]) -> Tuple[int, int]:
        """Pull raw chunk values for ``keys`` through the tier walk so
        hits promote into this replica's fastest tier (the kvplane
        migration destination path: the planner hands over the keys the
        source's migrate_out published). No deserialization — the
        promotion side effect IS the work. Returns (warmed, missed)."""
        warmed = missed = 0
        for key in keys:
            val, _tier = self.store.get_with_tier(key)
            if val is None:
                missed += 1
            else:
                warmed += 1
                self.warmed_chunks += 1
                self._mark_seen(key)
        return warmed, missed

    def tier_stats(self) -> dict:
        """{tier_name: {bytes, count, ...}} for the occupancy gauges."""
        try:
            return self.store.tier_stats()
        except Exception as e:    # a sick tier must not break /load
            logger.warning("KV tier stats failed: %s", e)
            return {}

    def stats_report(self) -> dict:
        """Counters surfaced on /load (and deltas fed to /metrics):
        everything the cache-aware router and the kvshare rig read."""
        return {
            # the engine's disagg role: the router's pool wiring and
            # the disagg rig read it off /load for topology checks
            "role": self.cfg.kv_role,
            "queries": self.queries,
            "query_tokens": self.query_tokens,
            "hit_tokens": self.hit_tokens,
            "foreign_hit_tokens": self.foreign_hit_tokens,
            "hit_rate": round(self.hit_rate, 4),
            "chunk_hits": self.chunk_hits,
            "chunk_misses": self.chunk_misses,
            "bytes_loaded": self.bytes_loaded,
            "bytes_saved": self.bytes_saved,
            "published_chunks": self.published_chunks,
            "progress_published_chunks": self.progress_published_chunks,
            "rejected_chunks": self.rejected_chunks,
            "dropped_saves": self.dropped_saves,
            "prefetch_deadline_hits": self.prefetch_deadline_hits,
            "prefetch_chunk_deadline_hits":
                self.prefetch_chunk_deadline_hits,
            "pipelined_fetches": self.pipelined_fetches,
            "migrated_chunks": self.migrated_chunks,
            "warmed_chunks": self.warmed_chunks,
            "codecs": self.codec_stats(),
            "tier_hits": dict(self.tier_hits),
            "remote_breaker_open": self.remote_breaker_open(),
            # remote occupancy lives on the cache server's own surface;
            # its local entry carries only breaker state (no bytes)
            "tiers": {name: {"bytes": st.get("bytes", 0),
                             "count": st.get("count", 0)}
                      for name, st in self.tier_stats().items()
                      if "bytes" in st},
        }

    def flush(self, timeout: float = 30.0) -> None:
        """Block until queued saves are written (tests/shutdown)."""
        import time
        deadline = time.monotonic() + timeout
        while (not self._save_q.empty() or self._inflight.is_set()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)

    def close(self) -> None:
        self.flush(timeout=5.0)
        self._stop.set()
        self._writer.join(timeout=5.0)
        if self._fetcher is not None:
            self._fetcher.close()
        self.store.close()
