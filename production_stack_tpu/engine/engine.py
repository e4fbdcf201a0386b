"""LLMEngine: the synchronous continuous-batching core.

One ``step()`` = one unit of device work (a prefill chunk or a fused
decode over all running slots) plus host bookkeeping (sampling-param
assembly, stop detection, metrics). The async server drives this loop on
a dedicated thread (see server.py); batch composition changes never
recompile because shapes are static.
"""

import itertools
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.sampler import SamplingParams
from production_stack_tpu.engine.scheduler import (RequestWaits, Scheduler,
                                                   SamplingOptions,
                                                   SeqStatus, Sequence)
from production_stack_tpu.engine.tokenizer import (DetokenizeStream,
                                                   load_tokenizer)
from production_stack_tpu.models.config import get_config
from production_stack_tpu.models.hf_loader import load_checkpoint
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


@dataclass
class StepOutput:
    seq_id: str
    new_token: Optional[int]
    text_delta: str
    finished: bool
    finish_reason: Optional[str]
    # chosen token's log p under the raw model distribution (runner)
    logprob: Optional[float] = None
    # top_logprobs alternatives [(token_id, logprob)] when requested
    top_alts: Optional[list] = None
    # terminal outputs only: the sequence's phase timeline (monotonic
    # stamps + KV prefetch cost) so the server can render engine-side
    # trace spans without reaching into scheduler internals
    # (tracing.py; docs/observability.md "Tracing")
    timing: Optional[dict] = None
    # first-token and terminal outputs only: the sequence's
    # RequestWaits, for AsyncLLMEngine to stamp the moment the output
    # is handed to the request's queue
    waits: Optional[RequestWaits] = None


@dataclass
class _Window:
    """One decode window in flight: dispatched, not yet synced. The
    arrays are the device's until ``_sync_inflight`` replaces them with
    host copies."""
    ids: object
    lps: object
    counts: object          # None without speculation
    tops: object            # None unless top_logprobs was asked
    steps: int
    seqs: List[Sequence]    # the running sequences at dispatch
    # where its seconds start: the exit stamp of its decode_dispatch
    # phase, moved up to the previous sync's return at its own sync
    t0: float
    spec_ok: object
    kv_len: int
    batch: int
    host_s: float           # decode_host + decode_dispatch spent on it
    # what each step counted (models/llama.Work of [steps] leaves, as
    # ModelRunner.decode hands it), by named member: ``experts_read``,
    # the experts whose weights it read summed over the layers (a MoE
    # model), and ``loop``, its passes (a looped one); a member is None
    # where the model has no such part
    work: object = None


@dataclass
class _Prefill:
    """One prefill dispatch in flight: its place in the device queue is
    its place in ``_inflight``. What needs its result (a first token,
    prefix registration, connector progress) happens when it is
    retired (_land_prefill)."""
    group: list             # the PrefillWork of each row, in row order
    # device (ids, logprobs, tops) by row, and the rows the experts
    # multiplied (None on a dense model)
    devs: tuple
    # each row's sequence's admit_time at dispatch: a sequence that was
    # preempted since, admitted again or not, is not this entry's
    admitted: List[float]
    # the sequences whose first token was joined to the decode carry on
    # the device (_join_carry): the device is one token past the host
    # for them, plus the windows dispatched since
    joined: List[Sequence]


# finished sequences kept for post-hoc inspection (bounded; see _remember)
_FINISHED_RETENTION = 1024


class AdmissionRejected(Exception):
    """Bounded admission (cfg.max_waiting_seqs): the waiting queue is
    full, so the request is shed at submit time instead of queuing
    forever. The server maps this to 503 + Retry-After; the router
    treats that answer as shed-not-sick (router/resilience.py)."""

    def __init__(self, queue_depth: int, retry_after_s: float):
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s
        super().__init__(
            f"engine overloaded: {queue_depth} sequences already "
            f"waiting (max_waiting_seqs reached); retry in "
            f"~{retry_after_s:.1f}s")


class DeadlineExceeded(Exception):
    """The request's deadline (x-request-deadline-ms) expired while it
    was still WAITING; the scheduler dropped it before prefill. The
    server maps this to 504 with an x-deadline-expired marker."""


_LISTENING = False      # this process's jax.monitoring has the listeners


def _listen_to_builds() -> None:
    """Register efficiency.BUILD_EVENTS with ``jax.monitoring``, once a
    process (its registry is the process's, and only grows): the
    scalar that opens a timed part, the duration that closes it, the
    cache's events. They fire only when JAX traces, lowers, compiles
    or loads; a step served from the runner's table reaches none."""
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    from production_stack_tpu.engine.efficiency import BUILD_EVENTS
    jax.monitoring.register_scalar_listener(BUILD_EVENTS.began)
    jax.monitoring.register_event_duration_secs_listener(
        BUILD_EVENTS.lasted)
    jax.monitoring.register_event_listener(BUILD_EVENTS.happened)


class LLMEngine:
    def __init__(self, engine_cfg: EngineConfig, params=None, mesh=None):
        self.cfg = engine_cfg
        self.model_cfg = get_config(engine_cfg.model)
        # honor --dtype (validated to bfloat16/float32 in EngineConfig;
        # the reference passes --dtype down to vllm serve the same way,
        # reference: helm/templates/deployment-vllm-multi.yaml:80-83)
        want_dtype = jnp.bfloat16 if engine_cfg.dtype == "bfloat16" \
            else jnp.float32
        if self.model_cfg.dtype != want_dtype:
            import dataclasses
            self.model_cfg = dataclasses.replace(self.model_cfg,
                                                 dtype=want_dtype)
        if (engine_cfg.moe_capacity_factor is not None
                and engine_cfg.moe_capacity_factor
                != self.model_cfg.moe_capacity_factor):
            import dataclasses
            self.model_cfg = dataclasses.replace(
                self.model_cfg,
                moe_capacity_factor=engine_cfg.moe_capacity_factor)
        self.tokenizer = load_tokenizer(engine_cfg.model,
                                        engine_cfg.tokenizer,
                                        engine_cfg.chat_template)
        load_t0 = time.monotonic()
        if params is None and engine_cfg.checkpoint:
            params = load_checkpoint(self.model_cfg, engine_cfg.checkpoint)
        weights_loaded_s = time.monotonic() - load_t0
        # multi-LoRA: every adapter is served as its own model id; the
        # stacked adapter pytree rides in the runner, rows select their
        # adapter per request (reference surface: --enable-lora +
        # proposals/lora-k8s-support.md routing by served model name)
        self.lora_ids: Dict[str, int] = {}
        # runtime adapter pool (load_adapter/evict_adapter): rows are
        # APPEND-ONLY — adapter id == row index + 1 forever, so an
        # evicted name can vanish from the catalog while in-flight
        # sequences keep a valid row. The config is pinned at first
        # use: every adapter in one engine shares rank/targets (the
        # stacked-pytree contract).
        self._lora_cfg = None
        self._lora_rows: List = []
        self.adapter_loads = 0
        self.adapter_evictions = 0
        lora_stacked, lora_scaling = None, 1.0
        if engine_cfg.lora_adapters:
            from production_stack_tpu.models import lora as lora_mod
            lcfg = self._ensure_lora_cfg()
            for name, src in sorted(engine_cfg.lora_adapters.items()):
                self._lora_rows.append(self._build_adapter(name, src))
                self.lora_ids[name] = len(self._lora_rows)
            lora_stacked = lora_mod.stack_adapters(self.model_cfg, lcfg,
                                                   self._lora_rows)
            lora_scaling = lcfg.scaling
        self.served_models = [engine_cfg.model] + list(self.lora_ids)
        if mesh is None and (engine_cfg.tensor_parallel_size > 1
                             or engine_cfg.expert_parallel_size > 1):
            from production_stack_tpu.parallel.mesh import (MeshConfig,
                                                            build_mesh)
            tp = engine_cfg.tensor_parallel_size
            ep = engine_cfg.expert_parallel_size
            if ep > 1:
                E = self.model_cfg.num_experts
                if not E:
                    raise ValueError(
                        f"expert_parallel_size={ep} but model "
                        f"{self.model_cfg.name!r} is dense (no experts)")
                if E % ep:
                    raise ValueError(
                        f"expert_parallel_size={ep} does not divide "
                        f"num_experts={E}")
            mesh = build_mesh(MeshConfig(dp=1, tp=tp, ep=ep),
                              jax.devices()[:tp * ep])
        # what this engine runs on, said BEFORE the weights are built (a
        # launcher that must stay off JAX reads it here first, and in
        # full from GET /debug/perf): the devices its arrays live on —
        # the mesh's, else the default one — and the per-chip HBM peak
        # its MBU is quoted against: --hbm-peak-gbps, else the table
        # entry for this device kind, else none — never another chip's
        from production_stack_tpu.engine.efficiency import (
            HBM_PEAK_GBPS, EngineEffAccounting)
        # before the first jit (the weights' init): what JAX traces,
        # lowers, compiles or loads is told to the accounting
        _listen_to_builds()
        from production_stack_tpu.ops import pallas_paged
        self.devices = (list(mesh.devices.flat) if mesh is not None
                        else jax.devices()[:1])
        kind = self.devices[0].device_kind
        peak_gbps = engine_cfg.hbm_peak_gbps or HBM_PEAK_GBPS.get(kind)
        if peak_gbps is None:
            logger.info("no HBM peak known for device kind %r: MBU is "
                        "not reported (--hbm-peak-gbps sets one)", kind)
        logger.info(
            "engine device: platform=%s device_kind=%r devices=%d "
            "(process sees %d) hbm_peak_gbps=%s pallas_attention=%s",
            self.devices[0].platform, kind, len(self.devices),
            jax.device_count(), peak_gbps, pallas_paged.mode())
        self.runner = ModelRunner(self.model_cfg, engine_cfg, params=params,
                                  mesh=mesh, lora_stacked=lora_stacked,
                                  lora_scaling=lora_scaling,
                                  weights_loaded_s=weights_loaded_s)
        self.scheduler = Scheduler(engine_cfg.max_num_seqs,
                                   engine_cfg.max_model_len,
                                   engine_cfg.prefill_chunk)
        self.metrics = EngineMetrics(self.model_cfg.name)
        self.metrics.adapters_loaded.set(len(self.lora_ids))
        # paged-KV block accounting (engine/block_manager.py): admission
        # allocates each prompt's blocks, decode windows extend tables
        # on demand, and prefix caching is refcounted block SHARING —
        # zero-copy prefix hits (the reference's --enable-prefix-caching,
        # helm/templates/deployment-vllm-multi.yaml:73-75)
        from production_stack_tpu.engine.block_manager import BlockManager
        from production_stack_tpu.kvcache.chunks import model_fingerprint
        self.block_mgr = BlockManager(
            self.runner.cache.num_blocks, engine_cfg.kv_block_size,
            enable_prefix_caching=engine_cfg.enable_prefix_caching,
            namespace=model_fingerprint(self.model_cfg,
                                        engine_cfg.kv_dtype),
            bytes_per_token=self.runner.cache.bytes_per_token,
            layout=self.runner.cache.layout,
            index_bytes_per_token=self.runner.cache.index_bytes_per_token,
            state_pages=self.runner.cache.state_pages,
            state_bytes_per_slot=self.runner.cache.state_bytes_per_slot,
            pool_layers=(0 if self.runner.cache.k is None
                         else self.runner.cache.k.shape[0]),
            reader_layers=(self.model_cfg.reader_layers
                           if self.runner.cache.k is not None else 0),
            weight_layers=self.model_cfg.num_layers)
        # a slot's row: its blocks and, where the model keeps state a
        # sequence, its state page as one more column (models/kv.
        # split_tables); an empty row names trash block and trash page
        self._tables = np.zeros(self.runner.table_shape, np.int32)
        self.scheduler.can_admit = self._try_admit
        self.scheduler.on_admit = self._on_admit
        # pool-occupancy-at-allocation histogram: the block manager
        # stays metrics-free, the metrics layer owns the plain-int
        # buckets (one bisect per allocation attempt, not per token)
        self.block_mgr.on_alloc_occupancy = \
            self.metrics.kvpool_occ_hist.observe
        # kvplane defrag trigger state: fragmented-failure count at the
        # last end-of-step check (engine lock only)
        self._defrag_seen_failures = 0
        # engine efficiency accounting (engine/efficiency.py;
        # docs/engine.md "Efficiency telemetry"): classifies every
        # fused window's token-steps, models HBM traffic for the
        # effective-bandwidth/MBU gauges, and stamps XLA compiles.
        # Byte model inputs are host-side metadata only (no device
        # sync): the full parameter footprint and the per-position KV
        # read cost (K+V across layers/heads, plus the int8 cache's
        # f32 scales).
        mc = self.model_cfg
        kv_itemsize = {"bfloat16": 2, "float32": 4,
                       "int8": 1}[engine_cfg.kv_dtype]
        # (every POOL layer: a looped model's passes keep K and V of
        # their own, ModelConfig.pool_layers)
        kv_pos_bytes = (2 * mc.pool_layers * mc.num_kv_heads
                        * mc.head_dim_ * kv_itemsize)
        if engine_cfg.kv_dtype == "int8":
            # per-(token, head) f32 scales stream alongside the blocks
            kv_pos_bytes += 2 * mc.pool_layers * mc.num_kv_heads * 4
        def tree_bytes(tree) -> int:
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(tree))
        weight_bytes = tree_bytes(self.runner.params)
        # one expert's gate, up and down (and scales) in one layer: what
        # a decode step leaves unread of an expert off its list
        expert_bytes = tree_bytes(self.runner.expert_stacks()) // (
            self._resident_layers() * mc.num_experts
        ) if mc.num_experts else 0
        self.eff = EngineEffAccounting(
            weight_bytes=weight_bytes,
            kv_position_bytes=kv_pos_bytes,
            # per-chip peak x the chips the weights and KV are spread
            # over: the byte model counts the whole engine's traffic
            hbm_peak_bytes_per_s=(peak_gbps * 1e9 * len(self.devices)
                                  if peak_gbps else None),
            ring_entries=engine_cfg.perf_ring_entries,
            compile_hist=self.metrics.compile_hist,
            expert_bytes=expert_bytes,
            # a looped model: its layers' weights are read once a pass
            loop=None if mc.loop_steps == 1 else {
                "passes": mc.loop_steps, "weight_layers": mc.num_layers,
                "pool_layers": mc.pool_layers,
                "looped_weight_bytes": tree_bytes(
                    self.runner.params["layers"]),
                "head_bytes": tree_bytes(self.runner.params["lm_head"])},
            annotate=jax.profiler.TraceAnnotation,
            queue_depth=self._device_queue_depth)
        # the step timeline (efficiency.STEP_PHASES): every phase of
        # step() runs under `with self._phase(name)`
        self._phase = self.eff.phase
        if self.block_mgr.keeps_pages:
            self.eff.state_pages = self.block_mgr.page_counts
        # bytes of one sequence's state page, all layers (0: none)
        self._state_page_bytes = self.runner.cache.state_bytes_per_slot
        # layers that read another layer's K/V (0: every layer its own)
        self._cross_layers = mc.reader_layers - mc.attn_layers
        self.runner.compile_observer = self.eff
        # the ``startup`` block's spans are the runner's own dict: its
        # ``weights_s`` arrives when the device has the parameters
        self.eff.startup_spans = self.runner.startup_spans
        # advertised once: the router's per-endpoint concurrency cap
        # reads this gauge (0 = unbounded admission, nothing to cap on)
        self.metrics.capacity.set(
            engine_cfg.max_num_seqs + engine_cfg.max_waiting_seqs
            if engine_cfg.max_waiting_seqs is not None else 0)
        # KV tiering (HBM→host→disk→remote; kvcache/): the reference wires
        # the same capability through LMCache env + --kv-transfer-config
        # (reference: helm/templates/deployment-vllm-multi.yaml:94-99,154-178)
        self.connector = None
        if engine_cfg.kv_transfer_config:
            from production_stack_tpu.kvcache.connector import (
                KVConnector, KVTransferConfig)
            tcfg = KVTransferConfig.from_dict(engine_cfg.kv_transfer_config)
            if tcfg.enabled:
                self.connector = KVConnector(self.runner, self.model_cfg,
                                             engine_cfg, tcfg)
                # kv_prefetch / kv_publish durations land in the same
                # phase family as queue_wait/prefill/decode
                self.connector.phase_recorder = \
                    self.metrics.engine_phases
        # rolling KV: models whose EVERY layer is windowed (Mistral
        # v0.1-style) never attend positions behind the window again, so
        # their blocks are freed as generation advances — live-context
        # HBM bounded by W instead of total length. Off where some layer
        # sees every key (ModelConfig.window_everywhere: Gemma-2's
        # global layers, a decoder-hybrid-decoder's full layer) and under KV
        # tiering (tier extraction reads from position 0).
        self._roll_window = (self.model_cfg.window_everywhere
                             if self.connector is None else None)
        self.seqs: Dict[str, Sequence] = {}
        self._finished_order: List[str] = []
        self._id_counter = itertools.count()
        # EWMA of finished-request wall time (arrival -> finish),
        # seeding the load report's queue-delay estimate before any
        # request has completed
        self._service_ewma = 0.5
        # guards scheduler state across the engine-loop and server threads
        self._lock = threading.RLock()
        # per-slot host mirrors feeding the decode batch. Free/prefilling
        # slots sit at position S: their garbage window writes DUS-clamp
        # onto S-1, which is safe because every forward writes a row's
        # real K/V BEFORE attention reads the cache — any query that
        # legitimately reaches position S-1 overwrites the garbage in the
        # same executable that first attends it (see models/kv.py).
        B = engine_cfg.max_num_seqs
        self._slot_token = np.zeros((B,), np.int32)
        self._slot_pos = np.full((B,), engine_cfg.max_model_len, np.int32)
        self._slot_temp = np.full((B,), 1.0, np.float32)
        self._slot_top_p = np.ones((B,), np.float32)
        self._slot_top_k = np.zeros((B,), np.int32)
        self._slot_adapter = np.zeros((B,), np.int32)
        self._slot_seed = np.zeros((B,), np.int32)
        # OpenAI/vLLM logit-shaping mirrors (engine/sampler.py); all
        # default-inert so unshaped batches compile the ordinary
        # executables
        from production_stack_tpu.engine.sampler import (LOGIT_BIAS_K,
                                                         MIN_TOKENS_STOP_K)
        self._slot_presence = np.zeros((B,), np.float32)
        self._slot_frequency = np.zeros((B,), np.float32)
        self._slot_repetition = np.ones((B,), np.float32)
        self._slot_min_p = np.zeros((B,), np.float32)
        self._slot_min_tokens = np.zeros((B,), np.int32)
        self._slot_prompt_len = np.zeros((B,), np.int32)
        self._slot_bias_ids = np.full((B, LOGIT_BIAS_K), -1, np.int32)
        self._slot_bias_vals = np.zeros((B, LOGIT_BIAS_K), np.float32)
        # stop_token_ids masked below min_tokens (sampler.adjust_logits)
        self._slot_stop_ids = np.full((B, MIN_TOKENS_STOP_K), -1, np.int32)
        self.runner._eos_id = int(self.tokenizer.eos_token_id or 0)
        # guided decoding: per-slot DFA-state host mirror (grammar row
        # indices are rebuilt per dispatch from the sequences)
        self._slot_gstate = np.zeros((B,), np.int32)
        self._guided_key = None      # tuple of active patterns
        self._guided_table = None    # device [G+1, S, V] int32
        self._guided_gids = {}       # pattern -> row index
        # device-resident sampling params, re-uploaded only when a slot's
        # options change (admission/finish), never per decode window
        self._dev_sampling = None
        self._sampling_dirty = True
        # decode inputs are device-carried across windows (runner); the
        # host re-uploads its mirrors only when this is set (admission,
        # finish, abort — any slot-composition change)
        self._decode_dirty = True
        # speculative-ngram history re-upload flag: tracked separately
        # because the history matrix is only (re)built for windows that
        # actually speculate — a stale device history can only degrade
        # DRAFT quality, never correctness (verification ignores it)
        self._hist_dirty = True
        # what is queued on the device between step() calls, in
        # dispatch order (FIFO of _Window and _Prefill; docs/engine.md
        # "The in-flight queue").
        # Up to cfg.pipeline_depth windows ride the device queue at once:
        # window N+1 is dispatched BEFORE window N's results are synced,
        # so the device starts N+1 the instant N retires instead of
        # idling one host round-trip. Valid because decode inputs are
        # device-carried; the host only has to stay out of the way
        # (no mirror uploads) until every queued entry is retired. A
        # prefill joins the queue behind them (_prefill_drains says
        # when it may) and the carry is edited by slot on the device.
        self._inflight: List[object] = []
        # (device scalar, routed rows) of the prefill entries retired
        # without a sync of their own, a MoE engine's: the rows their
        # experts multiplied, read at the next sync (_land_prefill)
        self._expert_rows_due: List[tuple] = []
        # the newest result the device was asked for (a window's or a
        # prefill's ids): the device runs its queue in order, so once
        # this is ready nothing of ours is left on it
        # (_device_queue_depth); None: known to be finished
        self._queue_tail = None
        # continuous batching across windows (docs/engine.md
        # "Continuous batching across windows"): the device carry's
        # current batch bucket (dispatches at a different bucket must
        # re-upload the host mirrors), and an EWMA of the per-row-step
        # probability of a non-length stop — the EOS-rate horizon the
        # adaptive window sizing reads so finished tails cannot span a
        # long window even when max_tokens gives no warning
        self._carry_batch = engine_cfg.max_num_seqs
        self._eos_rate = 0.0
        # real embedding encoder (models/encoder.py), built EAGERLY:
        # a lazy first-request load would run checkpoint reading on the
        # server's event loop (stalling every in-flight stream) and
        # race across executor threads; and a bad preset/checkpoint
        # must fail at startup, not at first request
        self._enc_params = None
        self._embed_tok = None
        if engine_cfg.embedding_model:
            self._ensure_encoder()

    # ------------------------------------------------------------------

    def _adapter_salt(self, adapter_id: int) -> str:
        """KV-tier key salt: adapter NAME (stable across processes and
        config orderings, unlike the id) — adapter-colored KV chunks must
        never collide with the base model's or each other's."""
        if adapter_id == 0:
            return ""
        for name, aid in self.lora_ids.items():
            if aid == adapter_id:
                return f"lora:{name}"
        return f"lora-id:{adapter_id}"

    def resolve_model(self, model: Optional[str]) -> int:
        """Served model name -> adapter id (0 = base). Raises on unknown."""
        if model is None or model == self.cfg.model:
            return 0
        if model in self.lora_ids:
            return self.lora_ids[model]
        raise ValueError(f"unknown model {model!r}; serving "
                         f"{self.served_models}")

    # ------------------------------------------------- runtime adapters

    def _ensure_lora_cfg(self):
        if self._lora_cfg is None:
            from production_stack_tpu.models import lora as lora_mod
            self._lora_cfg = lora_mod.LoRAConfig(
                rank=self.cfg.lora_rank, alpha=self.cfg.lora_alpha,
                targets=tuple(self.cfg.lora_targets))
        return self._lora_cfg

    def _build_adapter(self, name: str, src: str):
        lcfg = self._ensure_lora_cfg()
        from production_stack_tpu.models import lora as lora_mod
        if src.startswith("random:"):
            import jax
            return lora_mod.random_adapter(
                self.model_cfg, lcfg,
                jax.random.PRNGKey(int(src.split(":", 1)[1])))
        return lora_mod.load_adapter_npz(self.model_cfg, lcfg, src)

    def load_adapter(self, name: str, src: str) -> bool:
        """Load a LoRA adapter at runtime and start serving it as model
        ``name``. Returns False when the name is already serving
        (idempotent); raises on any failure — the server answers a
        load failure with a structured 503 + Retry-After (a SHED, per
        the r9 shed!=sick contract: a failed weight fetch means "not
        now", never a breaker signal against the engine)."""
        with self._lock:
            if name == self.cfg.model or name in self.lora_ids:
                return False
            new_row = self._build_adapter(name, src)
            from production_stack_tpu.models import lora as lora_mod
            lcfg = self._ensure_lora_cfg()
            rows = self._lora_rows + [new_row]
            stacked = lora_mod.stack_adapters(self.model_cfg, lcfg, rows)
            # restack + device swap BEFORE publishing the id: a request
            # racing in on the new name must never select a row the
            # device pytree does not hold yet
            self.runner.set_lora(stacked, lcfg.scaling)
            self._lora_rows = rows
            self.lora_ids[name] = len(rows)
            self.served_models.append(name)
            self.adapter_loads += 1
            self.metrics.adapter_loads.inc()
            self.metrics.adapters_loaded.set(len(self.lora_ids))
            logger.info("adapter %s loaded from %s (id=%d, %d rows "
                        "stacked)", name, src, len(rows), len(rows))
            return True

    def evict_adapter(self, name: str) -> None:
        """Stop serving adapter ``name``. Raises KeyError when unknown
        (the server answers 404). The stacked row is tombstoned, not
        freed: in-flight sequences carry the adapter id in their device
        sampling rows, and id stability is what keeps them valid —
        only the NAME leaves the catalog, so new requests 404 at
        resolve_model while old ones finish."""
        with self._lock:
            if name not in self.lora_ids:
                raise KeyError(f"adapter {name!r} is not loaded; "
                               f"serving {self.served_models}")
            del self.lora_ids[name]
            self.served_models.remove(name)
            self.adapter_evictions += 1
            self.metrics.adapter_evictions.inc()
            self.metrics.adapters_loaded.set(len(self.lora_ids))
            logger.info("adapter %s evicted (row tombstoned)", name)

    def add_request(self, prompt_tokens: List[int],
                    options: Optional[SamplingOptions] = None,
                    seq_id: Optional[str] = None,
                    model: Optional[str] = None,
                    deadline: Optional[float] = None) -> str:
        if self.eff.startup_marks["first_request"] is None:
            self.eff.mark("first_request")
        seq_id = seq_id or f"seq-{next(self._id_counter)}"
        options = options or SamplingOptions()
        if options.logit_bias:
            # validate at the ENGINE boundary (callers' thread): a bad
            # map must 400 here, not poison step() with an
            # IndexError/OverflowError the engine loop would retry
            # forever
            from production_stack_tpu.engine.sampler import LOGIT_BIAS_K
            if len(options.logit_bias) > LOGIT_BIAS_K:
                raise ValueError(
                    f"logit_bias supports at most {LOGIT_BIAS_K} "
                    f"entries (got {len(options.logit_bias)})")
            V = self.model_cfg.vocab_size
            bad = [t for t in options.logit_bias
                   if not 0 <= int(t) < V]
            if bad:
                raise ValueError(
                    f"logit_bias token id {bad[0]} out of range for "
                    f"vocab size {V}")
        # penalty ranges (vLLM/OpenAI contracts): out-of-range values
        # would silently produce garbage logits, not errors
        if not options.repetition_penalty > 0:
            raise ValueError(
                f"repetition_penalty must be > 0 "
                f"(got {options.repetition_penalty})")
        for fname in ("presence_penalty", "frequency_penalty"):
            val = getattr(options, fname)
            if not -2.0 <= val <= 2.0:
                raise ValueError(
                    f"{fname} must be in [-2, 2] (got {val})")
        if not 0.0 <= options.min_p <= 1.0:
            raise ValueError(f"min_p must be in [0, 1] "
                             f"(got {options.min_p})")
        if options.min_tokens < 0:
            raise ValueError(f"min_tokens must be >= 0 "
                             f"(got {options.min_tokens})")
        if options.min_tokens and options.stop_token_ids:
            # the floor must ban these ids on-device; the mask array is
            # a fixed small width (sampler.MIN_TOKENS_STOP_K)
            from production_stack_tpu.engine.sampler import (
                MIN_TOKENS_STOP_K)
            if len(options.stop_token_ids) > MIN_TOKENS_STOP_K:
                raise ValueError(
                    f"min_tokens supports at most {MIN_TOKENS_STOP_K} "
                    f"stop_token_ids (got {len(options.stop_token_ids)})")
        seq = Sequence(seq_id=seq_id, prompt_tokens=list(prompt_tokens),
                       options=options,
                       adapter_id=self.resolve_model(model),
                       deadline=deadline,
                       detok=DetokenizeStream(self.tokenizer))
        if seq.options.guided_regex:
            from production_stack_tpu.engine import guided
            # compiled per (pattern, tokenizer) with an LRU cache; a bad
            # pattern raises here, on the caller's thread, as ValueError
            seq.grammar = guided.compile_grammar(seq.options.guided_regex,
                                                 self.tokenizer)
        if self.connector is not None:
            # tier lookup + D2H-side fetch runs here, on the caller's
            # thread — never on the engine loop
            seq.kv_prefetch = self.connector.prefetch(
                seq.prompt_tokens, salt=self._adapter_salt(seq.adapter_id))
            if seq.kv_prefetch is not None:
                seq.kv_prefetch_wait_s = seq.kv_prefetch.wait_s
                seq.kv_cached_tokens = seq.kv_prefetch.cached_tokens
        with self._lock:
            seq.waits.locked = time.monotonic()
            # bounded admission: shed at submit rather than queue
            # forever. Admission happens only at step time, so a fresh
            # submit ALWAYS lands in waiting first — the bound is
            # therefore on waiting beyond what the free slots will
            # absorb on the next pass (max_waiting_seqs=0 = "shed
            # anything that cannot be admitted immediately", not "shed
            # everything"). Only never-admitted sequences count —
            # preempted ones re-queue at the front and must not be
            # double-counted against new arrivals (they already hold a
            # client stream).
            if self.cfg.max_waiting_seqs is not None:
                depth = sum(1 for s in self.scheduler.waiting
                            if not s.output_tokens)
                # free slots absorb that much of the queue on the next
                # pass — minus the preempted sequences queued ahead of
                # everyone (recompute-first), which reclaim slots
                # before any fresh arrival
                preempted = len(self.scheduler.waiting) - depth
                allowance = self.cfg.max_waiting_seqs + max(
                    0, len(self.scheduler.free_slots) - preempted)
                if depth >= allowance:
                    self.metrics.admission_rejected.inc()
                    raise AdmissionRejected(
                        depth, self.estimated_queue_delay_s())
            self.scheduler.add(seq)
            self.seqs[seq_id] = seq
        return seq_id

    def abort(self, seq_id: str) -> bool:
        with self._lock:
            seq = self.seqs.get(seq_id)
            slot = seq.slot if seq is not None else -1
            ok = self.scheduler.abort(seq_id)
            if ok:
                self._park_slot(slot)
                if seq is not None:
                    self._free_seq_blocks(seq)
                    self._remember(seq)
            self._refresh_gauges()
            return ok

    # ------------------------------------------------------------------

    def step(self) -> List[StepOutput]:
        """One engine iteration: at most one prefill chunk AND one decode
        window — interleaved 1:1, so running sequences keep their token
        cadence while a long prompt prefills chunk by chunk (no
        head-of-line blocking; the reference exposes the same property as
        --enable-chunked-prefill, reference:
        helm/templates/deployment-vllm-multi.yaml:69-72)."""
        with self._lock, self.eff.step():
            outputs: List[StepOutput] = []
            with self._phase("expire"):
                outputs.extend(self._drop_expired())
            with self._phase("schedule"):
                works, decode_seqs = self.scheduler.schedule()
            if works:
                # the chunk joins the device queue behind the windows in
                # flight: they were dispatched from pre-prefill state
                # and stay valid, its writes are ordered after them on
                # the device, and its first token joins the carry there
                # (_do_prefill). Where that needs state only the host
                # holds, the queue is emptied first.
                drained = self._prefill_drains(works)
                if drained is not None:
                    outputs.extend(self._drain_decode())
                with self._phase("prefill_host"):
                    self._do_prefill(works, drained)
                # re-snapshot: sequences whose prompt is now whole are
                # RUNNING and part of the next window dispatched — the
                # device generates tokens for every live row, and a row
                # the host skipped would desync the device carry
                decode_seqs = list(self.scheduler.running.values())
            if decode_seqs or self._inflight:
                with self._phase("decode_host"):
                    if not self._inflight:
                        self._dispatch_decode(decode_seqs)
                    # optimistic pipelining: top the device queue up to
                    # cfg.pipeline_depth windows BEFORE blocking on the
                    # front entry's sync — with window N+1 already
                    # queued behind N, the device starts N+1 the instant
                    # N retires instead of idling one host round-trip
                    # (the timeline's starved seconds say how long that
                    # is), and it keeps decoding while the host walks
                    # tokens (detok, stop checks, callbacks). Valid
                    # because decode inputs are device-carried: each
                    # window continues from its predecessor's final
                    # tokens/positions regardless of what the host
                    # decides; rows whose sequence turns out to have
                    # finished are discarded when their window is
                    # retired (their writes only touch blocks still
                    # owned by the finished sequence — never
                    # registered-prefix blocks, which are always full).
                    # Only when the device carry is self-contained: a
                    # dirty decode/sampling state means the next
                    # dispatch must upload host mirrors, and
                    # mid-processing mirrors lag the device (uploading
                    # them would rewind live rows and duplicate tokens).
                    self._top_up_pipeline()
                outputs.extend(self._retire_window("decode"))
                if not self._inflight:
                    decode_seqs = list(self.scheduler.running.values())
                    if decode_seqs:
                        self._dispatch_decode(decode_seqs)
            with self._phase("housekeeping"):
                self._maybe_defrag()
                self._refresh_gauges()
            return outputs

    def _drop_expired(self) -> List[StepOutput]:
        """Overload protection: drop expired-deadline / over-delayed
        sequences from the waiting queue BEFORE admission, so no
        prefill compute is burned on a request whose client has
        already given up (ISSUE 4; docs/engine.md)."""
        outputs: List[StepOutput] = []
        delay_cap = self.cfg.max_queue_delay_ms
        expired = self.scheduler.expire_waiting(
            max_queue_delay_s=delay_cap / 1e3
            if delay_cap is not None else None)
        for seq in expired:
            self._free_seq_blocks(seq)
            self._remember(seq)
            if seq.finish_reason == "deadline":
                self.metrics.deadline_expired.inc()
            else:
                self.metrics.queue_delay_shed.inc()
            logger.info("dropped %s while waiting (%s): queued "
                        "%.0fms", seq.seq_id, seq.finish_reason,
                        1e3 * (time.monotonic() - seq.arrival_time))
            drop_now = time.monotonic()
            # a WAITING-dropped request's whole remaining life IS
            # queue wait — close its open interval so shed storms
            # show up in the phase histograms, not just counters
            seq.queue_wait_s += drop_now - seq.enqueued_time
            self.metrics.engine_phases.observe(
                "queue_wait", seq.queue_wait_s)
            outputs.append(StepOutput(
                seq.seq_id, None, "", True, seq.finish_reason,
                timing=self._seq_timing(seq, drop_now)))
        return outputs

    def _maybe_defrag(self) -> None:
        """kvplane intra-replica defrag, between fused windows: if this
        step's admissions hit the fragmented-failure regime, compact
        the free list so the next allocations hand out dense block-id
        runs. Called under the engine lock at the end of step() — the
        one point where no allocation is mid-flight."""
        if not self.cfg.kvplane_defrag:
            return
        frag = self.block_mgr.alloc_failures_fragmented
        if frag > self._defrag_seen_failures:
            self._defrag_seen_failures = frag
            self.block_mgr.defrag()

    def migrate_out(self, max_seqs: int = 2,
                    target_blocks: int = 0) -> Dict[str, object]:
        """kvplane live migration, source side: publish the victim
        sequences' computed chunks to the shared tiers, preempt them
        (freeing their blocks for the admissions that were failing),
        flush the write-through, and hand back the chunk keys so the
        planner can warm the destination replica and re-home routing.

        Victims are the LEAST recently active sequences first (oldest
        ``last_active`` stamp, arrival time as the tie-break): their KV
        is the coldest on this replica, they are the least likely to be
        mid-burst, and the stall a migration adds lands on the request
        that has already waited longest — instead of yanking the
        hottest sequence just because it holds the most blocks.
        Preempted victims are re-prefetched from the tiers before their
        next admission, so migration costs them a tier read, not a
        recompute. A planner crash after this call leaves only
        published chunks + preempted sequences — both states the stack
        already recovers from (recompute + checksummed tier reads), so
        migration is torn-safe by construction."""
        if self.connector is None or not self.connector.cfg.is_producer:
            return {"migrated": [], "freed_blocks": 0, "keys": [],
                    "error": "kv tiering with a producer role is "
                             "required for migration"}
        keys: List[bytes] = []
        victims = []
        freed = 0
        with self._lock:
            candidates = list(self.scheduler.running.values()) \
                + list(self.scheduler._prefilling.values())
            candidates.sort(
                key=lambda s: (s.last_active, s.arrival_time))
            for seq in candidates:
                if len(victims) >= max(1, max_seqs):
                    break
                if target_blocks and freed >= target_blocks:
                    break
                held = len([b for b in seq.block_ids if b])
                if held == 0:
                    continue
                keys.extend(self.connector.on_migrate(
                    seq, salt=self._adapter_salt(seq.adapter_id)))
                self._preempt(seq)
                freed += held
                victims.append(seq)
            self.metrics.kvplane_migrations.inc(len(victims))
            self.metrics.kvplane_migrated_blocks.inc(freed)
        # outside the lock: make the published chunks tier-visible
        # before the planner acts on the keys, then re-prefetch each
        # victim so its re-admission injects instead of recomputing
        # (benign race: a victim admitted before its prefetch lands
        # simply recomputes, the pre-migration behavior)
        self.connector.flush(timeout=10.0)
        for seq in victims:
            pf = self.connector.prefetch(
                seq.prompt_tokens,
                salt=self._adapter_salt(seq.adapter_id))
            if pf is not None and seq.kv_prefetch is None:
                seq.kv_prefetch = pf
        return {"migrated": [s.seq_id for s in victims],
                "freed_blocks": freed,
                "keys": [k.hex() for k in keys]}

    def warm_chunks(self, hex_keys: List[str]) -> Dict[str, int]:
        """kvplane migration, destination side: pull the given chunk
        keys through the tier walk so hits promote into this replica's
        fastest tier (connector.warm_keys). Runs on the caller's
        thread — never the engine loop."""
        if self.connector is None:
            return {"warmed": 0, "missed": 0}
        try:
            keys = [bytes.fromhex(k) for k in hex_keys]
        except ValueError:
            return {"warmed": 0, "missed": len(hex_keys)}
        # connector.warmed_chunks totals delta-sync into
        # tpu:kvplane_warmed_chunks_total at scrape time
        warmed, missed = self.connector.warm_keys(keys)
        return {"warmed": warmed, "missed": missed}

    def _top_up_pipeline(self) -> None:
        """Queue optimistic decode windows behind what is in flight, up
        to cfg.pipeline_depth windows, provided the device carry is
        self-contained (no pending mirror uploads) and the extra window
        is unlikely to be pure discarded work."""
        while (self._inflight
               and sum(isinstance(e, _Window) for e in self._inflight)
               < self.cfg.pipeline_depth
               and not self._decode_dirty and not self._sampling_dirty
               and not (self.cfg.speculative_ngram_tokens
                        and self._hist_dirty)
               # mid-window admission preference: with a request
               # waiting AND a slot to admit it into, an extra queued
               # window only delays the admission pass it is waiting
               # for
               and not (self.cfg.window_adapt
                        and self._admission_imminent())
               and self._worth_dispatch_ahead()):
            if not self._dispatch_decode(
                    list(self.scheduler.running.values())):
                break

    def _device_queue_depth(self) -> int:
        """How many entries of ``_inflight`` the device has NOT
        finished, from the device's own answer and without blocking
        (``jax.Array.is_ready``); the step timeline asks
        (efficiency.EngineEffAccounting ``queue_depth``). 0 means the
        chip has nothing of ours left to do. The queue runs in order:
        the newest result decides between 0 and more, and the walk from
        the newest entry stops at the first finished one. An entry
        taken off the list to be synced or landed may still be running,
        so an unfinished newest result counts as 1 at least."""
        tail = self._queue_tail
        if tail is None:
            return 0
        if tail.is_ready():
            self._queue_tail = None
            return 0
        depth = 0
        for entry in reversed(self._inflight):
            result = (entry.ids if isinstance(entry, _Window)
                      else entry.devs[0])
            if result is not tail and result.is_ready():
                break
            depth += 1
        return max(depth, 1)

    def _device_leads(self):
        """How many tokens the device is past the host, by row: (the
        decode steps of every window in flight: the lead of a row that
        is part of them all, {seq_id: lead} for the rows a prefill
        entry in flight joined to the carry: one for the first token,
        plus the steps of the windows dispatched behind that entry)."""
        steps, joined = 0, {}
        for entry in reversed(self._inflight):
            if isinstance(entry, _Window):
                steps += entry.steps
            else:
                for seq in entry.joined:
                    joined[seq.seq_id] = steps + 1
        return steps, joined

    def _worth_dispatch_ahead(self) -> bool:
        """Skip the optimistic window when every live sequence could
        reach its token budget within the windows already in flight —
        then the whole dispatch would likely be discarded work (and
        would delay the next admission wave by one window)."""
        inflight_steps = self._device_leads()[0]
        live = [s for s in self.scheduler.running.values()
                if s.status is SeqStatus.RUNNING]
        if not live:
            return False
        return any(
            s.options.max_tokens is None
            or s.options.max_tokens - len(s.output_tokens) > inflight_steps
            for s in live)

    def _prefill_drains(self, works) -> Optional[str]:
        """THE rule for a prefill dispatch (in the manner of
        ops/pallas_paged.attention_path and ops/moe.list_path: no
        option selects): None where its chunks go behind the windows in
        flight, else why the queue is emptied first (a name of
        efficiency.DRAIN_REASONS). Behind is the rule; each exception
        needs, before the next window may be dispatched, state that only
        the host holds once the chunk's result is back:

        ``speculation``  the n-gram history [B, S] of the new row;
        ``guided``       the DFA state its first token leads to;
        ``shaped``       the [B, V] counts with its first token in them;
        ``resume``       a preempted sequence's last EMITTED token is its
                         next input, not the id the chunk samples;
        ``reshape``      the carry is replaced whole anyway (mirrors to
                         upload, a row outside its batch, another batch
                         bucket or compaction due);
        ``pressure``     the pool cannot cover the window behind the
                         chunk: someone has to be preempted, which an
                         optimistic dispatch never does.

        The first four hold for every chunk of such a sequence (one
        rule a sequence, whatever the chunk). The last two are about
        the join: a step whose chunks all leave their prompts unfinished
        joins nothing and goes behind the queue whatever the carry is
        due."""
        if self.cfg.speculative_ngram_tokens:
            return "speculation"
        seqs = [w.seq for w in works]
        if any(s.grammar is not None for s in seqs):
            return "guided"
        if any(s.options.shaped for s in seqs):
            return "shaped"
        if any(s.output_tokens for s in seqs):
            return "resume"
        joining = [w.seq for w in works if w.is_last]
        if not joining:
            return None
        if (self._decode_dirty
                or self._wanted_batch() != self._carry_batch
                or any(s.slot >= self._carry_batch for s in joining)):
            return "reshape"
        if self._pool_short(joining):
            return "pressure"
        return None

    def _wanted_batch(self) -> int:
        """The batch bucket a dispatch into an empty queue would give
        the carry now (_launch_window: compaction packs the live rows
        low, a variant off the warmed grid pins the full batch). Where
        it is not the carry's, a reshape is due, and only an empty
        queue lets one happen."""
        live = ([s for s in self.scheduler.running.values()
                 if s.status is SeqStatus.RUNNING]
                + list(self.scheduler._prefilling.values()))
        if not live:
            return self._carry_batch
        if not self._adapts(live, self._device_leads()[0]):
            return self.cfg.max_num_seqs
        return self.cfg.batch_bucket_for(len(live))

    def _pool_short(self, joining) -> bool:
        """The free blocks do not cover the longest window that could
        be queued behind the chunks that make ``joining`` rows live
        (_launch_window's coverage, at cfg.decode_window)."""
        ahead, joined = self._device_leads()
        rows = [(s, joined.get(s.seq_id, ahead))
                for s in self.scheduler.running.values()
                if s.status is SeqStatus.RUNNING]
        rows += [(s, 1) for s in joining]
        need = sum(
            max(0, self.block_mgr.blocks_for(min(
                s.next_position + lead + self.cfg.decode_window + 1,
                self.cfg.max_model_len)) - len(s.block_ids))
            for s, lead in rows)
        return need > self.block_mgr.available

    # adaptive window sizing: the largest window bucket whose EXPECTED
    # dead fraction (finished-row tails, from remaining max_tokens
    # budgets + the EOS-rate horizon) stays under this budget. A hard
    # bound, not a target: real storms sit well below it because most
    # windows have no finishing row at all.
    _WINDOW_DEAD_BUDGET = 0.125

    def _choose_window(self, ahead: int) -> int:
        """Window length for the next decode dispatch (adaptive sizing,
        docs/engine.md "Continuous batching across windows").

        With ``window_adapt`` off this is the configured
        ``decode_window``. Otherwise pick the LARGEST bucket from
        ``decode_window_buckets`` whose expected dead fraction stays
        under ``_WINDOW_DEAD_BUDGET``:

        - **budget tails**: a row whose remaining ``max_tokens``
          budget ends inside the window contributes its tail
          ``W - remaining`` as dead steps. With one live row this
          degenerates to "the smallest bucket covering the remaining
          budget"; with a big churny batch it keeps windows LONG as
          long as the occasional tail is an acceptable fraction of
          ``live x W`` — ending the window at every first finish
          would multiply per-window dispatch overhead past what the
          saved tails buy back (measured on the r17 A/B);
        - **EOS-rate horizon**: rows that have recently been stopping
          on EOS/stop (not budget) are expected to stop at rate
          ``_eos_rate`` per row-step, contributing ``rate x W^2 / 2``
          expected tail steps per row — ``max_tokens`` gives no
          warning for natural stops, so long windows get charged for
          them the same way;
        - **mid-window admission**: a request waiting WITH a free
          slot to land in takes the next SHORTER bucket below the
          capped choice — finishing the window sooner runs the
          admission + prefill pass sooner, trading per-window fusion
          for time-to-join. One bucket, not the minimum (under churny
          closed loops someone is waiting at almost every dispatch),
          and only when admission can actually happen: with the batch
          full, the waiter needs a finish first — which the dead
          budget above already steers the window toward.

        ``ahead`` steps already in flight count against the budgets
        (an optimistic window continues from where the queued ones
        will end)."""
        cfg = self.cfg
        if not cfg.window_adapt:
            return cfg.decode_window
        buckets = cfg.decode_window_buckets
        live = [s for s in self.scheduler.running.values()
                if s.status is SeqStatus.RUNNING]
        if not live:
            return buckets[0]
        # no spec_w scaling: speculation pins the full fixed geometry
        # at config time (window_adapt is forced off), so this only
        # ever runs with one token per row-step
        budgets = [max(0, s.options.max_tokens - len(s.output_tokens)
                       - ahead)
                   for s in live if s.options.max_tokens is not None]
        cap = buckets[0]
        for w in buckets:
            tail = sum(max(0, w - b) for b in budgets)
            tail += self._eos_rate * len(live) * w * w / 2.0
            if tail <= self._WINDOW_DEAD_BUDGET * len(live) * w:
                cap = w
        if self._admission_imminent():
            i = buckets.index(cap)
            cap = buckets[max(0, i - 1)]
        return cap

    def _admission_imminent(self) -> bool:
        """A request is waiting and a slot is free to admit it into:
        the next scheduler pass will admit — every queued window step
        between now and then is time-to-join the waiter pays. A pass
        that just deferred the head waiter on the KV admission gate
        (`kv_deferred`) negates that premise: under pool pressure the
        next pass will NOT admit, and shortening windows / pausing the
        pipeline would cost fusion and device occupancy for nothing."""
        return bool(self.scheduler.waiting
                    and self.scheduler.free_slots
                    and not self.scheduler.kv_deferred)

    @staticmethod
    def _grid_hot(seqs) -> bool:
        """True when this batch composition lands on an executable
        variant the warmup grid actually compiled — greedy or
        plain-sampled, with no seeded/guided/penalized/top-k rows.
        Only those variants may dispatch at adapted (batch, window)
        geometry: every other variant warms at the FULL shape alone,
        and adapting it would pay a cold multi-second compile per
        geometry reached, mid-serving (the pre-r17 fixed dispatch
        paid exactly one lazy compile per variant — keep that). Both
        hot variants are closed under row subsetting, so a preemption
        between this check and the dispatch cannot turn a hot window
        cold."""
        return (all(s.options.seed is None and s.grammar is None
                    and not s.options.shaped
                    and not s.options.top_logprobs for s in seqs)
                and (all(s.options.temperature <= 0.0 for s in seqs)
                     or all(s.options.top_p >= 1.0
                            and not s.options.top_k
                            and not s.options.min_p for s in seqs)))

    def _compact_slots(self) -> None:
        """Remap RUNNING sequences into the lowest slots (skipping
        slots held by still-prefilling sequences) so the decode batch
        bucket tracks the LIVE batch instead of historical slot
        positions. Only legal between windows (nothing in flight):
        the remap rewrites the host mirrors, and the next dispatch
        rebuilds every device carry from them (the move marks decode/
        sampling/history dirty; penalty counts and guided ids are
        rebuilt from the sequences at dispatch). KV never moves — a
        slot only indexes a block-table row, so the remap is two table
        rows per moved sequence, not a cache copy (a state page is a
        column of the row: it does not move either)."""
        running = sorted(self.scheduler.running.values(),
                         key=lambda s: s.slot)
        if not running:
            return
        busy = {s.slot for s in self.scheduler._prefilling.values()}
        target = 0
        for seq in running:
            while target in busy:
                target += 1
            if seq.slot != target:
                # target < seq.slot and every lower-slotted live row
                # already sits at an earlier target, so target is free
                self._move_slot(seq, target)
            target += 1

    def _move_slot(self, seq: Sequence, new: int) -> None:
        """Move a RUNNING sequence's slot: scheduler maps, every host
        sampling/decode/guided mirror row, and the block-table row —
        coherently, so the next dispatch's uploads see the sequence at
        its new index."""
        old = seq.slot
        sched = self.scheduler
        del sched.running[old]
        sched.running[new] = seq
        sched.free_slots.remove(new)
        seq.slot = new
        for arr in (self._slot_token, self._slot_pos, self._slot_temp,
                    self._slot_top_p, self._slot_top_k,
                    self._slot_adapter, self._slot_seed,
                    self._slot_presence, self._slot_frequency,
                    self._slot_repetition, self._slot_min_p,
                    self._slot_min_tokens, self._slot_prompt_len,
                    self._slot_bias_ids, self._slot_bias_vals,
                    self._slot_stop_ids, self._slot_gstate):
            arr[new] = arr[old]
        self._set_table_row(new, seq.block_ids, seq.state_page)
        # the next dispatch rebuilds every carry from the mirrors; park
        # AFTER copying (resets old's mirrors). The moved row's
        # sampling differs from the parked defaults park left at
        # `new`, so force the sampling re-upload
        self._decode_dirty = True
        self._hist_dirty = True
        self._park_slot(old)
        self._set_table_row(old, [])
        sched._free_slot(old)
        self._sampling_dirty = True

    def _do_prefill(self, works, drained: Optional[str]) -> None:
        """Dispatch every scheduled chunk; each dispatch becomes a
        _Prefill entry of the in-flight queue. The chunks due in one
        chunk-length bucket run in dispatches of cfg.prefill_rows_for
        rows: up to a quarter of the batch, a one-row dispatch each
        (one prompt due is one row computed, the steady case); more,
        one dispatch of max_num_seqs rows for all. Rows are not slots:
        a dispatch's chunks take its rows 0.. in order, ``slots`` tells
        the executable which slot each serves, and what it returns is
        read by row (_land_prefill).

        What makes a sequence RUNNING and its row part of the next
        window happens here, at dispatch (scheduler.on_prefill_done,
        and with ``drained`` None the first token's join to the carry,
        on the device: _join_carry); what needs the chunk's result
        happens when the entry is retired. ``drained``: why the queue
        was emptied first (_prefill_drains), None if it was not; then
        the next decode dispatch uploads the host mirrors, which the
        entry's landing, due before it, brings up to date.
        Runs under the ``prefill_host`` phase: what is not inside
        ``prefill_dispatch`` is host preparation (grouping, table and
        sampling uploads, the join)."""
        for w in works:
            self._sync_sampling(w.seq)
        self._ensure_dev_sampling()
        by_bucket: Dict[int, list] = {}
        for w in works:
            by_bucket.setdefault(self.cfg.bucket_for(len(w.chunk)),
                                 []).append(w)
        dispatches = []
        for bucket, due in sorted(by_bucket.items()):
            rows = self.cfg.prefill_rows_for(len(due), bucket)
            dispatches += [(bucket, rows, due[i:i + rows])
                           for i in range(0, len(due), rows)]
        if any(w.seq.options.shaped for w in works if w.is_last):
            # last-chunk rows sample their first token with shaped
            # logits; mirrors are current (a shaped prefill drains the
            # queue first), and one upload serves every dispatch of
            # the step: the state is per slot. The next decode dispatch
            # rebuilds AGAIN — not redundant: that rebuild includes the
            # first tokens this very prefill samples, which prefill
            # executables don't record device-side
            self.runner.set_penalty_state(*self._penalty_arrays())
        B, S = self.cfg.max_num_seqs, self.cfg.max_model_len
        for bucket, rows, group in dispatches:
            tokens = np.zeros((rows, bucket), np.int32)
            # spare rows: parked at S, where nothing is written or read
            starts = np.full((rows,), S, np.int32)
            lengths = np.ones((rows,), np.int32)
            slots = np.zeros((rows,), np.int32)
            kv_need = bucket
            for row, w in enumerate(group):
                tokens[row, :len(w.chunk)] = w.chunk
                starts[row] = w.start
                lengths[row] = len(w.chunk)
                slots[row] = w.seq.slot
                kv_need = max(kv_need, w.start + bucket)
            kv_len = self.cfg.kv_bucket_for(min(kv_need, S))
            gtable = gids = gstates = None
            if any(w.seq.grammar is not None for w in group):
                gtable, gid_map = self._ensure_guided_table()
                gids = np.zeros((B,), np.int32)
                gstates = np.zeros((B,), np.int32)
                for w in group:
                    if w.seq.grammar is not None:
                        gids[w.seq.slot] = gid_map[w.seq.options.guided_regex]
                        gstates[w.seq.slot] = w.seq.fsm_state
            penalized = any(w.seq.options.shaped for w in group
                            if w.is_last)
            topk = max((w.seq.options.top_logprobs for w in group
                        if w.is_last), default=0)
            if topk:
                topk = 1 << (topk - 1).bit_length()
            # some row's prompt ends in this dispatch: what a model
            # whose prefill has two depths runs its later layers for
            finishing = any(w.is_last for w in group)
            with self._phase("prefill_dispatch", dispatches=True) as call:
                devs = self.runner.prefill(
                    tokens, starts, lengths, self._dev_sampling, kv_len,
                    guide_table=gtable, guide_ids=gids,
                    guide_states=gstates, penalized=penalized, topk=topk,
                    slots=slots, finishing=finishing)
            # bucket-padding accounting: the dispatch computed
            # rows*bucket positions; only the scheduled chunks' tokens
            # were real
            self.eff.note_prefill(
                bucket=bucket, batch=rows,
                real_tokens=sum(len(w.chunk) for w in group),
                drained=drained, chunks=len(group),
                attention_path=self.runner.prefill_attention_path(
                    bucket, kv_len))
            if self.model_cfg.state_layers:
                # a query at position p has p + 1 keys in context; a
                # chunk reads and writes its row's page once a layer
                self.eff.note_state(
                    scan_tokens=sum(len(w.chunk) for w in group),
                    prefill_keys=sum(
                        len(w.chunk) * w.start
                        + len(w.chunk) * (len(w.chunk) + 1) // 2
                        for w in group),
                    scan_bytes=2 * rows * self._state_page_bytes)
            if self._cross_layers:
                # the later layers run on a finishing row's last
                # position alone, where any row finishes, and read the
                # shared layer's keys at or before it
                self.eff.note_depths(rows * bucket,
                                     len(group) if finishing else 0)
                if finishing:
                    self.eff.note_shared_kv(
                        self._cross_layers, self._cross_layers * sum(
                            w.start + len(w.chunk) for w in group))
            if self.model_cfg.index_topk:
                # (a chunk's padding past its tokens is not counted)
                for w in group:
                    self.eff.note_sparse(
                        "prefill", [w.start], len(w.chunk),
                        self.model_cfg.index_topk,
                        self.runner.selects(kv_len))
            entry = _Prefill(
                group, devs, [w.seq.admit_time for w in group],
                joined=[w.seq for w in group
                        if w.is_last and drained is None])
            for w in group:
                if w.seq.waits.prefill_call is None:
                    w.seq.waits.prefill_call = call.t1
                w.seq.waits.prefill_chunks += 1
                self.scheduler.on_prefill_done(w)
                self.metrics.prompt_tokens.inc(len(w.chunk))
            if entry.joined:
                self._join_carry(entry, rows, starts + lengths)
            self._inflight.append(entry)
            self._queue_tail = devs[0]
        if drained is not None:
            # the carry is rebuilt from the mirrors, first tokens
            # included, once these entries have landed
            self._decode_dirty = True
            self._hist_dirty = True

    def _join_carry(self, entry: _Prefill, rows: int, ends) -> None:
        """Make the rows of a prefill dispatch whose prompt is now
        whole part of the decode carry, on the device: the id the
        chunk sampled (its result, which the host has not seen) at the
        position after the prompt (``ends`` [rows]: start + length).
        The other rows of the dispatch name no slot."""
        slots = np.full((rows,), self.cfg.max_num_seqs, np.int32)
        for row, w in enumerate(entry.group):
            if w.is_last:
                slots[row] = w.seq.slot
        self.runner.edit_carry(slots, entry.devs[0], ends)

    def _land_prefills(self, top_up: bool) -> List[StepOutput]:
        """Retire the prefill entries at the head of the queue.
        ``top_up``: queue decode windows behind them first, as far as
        the pipeline goes, so that the sync of a chunk's result does
        not leave the device without work."""
        outputs: List[StepOutput] = []
        while self._inflight and isinstance(self._inflight[0], _Prefill):
            if top_up:
                with self._phase("decode_host"):
                    self._top_up_pipeline()
            with self._phase("prefill_process"):
                outputs.extend(self._land_prefill(self._inflight.pop(0)))
        return outputs

    def _land_prefill(self, entry: _Prefill) -> List[StepOutput]:
        """Host side of one prefill dispatch, once it is the oldest
        entry in flight: cache bookkeeping per chunk and, for rows
        whose prompt is whole, the first token (one sync per entry,
        ``prefill_sync``; no first token, no sync). The n-th chunk ran
        in row n of the dispatch. A sequence that finished, was
        aborted or was preempted since the dispatch has its row
        discarded, as a finished row of a window is."""
        outputs: List[StepOutput] = []
        ids_dev, lps_dev, tops_dev, expert_rows_dev = entry.devs
        ids = lps = tops = None
        if expert_rows_dev is not None:
            # counted at the next sync of a prefill's result, this
            # entry's or a later one's: by then the device has it
            mc = self.model_cfg
            self._expert_rows_due.append((
                expert_rows_dev,
                sum(len(w.chunk) for w in entry.group)
                * mc.num_experts_per_tok * mc.expert_layers))
        for row, (w, admitted) in enumerate(zip(entry.group,
                                                entry.admitted)):
            seq = w.seq
            if (seq.admit_time != admitted or seq.status not in
                    (SeqStatus.PREFILLING, SeqStatus.RUNNING)):
                continue
            if (self.cfg.enable_prefix_caching
                    and not seq.rolled_blocks):
                # LIVE progressive registration: a full block's
                # K/V is final the moment its last position is
                # written (write-then-attend; full blocks are
                # never rewritten), so a concurrent same-prefix
                # request can attach it WITHOUT waiting for this
                # sequence to finish. The hasher chain state rides
                # the sequence so each chunk keys only its NEW
                # blocks (O(L^2) otherwise on long prompts).
                seq.reg_state = self.block_mgr.register_incremental(
                    seq.prefill_tokens[:seq.num_prefilled],
                    seq.block_ids, seq.reg_state,
                    salt=self._adapter_salt(seq.adapter_id))
            if self.connector is not None:
                # progressive publish: disagg decode engines can pull
                # the prefix while later chunks still prefill
                self.connector.on_prefill_progress(
                    seq, salt=self._adapter_salt(seq.adapter_id))
            if not w.is_last:
                continue
            if seq.output_tokens:
                # preemption-recompute resume: emitted output was
                # teacher-forced back in; the prefill's sampled id
                # is discarded (the last emitted token is the next
                # decode input — _sync_slot restores it)
                self._sync_slot(seq)
                continue
            if ids is None:
                with self._phase("prefill_sync"):
                    ids = np.asarray(ids_dev)  # one sync per entry
                    lps = np.asarray(lps_dev)
                    tops = (None if tops_dev is None else
                            (np.asarray(tops_dev[0]),
                             np.asarray(tops_dev[1])))
                    for rows_dev, routed in self._expert_rows_due:
                        self.eff.note_expert_rows(
                            *np.asarray(rows_dev).tolist(), routed)
                    self._expert_rows_due.clear()
                if not self._inflight:
                    # nothing was queued behind the chunk
                    self.eff.device_idle()
            # prompt fully prefilled: the sampled id is the first
            # output token
            k = seq.options.top_logprobs
            alts = None
            if tops is not None and k:
                alts = [(int(t), float(l)) for t, l in
                        zip(tops[0][row, :k], tops[1][row, :k])
                        if l > -1e29]
            seq.first_token_time = time.monotonic()
            self.metrics.ttft.observe(
                seq.first_token_time - seq.arrival_time)
            outputs.extend(self._accept_token(
                seq, int(ids[row]), float(lps[row]), alts))
        return outputs

    def _ensure_dev_sampling(self) -> None:
        if self._sampling_dirty:
            self._dev_sampling = SamplingParams(
                temperature=jnp.asarray(self._slot_temp),
                top_p=jnp.asarray(self._slot_top_p),
                top_k=jnp.asarray(self._slot_top_k),
                adapter=jnp.asarray(self._slot_adapter),
                seed=jnp.asarray(self._slot_seed),
                presence=jnp.asarray(self._slot_presence),
                frequency=jnp.asarray(self._slot_frequency),
                repetition=jnp.asarray(self._slot_repetition),
                min_p=jnp.asarray(self._slot_min_p),
                min_tokens=jnp.asarray(self._slot_min_tokens),
                prompt_len=jnp.asarray(self._slot_prompt_len),
                bias_ids=jnp.asarray(self._slot_bias_ids),
                bias_vals=jnp.asarray(self._slot_bias_vals),
                stop_ids=jnp.asarray(self._slot_stop_ids))
            self._sampling_dirty = False

    def _penalty_arrays(self):
        """[B, V] generated-token counts + prompt membership for every
        live slot, rebuilt from the sequences (composition changes
        only; within windows the device carries counts itself)."""
        B, V = self.cfg.max_num_seqs, self.model_cfg.vocab_size
        counts = np.zeros((B, V), np.int32)
        seen = np.zeros((B, V), bool)
        live = list(self.scheduler.running.values()) + list(
            self.scheduler._prefilling.values())
        for s in live:
            if s.slot < 0:
                continue
            if s.output_tokens:
                out = np.asarray(s.output_tokens, np.int64)
                np.add.at(counts[s.slot], np.clip(out, 0, V - 1), 1)
            if s.prompt_tokens:
                pt = np.clip(np.asarray(s.prompt_tokens, np.int64),
                             0, V - 1)
                seen[s.slot][pt] = True
        return counts, seen

    def _ensure_guided_table(self):
        """(Re)build the stacked guided-decoding table for the distinct
        grammars among admitted sequences. Returns (device table
        [G+1, S, V] or None, {pattern: row index}). Row 0 is the
        unguided placeholder; vocab columns beyond a grammar's tokenizer
        range stay forbidden."""
        active = list(self.scheduler.running.values()) + list(
            self.scheduler._prefilling.values())
        pats = sorted({s.options.guided_regex for s in active
                       if s.grammar is not None})
        if not pats:
            return None, {}
        key = tuple(pats)
        if key != self._guided_key:
            from production_stack_tpu.engine import guided as guided_mod
            grammars = [guided_mod.compile_grammar(p, self.tokenizer)
                        for p in pats]
            # pad S and G up to power-of-two buckets: the decode/prefill
            # executables are keyed on the table shape, so raw sizes
            # would recompile on every pattern-set change
            S = max(g.n_states for g in grammars)
            S = 1 << (S - 1).bit_length() if S > 1 else 1
            G = len(pats) + 1
            G = 1 << (G - 1).bit_length()
            V = self.model_cfg.vocab_size
            table = np.full((G, S, V), -1, np.int32)
            for gi, g in enumerate(grammars, start=1):
                s, v = g.token_next.shape
                table[gi, :s, :min(v, V)] = g.token_next[:, :V]
            self._guided_table = jnp.asarray(table)
            self._guided_gids = {p: i + 1 for i, p in enumerate(pats)}
            self._guided_key = key
            self._decode_dirty = True   # gids/states must re-upload
        return self._guided_table, self._guided_gids

    def _dispatch_decode(self, decode_seqs) -> bool:
        """Launch one decode window (_launch_window) under the
        ``decode_host`` phase; False if none was dispatched."""
        with self._phase("decode_host") as host:
            win = self._launch_window(decode_seqs)
        if win is None:
            return False
        win.host_s += host.self_s
        return True

    def _adapts(self, live, ahead: int) -> bool:
        """Whether a window over ``live`` rows may take adapted (batch,
        window) geometry: a variant of the warmed grid (_grid_hot), at
        the SMALLEST kv bucket, where alone the grid exists — adapted
        geometry at a larger bucket would compile cold per (batch,
        window) combination reached mid-serving, so the full fixed
        geometry is pinned there instead (one lazy compile per variant,
        the pre-r17 cost). Long-context fleets that want adaptation
        should size --kv-len-buckets so the first bucket spans their
        serving contexts. Probed at the largest possible window so the
        actual kv pick (made after W is) can never exceed the probe."""
        if not (self.cfg.window_adapt and live and self._grid_hot(live)):
            return False
        probe = (max(s.next_position for s in live)
                 + self.cfg.decode_window + ahead + 1)
        return (self.cfg.kv_bucket_for(min(probe, self.cfg.max_model_len))
                == self.cfg.kv_len_buckets[0])

    def _launch_window(self, decode_seqs) -> Optional[_Window]:
        """Launch one decode window (async dispatch; no host sync).

        With ``window_adapt`` on, the dispatch tracks the LIVE batch
        along three levers (docs/engine.md "Continuous batching across
        windows"): live rows are first compacted into the low slots
        (only between windows — the remap rebuilds every device carry
        from the host mirrors, which are only current when nothing is
        in flight), the batch bucket is the smallest one covering
        them (parked rows above it are not computed at all), and the
        window length comes from the live rows' remaining budgets +
        the EOS-rate horizon — one bucket shorter when admission is
        imminent, so waiters join sooner (_choose_window). Windows
        needing a variant outside the warmed grid (seeded / guided /
        penalized / top-k / full-sort sampling) pin the full fixed
        geometry instead (_grid_hot).

        With anything in flight the dispatch is OPTIMISTIC: the tokens
        of the entries queued are still unprocessed on the host, so
        the device is past the host mirrors, by ``ahead`` steps for a
        row that is part of every window in flight and by its own lead
        for a row that a prefill entry joined since (_device_leads):
        block coverage and the kv bucket are computed from each row's
        position on the device. An optimistic dispatch must leave host
        state untouched by the device's view: it returns None WITHOUT
        dispatching if it would have to preempt (that replaces the
        decode carry) or upload host mirrors (they lag the device
        until every entry queued is retired) — the caller then falls
        back to the ordinary retire-first path. It also keeps the
        carry's batch bucket (a bucket change is a mirror upload by
        definition)."""
        queued = bool(self._inflight)
        ahead, joined = self._device_leads()
        live0 = [s for s in self.scheduler.running.values()
                 if s.status is SeqStatus.RUNNING]
        adapt = self._adapts(live0, ahead)
        if adapt and not queued:
            self._compact_slots()
        W = self._choose_window(ahead) if adapt else self.cfg.decode_window
        if self._roll_window:
            # free behind-window blocks BEFORE growing coverage: the
            # reclaimed blocks feed this very window's growth
            self._roll_windows(decode_seqs)
        # block coverage first: every live slot's table must span the
        # whole window (worst case: speculation emits spec+1 per step).
        # Pool pressure preempts youngest-first; a sequence that cannot
        # be covered even then is preempted itself (recompute later).
        spec_w = self.cfg.speculative_ngram_tokens + 1

        def reach(s: Sequence, per_step: int) -> int:
            # one past the last position the window makes of row s
            return (s.next_position
                    + (W + joined.get(s.seq_id, ahead)) * per_step + 1)
        for s in list(decode_seqs):
            if s.status is not SeqStatus.RUNNING:
                continue   # already preempted as a victim this pass
            covered = self._ensure_blocks(s, reach(s, spec_w),
                                          allow_preempt=not queued)
            if not covered:
                if queued:
                    return None   # pool pressure: no optimistic window
                self._preempt(s)
        decode_seqs = list(self.scheduler.running.values())
        if not decode_seqs:
            return None
        # batch bucket: smallest executable covering every live slot
        # (compaction just packed them low). An optimistic dispatch
        # continues the device carry, whose batch is fixed.
        if queued:
            batch = self._carry_batch
            if not adapt and batch != self.cfg.max_num_seqs:
                # a pinned-geometry window (non-hot variant, or the kv
                # probe crossed above the warmed grid's bucket) would
                # continue a BUCKETED carry here — that (carry batch,
                # full window, higher kv) executable was never warmed,
                # and an optimistic dispatch may not reshape the
                # carry. Fall back to the retire-first path: its
                # dispatch into an empty queue re-uploads at the full
                # batch.
                return None
        else:
            # a non-hot variant window (adapt False) pins the full
            # batch; crossing between that and a bucketed hot window
            # is a carry reshape like any other bucket change
            batch = (self.cfg.batch_bucket_for(
                max(s.slot for s in decode_seqs) + 1)
                if adapt else self.cfg.max_num_seqs)
            if batch != self._carry_batch:
                self._decode_dirty = True
                self._hist_dirty = True
        greedy = all(s.options.temperature <= 0.0 for s in decode_seqs)
        self._ensure_dev_sampling()
        gtable = gids = None
        if any(s.grammar is not None for s in decode_seqs):
            gtable, gid_map = self._ensure_guided_table()
            gids = np.zeros((len(self._slot_gstate),), np.int32)
            for s in decode_seqs:
                if s.grammar is not None:
                    gids[s.slot] = gid_map[s.options.guided_regex]
        # penalized windows carry [B, V] token counts and shape logits
        # before sampling; unshaped batches keep the ordinary executables
        penalized = any(s.options.shaped for s in decode_seqs)
        # OpenAI top_logprobs alternatives: one executable per
        # power-of-two K bucket, only when some live row asks
        topk = max((s.options.top_logprobs for s in decode_seqs),
                   default=0)
        if topk:
            topk = 1 << (topk - 1).bit_length()
        # n-gram speculation is PER-ROW: a row speculates iff it is
        # greedy (argmax verify is exact), unguided (drafts would
        # bypass the DFA mask), unshaped (draft verification ignores
        # the adjusted logits), and asked for no alternatives
        # (macro-steps emit several tokens). Ineligible rows single-
        # step inside the same window — one presence_penalty user
        # costs only their own row its speculation, not the batch's.
        spec_rows = [s for s in decode_seqs
                     if s.options.temperature <= 0.0
                     and s.grammar is None and not s.options.shaped
                     and not s.options.top_logprobs]
        spec = (self.cfg.speculative_ngram_tokens if spec_rows else 0)
        spec_ok = None
        if spec:
            spec_ok = np.zeros((self.cfg.max_num_seqs,), bool)
            for s in spec_rows:
                spec_ok[s.slot] = True
        kv_len = self.cfg.kv_bucket_for(
            min(max(reach(s, spec + 1) for s in decode_seqs),
                self.cfg.max_model_len))
        if queued and (self._decode_dirty or self._sampling_dirty):
            # the guided-table rebuild (or any path above) dirtied the
            # carry: uploading mid-processing mirrors would rewind the
            # device — bail, the normal path re-dispatches after
            # processing
            return None
        hist = None
        if spec and (self._hist_dirty or self._decode_dirty):
            # only built for windows that will actually read it; spec=0
            # windows skip the [B, S] host build + upload entirely
            hist = np.zeros((batch,
                             self.cfg.max_model_len), np.int32)
            for s in decode_seqs:
                row = s.prompt_tokens + s.output_tokens
                hist[s.slot, :len(row)] = row
            self._hist_dirty = False
        if penalized and self._decode_dirty:
            # counts/prompt-membership upload rides the same trigger as
            # the decode carry: any composition change. Within windows
            # the device updates counts itself (runner._decode_impl)
            counts_arr, seen_arr = self._penalty_arrays()
            self.runner.set_penalty_state(counts_arr[:batch],
                                          seen_arr[:batch])
        if self._decode_dirty or hist is not None:
            # mirrors are uploaded at the dispatch's batch bucket: the
            # runner's carry shape IS the executable's batch axis
            self.runner.set_decode_state(self._slot_token[:batch],
                                         self._slot_pos[:batch],
                                         self._slot_gstate[:batch], hist)
            self._decode_dirty = False
        self._carry_batch = batch
        seeded = any(s.options.seed is not None for s in decode_seqs)
        # the API-default sampling shape (top_p=1, top_k=0, min_p=0)
        # needs no [B, V] sort — a separate executable skips it
        # (sampler.py); min_p truncation lives on the sorted path
        plain = all(s.options.top_p >= 1.0 and not s.options.top_k
                    and not s.options.min_p
                    for s in decode_seqs)
        if self.model_cfg.state_layers:
            # a window moves pages of every row of the batch bucket, a
            # parked row's trash page among them: 2 W a row, or W + 2
            # where the window form runs (runner.state_pages_moved)
            self.eff.note_state(
                step_rows=W * len(decode_seqs), steps=W,
                step_bytes=self.runner.state_pages_moved(W) * batch
                * self._state_page_bytes)
        if self._cross_layers:
            # a query at position p reads p + 1 keys of the shared
            # layer, in every cross layer, every step of the window
            first = sum(s.next_position + joined.get(s.seq_id, ahead)
                        for s in decode_seqs)
            self.eff.note_shared_kv(
                W * self._cross_layers,
                self._cross_layers * (W * first + len(decode_seqs)
                                      * W * (W + 1) // 2))
        if self.model_cfg.index_topk:
            self.eff.note_sparse(
                "decode", [s.next_position + joined.get(s.seq_id, ahead)
                           for s in decode_seqs], W,
                self.model_cfg.index_topk, self.runner.selects(kv_len))
        with self._phase("decode_dispatch", dispatches=True) as call:
            (ids_dev, lps_dev, counts_dev, tops_dev,
             work_dev) = self.runner.decode(
                self._dev_sampling, steps=W, kv_len=kv_len, greedy=greedy,
                seeded=seeded, guide_table=gtable, guide_ids=gids,
                spec=spec, spec_ok=spec_ok, plain=plain,
                penalized=penalized, topk=topk)
        win = _Window(ids_dev, lps_dev, counts_dev, tops_dev, W,
                      list(decode_seqs), call.t1, spec_ok, kv_len, batch,
                      host_s=call.self_s, work=work_dev)
        self._inflight.append(win)
        self._queue_tail = ids_dev
        return win

    def _drain_decode(self) -> List[StepOutput]:
        """Sync + process every entry in flight. A sequence that
        finished or aborted after dispatch simply has its rows discarded
        (its slot is parked, on the device too)."""
        outputs: List[StepOutput] = []
        while self._inflight:
            outputs.extend(self._retire_window("drain"))
        return outputs

    def _retire_window(self, kind: str) -> List[StepOutput]:
        """Retire the OLDEST window in flight, in order: the prefill
        entries ahead of it land first, then it is synced and its tokens
        walked, under the phases ``<kind>_sync`` and ``<kind>_process``
        (``decode`` in the step proper, ``drain`` where a prefill must
        have the queue empty), then the prefill entries that have come
        to the head land too — so a first token is never kept waiting
        behind a window dispatched after its chunk, and no window is
        walked before the first token of a row it holds. The window's
        clock is the timeline's: its seconds end where the sync phase
        does."""
        top_up = kind == "decode"
        outputs = self._land_prefills(top_up)
        if not self._inflight:
            return outputs
        with self._phase(kind + "_sync") as sync:
            win = self._sync_inflight()
        if not self._inflight:
            self.eff.device_idle()
        if kind == "decode":
            # per-window host-visible decode latency: the blocking
            # device sync for one fused window — the batching-level
            # signal (how long a window takes end to end) the
            # roofline work reads next to the per-request phases
            self.metrics.engine_phases.observe("decode_window",
                                               sync.elapsed_s)
        window_s = sync.t1 - win.t0
        with self._phase(kind + "_process") as walk:
            walked, counted = self._process_window(win, window_s)
        outputs.extend(walked)
        work = win.work
        if work.experts_read is not None:
            counted.update(
                experts_read=int(work.experts_read.sum()),
                experts_resident=win.steps * self._resident_layers()
                * self.model_cfg.num_experts)
        if work.loop is not None:
            counted.update(loop=(work.loop.passes_run.sum(),
                                 work.loop.row_steps.sum(),
                                 work.loop.exit_mass.sum(axis=0)))
        self.eff.note_window(**counted, window_s=window_s,
                             host_s=win.host_s + walk.self_s,
                             sync_s=sync.self_s)
        outputs.extend(self._land_prefills(top_up))
        return outputs

    def _resident_layers(self) -> int:
        """The layers ``experts_resident`` counts a step: a layer
        plan's expert blocks; of every other model ALL its layers, the
        leading dense ones too (what the counter has counted since it
        was read: PERF.md, ``moe_read_share``)."""
        mc = self.model_cfg
        return mc.expert_layers if mc.layer_plan else mc.num_layers

    def _sync_inflight(self) -> _Window:
        """Device->host sync of the OLDEST in-flight window's arrays (no
        token processing). Its t0 is clamped to the previous sync's
        completion so pipelined windows report per-window wall, not
        time-since-dispatch."""
        win = self._inflight.pop(0)
        win.t0 = max(win.t0, self.eff.synced_at)
        win.ids = np.asarray(win.ids)  # the window's single sync
        win.lps = np.asarray(win.lps)
        if win.counts is not None:
            win.counts = np.asarray(win.counts)
        if win.tops is not None:
            win.tops = (np.asarray(win.tops[0]), np.asarray(win.tops[1]))
        # a few numbers a step, in the fetch of the window's token ids
        # (None members stay None)
        win.work = jax.tree.map(np.asarray, win.work)
        return win

    def _process_window(self, win: _Window, dt: float):
        """Walk a synced window's tokens. ``dt``: the window's seconds.
        Returns (outputs, what ``eff.note_window`` counts of it)."""
        ids, lps, counts, tops = win.ids, win.lps, win.counts, win.tops
        W, seqs, spec_ok, B = win.steps, win.seqs, win.spec_ok, win.batch
        outputs: List[StepOutput] = []
        # a row whose sequence finished, was aborted or was preempted
        # since the dispatch is discarded
        alive = [s for s in seqs if s.status is SeqStatus.RUNNING]
        walkers = len(alive)   # rows that will actually walk steps
        # window efficiency accounting: every row of the DISPATCHED
        # batch bucket B computes W steps of P positions each (P =
        # spec+1 under speculation). real counts tokens the client
        # keeps (one per _accept_token); non-live rows inside the
        # bucket are pure padding; everything else a live row computed
        # but did not emit — finished-row tails, rows finished/aborted
        # between dispatch and drain, rejected draft positions — is
        # dead.
        P = ids.shape[2] if counts is not None and ids.ndim == 3 else 1
        accepted = 0
        eos_stops = 0
        steps_walked = 0
        for j in range(W):
            steps_walked = j + 1
            still = []
            for seq in alive:
                if counts is None:
                    row = [(int(ids[seq.slot, j]),
                            float(lps[seq.slot, j]))]
                else:
                    # speculative macro-step: 1..spec+1 verified tokens
                    c = int(counts[seq.slot, j])
                    row = [(int(ids[seq.slot, j, t]),
                            float(lps[seq.slot, j, t]))
                           for t in range(c)]
                    if spec_ok is not None and spec_ok[seq.slot]:
                        self.metrics.spec_macro_steps.inc()
                        self.metrics.spec_accepted_tokens.inc(c - 1)
                # top_logprobs alternatives for rows that asked (trim
                # the window's K bucket to the request's k); a row with
                # alternatives never speculates (per-row spec_ok gate),
                # so its macro-steps always emit exactly one token and
                # the per-step alts attach unambiguously
                k = seq.options.top_logprobs
                alts = None
                if tops is not None and k:
                    ti, tl = tops
                    # guided rows mask forbidden tokens to -inf; those
                    # slots are garbage ids and would serialize as
                    # invalid JSON (-Infinity) — drop them (OpenAI
                    # allows fewer than k alternatives)
                    alts = [(int(t), float(l)) for t, l in
                            zip(ti[seq.slot, j, :k], tl[seq.slot, j, :k])
                            if l > -1e29]
                finished = False
                for token, lp in row:
                    accepted += 1
                    outs = self._accept_token(seq, token, lp, alts)
                    outputs.extend(outs)
                    if outs[-1].finished:
                        finished = True
                        if outs[-1].finish_reason == "stop":
                            eos_stops += 1
                        break
                if not finished:
                    still.append(seq)
            alive = still
            if not alive:
                break
        # per-token latency: the window wall over the steps actually
        # WALKED (every alive row retiring at step j means steps past
        # j never produced host-visible tokens — dividing by the full
        # W would understate ITL under adaptive/early-retired
        # windows); under speculation a macro-step emits several
        # verified tokens, so divide by the tokens actually emitted.
        # Observed after the walk (the divisor needs steps_walked);
        # histogram totals are order-independent.
        if accepted:
            per_tok_dt = dt / (steps_walked if counts is None
                               else accepted)
            for _ in range(accepted):
                self.metrics.per_token.observe(per_tok_dt)
        # EOS-rate EWMA feeding the adaptive window horizon
        # (_choose_window): observed per-row-step probability of a
        # non-length stop this window, over the rows that actually
        # WALKED steps — rows finished/aborted between dispatch and
        # drain never walked, and a window with no walkers says
        # nothing and leaves the rate alone (counting either would
        # bias the rate low and under-charge long windows for
        # finished tails).
        if walkers and steps_walked:
            obs = eos_stops / (walkers * steps_walked)
            self._eos_rate = 0.8 * self._eos_rate + 0.2 * obs
        pad = (B - len(seqs)) * W * P
        dead = B * W * P - pad - accepted
        return outputs, dict(steps=W, positions=P, batch=B,
                             live_rows=len(seqs), kv_len=win.kv_len,
                             real=accepted, pad=pad, dead=dead)

    @staticmethod
    def _seq_timing(seq: Sequence, end: float) -> dict:
        """Terminal StepOutput timing payload: the monotonic phase
        stamps the SERVER turns into engine-side trace spans (it holds
        the HTTP context — traceparent — that this layer must not)."""
        return {
            "arrival": seq.arrival_time,
            "admit": seq.admit_time,
            "first_token": seq.first_token_time,
            "queue_wait_s": seq.queue_wait_s,
            "end": end,
            "prompt_tokens": len(seq.prompt_tokens),
            "output_tokens": len(seq.output_tokens),
            "kv_prefetch_wait_s": seq.kv_prefetch_wait_s,
            "kv_cached_tokens": seq.kv_cached_tokens,
            "waits": seq.waits,
        }

    def _accept_token(self, seq: Sequence, token: int,
                      logprob: Optional[float] = None,
                      top_alts=None) -> List[StepOutput]:
        seq.output_tokens.append(token)
        seq.last_active = time.monotonic()
        seq.output_logprobs.append(logprob)
        if seq.options.top_logprobs:
            seq.output_top.append(top_alts)
        if seq.grammar is not None:
            # host mirror of the device-carried DFA state (re-uploaded on
            # slot composition changes); DEAD can't be sampled, max() is
            # pure defense
            seq.fsm_state = max(
                seq.grammar.next_state(seq.fsm_state, token), 0)
        self.metrics.generation_tokens.inc()
        delta = seq.detok.push(token)
        opt = seq.options
        if (token in opt.stop_token_ids
                or (not opt.ignore_eos
                    and token == self.tokenizer.eos_token_id)):
            # a token that stops the sequence is excluded from the
            # returned text (vLLM semantics) — this keeps the text
            # aligned with logprobs (server._lp_skip). Any earlier
            # bytes the detokenizer was still buffering drop with it.
            delta = ""
        seq.output_text += delta
        reason = self._stop_reason(seq, token, delta)
        if reason is not None and reason != "stop":
            seq.output_text += seq.detok.flush()
        text_delta = seq.output_text[seq.chars_emitted:]
        seq.chars_emitted = len(seq.output_text)
        if reason is not None:
            if self.connector is not None:
                # extract while the slot still holds this sequence's KV —
                # dispatched before scheduler.finish can recycle the slot
                self.connector.on_finish(
                    seq, salt=self._adapter_salt(seq.adapter_id))
            # prefix caching: the full blocks stay in the pool under
            # their chain keys (zero-copy sharing); register BEFORE
            # free so refcount-0 registered blocks land in the
            # evictable LRU instead of the free list. Rolled sequences
            # skip registration: chain keys need the contiguous prefix,
            # whose early blocks are gone.
            if not seq.rolled_blocks:
                self.block_mgr.register(
                    (seq.prompt_tokens + seq.output_tokens)[:-1],
                    seq.block_ids,
                    salt=self._adapter_salt(seq.adapter_id))
            self._free_seq_blocks(seq)
            slot = seq.slot
            self.scheduler.finish(seq, reason)
            self._park_slot(slot)
            self._remember(seq)
            now = time.monotonic()
            dur = now - seq.arrival_time
            self.metrics.e2e_latency.observe(dur)
            # service-time EWMA feeding the queue-delay estimate the
            # load report / Retry-After are built on (includes queueing
            # — deliberately: it is what the next queued client will
            # actually wait through)
            self._service_ewma = 0.8 * self._service_ewma + 0.2 * dur
            # phase attribution: where this request's engine wall time
            # went (tracing.py; tpu:engine_phase_seconds). Plain-int
            # bucket increments — no prometheus objects on the loop.
            # queue_wait is the CUMULATIVE wait across admissions
            # (scheduler stamps it), so a preempted-and-requeued
            # sequence never counts an interval twice; a first token
            # emitted BEFORE the last admission (preemption after
            # first token) zeroes prefill and folds the re-prefill
            # into decode — the phases stay disjoint and sum to at
            # most the request's wall time.
            phases = self.metrics.engine_phases
            admit = seq.admit_time if seq.admit_time is not None \
                else seq.arrival_time
            first = seq.first_token_time if seq.first_token_time \
                is not None else now
            phases.observe("queue_wait", seq.queue_wait_s)
            phases.observe("prefill", max(0.0, first - admit))
            phases.observe("decode", max(0.0, now - max(first, admit)))
            return [StepOutput(seq.seq_id, token, text_delta, True, reason,
                               logprob, top_alts,
                               timing=self._seq_timing(seq, now),
                               waits=seq.waits)]
        self._sync_slot(seq)
        return [StepOutput(seq.seq_id, token, text_delta, False, None,
                           logprob, top_alts,
                           waits=seq.waits if len(seq.output_tokens) == 1
                           else None)]

    def _stop_reason(self, seq: Sequence, token: int,
                     delta: str) -> Optional[str]:
        """Stop decision; on a stop-string match, truncates seq.output_text
        so the stop string itself is never delivered (OpenAI semantics)."""
        opt = seq.options
        if token in opt.stop_token_ids:
            return "stop"
        if not opt.ignore_eos and token == self.tokenizer.eos_token_id:
            return "stop"
        if opt.stop and delta:
            # a match can straddle the delta boundary: search a window of
            # (longest stop - 1) chars before the delta
            for s in opt.stop:
                from_idx = max(0, len(seq.output_text) - len(delta) - len(s))
                idx = seq.output_text.find(s, from_idx)
                if idx != -1:
                    seq.output_text = seq.output_text[:idx]
                    return "stop"
        if len(seq.output_tokens) >= opt.max_tokens:
            return "length"
        if seq.num_tokens >= self.cfg.max_model_len:
            return "length"
        return None

    def _remember(self, seq: Sequence) -> None:
        """Retain finished sequences for inspection, bounded in count."""
        self._finished_order.append(seq.seq_id)
        while len(self._finished_order) > _FINISHED_RETENTION:
            old = self._finished_order.pop(0)
            self.seqs.pop(old, None)

    def _sync_slot(self, seq: Sequence) -> None:
        """Mirror the sequence's next decode input into the slot arrays."""
        slot = seq.slot
        self._slot_token[slot] = seq.output_tokens[-1]
        self._slot_pos[slot] = seq.next_position
        self._slot_gstate[slot] = seq.fsm_state
        self._sync_sampling(seq)

    def _sync_sampling(self, seq: Sequence) -> None:
        slot, opt = seq.slot, seq.options
        # normalize the user seed (any int, 0 and negatives included)
        # into a nonzero int32: 0 stays the "unseeded" sentinel only for
        # requests that sent no seed at all
        seed = 0 if opt.seed is None else (opt.seed % 0x7FFFFFFE) + 1
        plen = len(seq.prompt_tokens)
        bias_ids = np.full((self._slot_bias_ids.shape[1],), -1, np.int32)
        bias_vals = np.zeros_like(self._slot_bias_vals[slot])
        if opt.logit_bias:
            for i, (tid, val) in enumerate(sorted(opt.logit_bias.items())):
                bias_ids[i] = tid
                bias_vals[i] = val
        stop_ids = np.full((self._slot_stop_ids.shape[1],), -1, np.int32)
        if opt.min_tokens and opt.stop_token_ids:
            # only meaningful below the min_tokens floor; width validated
            # at add_request
            stop_ids[:len(opt.stop_token_ids)] = opt.stop_token_ids
        if (self._slot_temp[slot] != opt.temperature
                or self._slot_top_p[slot] != opt.top_p
                or self._slot_top_k[slot] != opt.top_k
                or self._slot_adapter[slot] != seq.adapter_id
                or self._slot_seed[slot] != seed
                or self._slot_presence[slot] != opt.presence_penalty
                or self._slot_frequency[slot] != opt.frequency_penalty
                or self._slot_repetition[slot] != opt.repetition_penalty
                or self._slot_min_p[slot] != opt.min_p
                or self._slot_min_tokens[slot] != opt.min_tokens
                or self._slot_prompt_len[slot] != plen
                or not np.array_equal(self._slot_bias_ids[slot], bias_ids)
                or not np.array_equal(self._slot_bias_vals[slot],
                                      bias_vals)
                or not np.array_equal(self._slot_stop_ids[slot],
                                      stop_ids)):
            self._slot_temp[slot] = opt.temperature
            self._slot_top_p[slot] = opt.top_p
            self._slot_top_k[slot] = opt.top_k
            self._slot_adapter[slot] = seq.adapter_id
            self._slot_seed[slot] = seed
            self._slot_presence[slot] = opt.presence_penalty
            self._slot_frequency[slot] = opt.frequency_penalty
            self._slot_repetition[slot] = opt.repetition_penalty
            self._slot_min_p[slot] = opt.min_p
            self._slot_min_tokens[slot] = opt.min_tokens
            self._slot_prompt_len[slot] = plen
            self._slot_bias_ids[slot] = bias_ids
            self._slot_bias_vals[slot] = bias_vals
            self._slot_stop_ids[slot] = stop_ids
            self._sampling_dirty = True

    def _park_slot(self, slot: int) -> None:
        """Return a freed slot to the idle state (position S — its
        window writes clamp onto S-1, harmless because real K/V is
        always written before attention reads; see models/kv.py): the
        host mirrors, which a later upload carries, and the device
        carry, edited at the slot behind whatever is queued
        (runner.edit_carry), so that the pipeline keeps going across a
        finish. Where the carry is to be replaced whole anyway the
        upload does it: mirrors already due, a shaped row (the [B, V]
        counts are rebuilt with the sampling mirrors), or a live batch
        that now fits a smaller bucket (the queue is left to run dry,
        and the dispatch into the empty queue compacts and reshapes)."""
        if slot < 0:
            return
        self._slot_token[slot] = 0
        self._slot_pos[slot] = self.cfg.max_model_len
        self._slot_gstate[slot] = 0
        if (self._slot_presence[slot] or self._slot_frequency[slot]
                or self._slot_repetition[slot] != 1.0
                or self._slot_min_tokens[slot]
                or self._slot_min_p[slot]
                or self._slot_bias_ids[slot, 0] >= 0
                or self._slot_stop_ids[slot, 0] >= 0):
            self._slot_presence[slot] = 0.0
            self._slot_frequency[slot] = 0.0
            self._slot_repetition[slot] = 1.0
            self._slot_min_p[slot] = 0.0
            self._slot_min_tokens[slot] = 0
            self._slot_bias_ids[slot, :] = -1
            self._slot_bias_vals[slot, :] = 0.0
            self._slot_stop_ids[slot, :] = -1
            self._sampling_dirty = True
            self._decode_dirty = True
        if (self._decode_dirty
                or self._wanted_batch() != self._carry_batch):
            self._decode_dirty = True
            self._hist_dirty = True
        else:
            self.runner.edit_carry(
                [slot], [0], [self.cfg.max_model_len])

    @property
    def embedding_source(self) -> str:
        """What powers /v1/embeddings: 'encoder:<name>' when a real
        bidirectional encoder is configured, else the documented
        'causal-mean-pool' approximation (mean-pooled hidden states of
        the causal chat model — API-shape parity, unvalidated
        embedding quality)."""
        if self.cfg.embedding_model:
            return f"encoder:{self._encoder_cfg().name}"
        return "causal-mean-pool"

    def _encoder_cfg(self):
        self._ensure_encoder()
        return self._enc_cfg

    @property
    def embedding_tokenizer(self):
        """Tokenizer for the embeddings path: the encoder checkpoint's
        own (BERT vocabs differ from chat vocabs — loaded and
        validated at startup by _ensure_encoder), else the serving
        tokenizer."""
        return self._embed_tok or self.tokenizer

    @property
    def max_embed_len(self) -> int:
        """Length cap for pooling inputs: the encoder's position table
        when one is configured, else the serving cache length."""
        if self.cfg.embedding_model:
            return self._encoder_cfg().max_position_embeddings
        return self.cfg.max_model_len

    def _ensure_encoder(self) -> None:
        """Lazily build the embedding encoder (models/encoder.py):
        a preset name (random weights — tests/demos) or a HF BertModel
        checkpoint dir."""
        if getattr(self, "_enc_params", None) is not None:
            return
        import os
        from production_stack_tpu.models import encoder as enc
        spec = self.cfg.embedding_model
        if os.path.isdir(spec):
            import json as _json
            with open(os.path.join(spec, "config.json")) as f:
                cfg = enc.config_from_hf_json(_json.load(f),
                                              name=os.path.basename(spec))
            params = enc.load_checkpoint(cfg, spec)
            # string inputs MUST tokenize with the checkpoint's own
            # vocab: the serving tokenizer's ids would gather-clamp
            # into the encoder's smaller embedding table and return
            # confidently wrong vectors. Missing tokenizer = startup
            # error, never a silent fallback.
            from production_stack_tpu.engine.tokenizer import load_tokenizer
            tok = load_tokenizer(spec, None)
            tok_vocab = getattr(tok, "vocab_size", None)
            if tok_vocab is None or tok_vocab > cfg.vocab_size:
                raise ValueError(
                    f"embedding checkpoint {spec} has no usable "
                    f"tokenizer (got vocab "
                    f"{tok_vocab} vs encoder vocab {cfg.vocab_size}); "
                    f"ship the model's tokenizer files in the "
                    f"checkpoint dir")
            self._embed_tok = tok
        else:
            cfg = enc.get_encoder_config(spec)
            params = enc.init_params(cfg, jax.random.PRNGKey(
                self.cfg.seed ^ 0xE9C0DE))
            logger.info("random-initialized embedding encoder %s "
                        "(preset; pass a checkpoint dir for real "
                        "embeddings)", cfg.name)
        self._enc_cfg, self._enc_params = cfg, params
        self._enc_fns = {}

    def _embed_batch(self, tokens: np.ndarray,
                     lengths: np.ndarray) -> np.ndarray:
        """One padded batch -> pooled [B, H] fp32, via the configured
        encoder or the causal-mean-pool fallback."""
        if not self.cfg.embedding_model:
            return np.asarray(self.runner.embed(tokens, lengths))
        self._ensure_encoder()
        from production_stack_tpu.models import encoder as enc
        key = tokens.shape
        fn = self._enc_fns.get(key)
        if fn is None:
            fn = self._enc_fns[key] = jax.jit(
                lambda p, t, ln: enc.encode(p, self._enc_cfg, t, ln))
        return np.asarray(fn(self._enc_params,
                             jnp.asarray(tokens, jnp.int32),
                             jnp.asarray(lengths, jnp.int32)))

    def embed_tokens(self, token_lists: List[List[int]]) -> np.ndarray:
        """Pooled prompt embeddings [n, H] fp32 (the /v1/embeddings
        path; rerank and score pool on top of it). Length-bucketed and
        batch-padded to bound executable count; runs off the engine loop
        (read-only on params, nothing donated)."""
        B = self.cfg.max_num_seqs
        if self.cfg.embedding_model:
            # raw token-list inputs bypass the tokenizer: out-of-vocab
            # ids would gather-clamp silently into the embedding table
            V = self._encoder_cfg().vocab_size
            for toks in token_lists:
                bad = [t for t in toks if not 0 <= t < V]
                if bad:
                    raise ValueError(
                        f"token id {bad[0]} out of range for the "
                        f"embedding encoder vocab ({V})")
        buckets = sorted(set(self.cfg.prefill_buckets)
                         | set(self.cfg.kv_len_buckets))
        out: List[np.ndarray] = []
        for i in range(0, len(token_lists), B):
            group = token_lists[i:i + B]
            need = max(len(t) for t in group)
            tb = next((b for b in buckets if b >= need), need)
            if self.cfg.embedding_model:
                # serving buckets can exceed the encoder's position
                # table; callers are length-capped by max_embed_len
                tb = min(tb, self.max_embed_len)
            tokens = np.zeros((B, tb), np.int32)
            lengths = np.ones((B,), np.int32)
            for j, toks in enumerate(group):
                tokens[j, :len(toks)] = toks
                lengths[j] = len(toks)
            pooled = self._embed_batch(tokens, lengths)
            out.append(pooled[:len(group)])
        return np.concatenate(out, axis=0)

    def render_metrics(self) -> bytes:
        with self._lock:
            self._refresh_gauges()
            if self.connector is not None:
                # totals -> counter deltas + tier occupancy gauges, at
                # scrape frequency (never on the step loop)
                self.metrics.sync_kv(self.connector.stats_report())
            # efficiency + fragmentation totals -> counter deltas and
            # rate gauges, same scrape-time idiom
            self.metrics.sync_eff(self.eff.report(), self.eff.rates())
            self.metrics.sync_kvpool(self.block_mgr.frag_report())
        return self.metrics.render()

    # ------------------------------------------------- overload surface

    def admission_full(self) -> bool:
        """Lock-free fast-path hint: True when a new submit would very
        likely be rejected by bounded admission right now. The
        authoritative count (which excludes preempted sequences) stays
        in add_request under the lock; this lets a shed storm be
        refused BEFORE tokenization and the executor hop burn
        event-loop CPU on requests that are going to 503 anyway. May
        over-shed by up to the preempted-sequence count under combined
        KV pressure + queue overflow — when both valves are blowing,
        early shed is the right bias."""
        cap = self.cfg.max_waiting_seqs
        if cap is None:
            return False
        return len(self.scheduler.waiting) >= \
            cap + len(self.scheduler.free_slots)

    def estimated_queue_delay_s(self) -> float:
        """Rough wait a newly queued request faces: queue depth ahead of
        it over the batch width, paced by the recent per-request wall
        time. Deliberately lock-free (len()/attribute reads are atomic
        in CPython): the /load endpoint and Retry-After must answer
        while the engine lock is held across a multi-second compile."""
        waiting = len(self.scheduler.waiting)
        return (waiting / max(1, self.cfg.max_num_seqs)) \
            * self._service_ewma

    def device_report(self) -> Dict[str, object]:
        """The ``device`` block of GET /debug/perf: what this engine
        runs on, as JAX reports it — a launcher that must stay off JAX
        (one process owns the chip) reads the device from here.
        ``attention_paths`` names the attention implementation every
        compiled executable took ("kind|window|kv_bucket|batch", the
        ``totals.compiles`` key), ``kv_appends`` how it lands its new
        K/V in the pool (ops/pallas_paged.kv_append_path), ``moe_paths``
        the strategy its experts
        take (ops/moe.moe_path; empty on a dense model);
        ``bytes_in_use`` is per device where
        ``memory_stats()`` gives it (None on the CPU)."""
        from production_stack_tpu.ops import pallas_paged
        devs = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            devs.append({
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            })
        return {
            "platform": self.devices[0].platform,
            "device_kind": self.devices[0].device_kind,
            # the process's devices, as jax.devices() counts them, and
            # the ones this engine's mesh spans
            "count": jax.device_count(),
            "engine_devices": devs,
            "pallas_attention": pallas_paged.mode(),
            "attention_paths": dict(self.runner.attention_paths),
            # how each lands its new K/V in the pool: "rows" / "blocks"
            # (not a table of kernels an executable must be on: its
            # name does not end as those do)
            "kv_appends": dict(self.runner.kv_appends),
            "moe_paths": dict(self.runner.moe_paths),
            # ops/gdn.gdn_path or ops/retention.retention_path of each
            # executable of a model whose layers keep state pages
            # (empty on every other)
            "mixer_paths": dict(self.runner.mixer_paths),
            # a looped model's passes, layers and counters
            # (efficiency.loop_report); absent on every other model
            **({"loop": self.eff.loop_report()} if self.eff.looped
               else {}),
        }

    def load_report(self) -> Dict[str, object]:
        """Cheap point-in-time load signal (served on /load and as
        x-engine-* response headers; the router scrapes the same
        numbers from /metrics). Lock-free by design — see
        estimated_queue_delay_s."""
        sched = self.scheduler
        cap = None
        if self.cfg.max_waiting_seqs is not None:
            cap = self.cfg.max_num_seqs + self.cfg.max_waiting_seqs
        report = {
            "queue_depth": len(sched.waiting),
            "running": len(sched.running) + len(sched._prefilling),
            "max_num_seqs": self.cfg.max_num_seqs,
            "max_waiting_seqs": self.cfg.max_waiting_seqs,
            # total in-flight the engine will accept before shedding
            # (None = unbounded admission); the router derives its
            # per-endpoint concurrency cap from this
            "capacity": cap,
            "free_kv_blocks": self.block_mgr.available,
            "kv_usage": round(self.block_mgr.usage, 4),
            "est_queue_delay_ms": round(
                1e3 * self.estimated_queue_delay_s(), 1),
            # live model catalog (base first, then loaded adapters):
            # the router's /v1/models aggregation and pool resolution
            # read it, so a runtime adapter load is fleet-visible one
            # scrape later without a config push
            "models": list(self.served_models),
            # engine-efficiency accounting (engine/efficiency.py):
            # token-step totals, recent effective-bandwidth/MBU rates,
            # and compile counters — including compile_in_flight, which
            # this lock-free path reports WHILE the engine lock is held
            # across the compile itself. Parsed by signals.EngineLoad.
            "perf": self.eff.perf_block(),
            # kvplane census: block-state counts + allocation-failure
            # classification (block_manager.frag_report — plain-int
            # reads). The migration planner's trigger signal: fragmented
            # failures rising here while another replica reports free
            # headroom is exactly the stranded capacity it reclaims.
            "kv_pool": self.block_mgr.frag_report(),
        }
        if self.block_mgr.keeps_pages:
            report["state_pages_live"] = self.block_mgr.live_pages
        if self.connector is not None:
            # tier hit/miss/bytes counters (all in-memory totals — no
            # I/O): the cache-aware router scores endpoints on these,
            # and the kvshare rig reads them for its pass/fail contract
            report["kv_cache"] = self.connector.stats_report()
        return report

    # ---------------------------------------------------- paged-KV host

    def _try_admit(self, seq: Sequence) -> bool:
        """Scheduler admission gate: claim KV blocks for the whole
        prompt (+1 position for the first sampled token). Registered
        prefix blocks are attached by reference (zero copies); the rest
        are allocated fresh. Returns False — deferring admission —
        when the pool cannot cover the remainder."""
        toks = seq.prefill_tokens
        salt = self._adapter_salt(seq.adapter_id)
        # hash the prompt once per (salt, length): deferred admissions
        # retry every scheduler pass and must not re-hash or re-count
        state = seq.prefix_state
        first_try = state is None or state[0] != (salt, len(toks))
        if first_try:
            keys = self.block_mgr.prefix_keys(toks, salt=salt)
            seq.prefix_state = ((salt, len(toks)), keys)
        else:
            keys = state[1]
        shared, covered = self.block_mgr.match_keys(
            keys, record_stats=first_try)
        need = self.block_mgr.blocks_for(len(toks) + 1) - len(shared)
        fresh = self.block_mgr.alloc(max(need, 0))
        if fresh is None:
            self.block_mgr.free(shared)   # unpin; retry next iteration
            return False
        if self.block_mgr.state_pages:
            # admission counts pages as it counts blocks: all or nothing
            seq.state_page = self.block_mgr.alloc_page() or 0
            if not seq.state_page:
                self.block_mgr.free(shared + fresh)
                return False
        seq.block_ids = shared + fresh
        seq.num_prefilled = covered       # capped at len-1, full blocks
        return True

    def _on_admit(self, seq: Sequence) -> None:
        """Scheduler hook (slot now assigned): point the slot's table
        row at the sequence's blocks, then let the KV tiers inject any
        deeper cached prefix (host/disk/remote, kvcache/connector.py)."""
        self._set_table_row(seq.slot, seq.block_ids, seq.state_page)
        pf = seq.kv_prefetch
        seq.kv_prefetch = None   # release host buffers either way
        if pf is None:
            return
        conn_covered = pf.cached_tokens
        if conn_covered > seq.num_prefilled:
            # the injected range may overlap prefix-shared blocks; the
            # bytes are identical by key construction, so concurrent
            # sharers read the same values
            self.connector.inject(pf, seq.slot)
            seq.num_prefilled = conn_covered
        else:
            # block sharing already covers at least as much: the tier
            # holds these chunks, skip the device->host re-extract at
            # finish
            self.connector.mark_seen(pf.keys)

    def _set_table_row(self, slot: int, block_ids,
                       state_page: int = 0) -> None:
        self._tables[slot, :] = 0
        if state_page:      # the row's last column (models/kv.py)
            self._tables[slot, -1] = state_page
        if block_ids:
            # rolled entries are None placeholders -> trash block 0
            # (never read: every attention path skips blocks behind the
            # window, the only reason entries roll)
            self._tables[slot, :len(block_ids)] = [
                b or 0 for b in block_ids]
        self.runner.set_block_tables(self._tables)

    def _free_seq_blocks(self, seq: Sequence) -> None:
        """Release a sequence's live blocks (rolled entries are None
        placeholders, already freed) and, with them, its state page."""
        self.block_mgr.free([b for b in seq.block_ids if b])
        seq.block_ids = []
        self.block_mgr.free_page(seq.state_page)
        seq.state_page = 0

    def _roll_windows(self, decode_seqs) -> None:
        """Free blocks every future query of a windowed sequence can no
        longer attend (positions <= next_position - W). Safe against
        in-flight windows: their starts are >= the host's view, so
        their own window lower bound is at least as high, and they
        never read (or write) behind it."""
        W = self._roll_window
        Bs = self.cfg.kv_block_size
        for s in decode_seqs:
            if s.status is not SeqStatus.RUNNING:
                continue
            keep_from = max(s.next_position - W + 1, 0) // Bs
            if keep_from <= s.rolled_blocks:
                continue
            keep_from = min(keep_from, len(s.block_ids))
            dead = [b for b in s.block_ids[s.rolled_blocks:keep_from]
                    if b]
            if dead:
                self.block_mgr.free(dead)
            for i in range(s.rolled_blocks, keep_from):
                s.block_ids[i] = None
            s.rolled_blocks = keep_from
            self._set_table_row(s.slot, s.block_ids, s.state_page)

    def _ensure_blocks(self, seq: Sequence, upto_tokens: int,
                       allow_preempt: bool = True) -> bool:
        """Grow a live sequence's block list to cover positions
        < min(upto_tokens, max_model_len), preempting younger sequences
        under pool pressure. False = could not cover even after
        preemption (caller preempts `seq` itself). allow_preempt=False
        (optimistic dispatch) fails fast instead of evicting anyone."""
        need = self.block_mgr.blocks_for(
            min(upto_tokens, self.cfg.max_model_len))
        while len(seq.block_ids) < need:
            fresh = self.block_mgr.alloc(need - len(seq.block_ids))
            if fresh is not None:
                seq.block_ids.extend(fresh)
                self._set_table_row(seq.slot, seq.block_ids,
                                    seq.state_page)
                return True
            if not allow_preempt:
                return False
            if not self._preempt_youngest(requester=seq):
                return False
        return True

    def _preempt_youngest(self, requester: Sequence) -> bool:
        """Free pool pressure by preempting the most recently arrived
        live sequence (recompute flavor). If the REQUESTER is itself
        the youngest, returns False so the caller preempts it rather
        than letting a new arrival serially evict older sequences
        (youngest-first must hold globally, not just among victims)."""
        candidates = list(self.scheduler.running.values()) \
            + list(self.scheduler._prefilling.values())
        if requester not in candidates:
            candidates.append(requester)
        victim = max(candidates, key=lambda s: s.arrival_time)
        if victim is requester or len(candidates) == 1:
            return False
        self._preempt(victim)
        return True

    def _preempt(self, seq: Sequence) -> None:
        logger.warning(
            "preempting %s (KV pool pressure): %d blocks freed, "
            "%d tokens will recompute", seq.seq_id, len(seq.block_ids),
            seq.num_tokens)
        slot = seq.slot
        self._free_seq_blocks(seq)
        seq.rolled_blocks = 0   # recompute re-prefills from position 0
        seq.reg_state = None    # re-register the recomputed blocks
        self.scheduler.preempt(seq)
        # a preemption replaces the carry: its sequence comes back
        # through a prefill that resumes from host state
        self._decode_dirty = True
        self._park_slot(slot)
        self._set_table_row(slot, [])
        self.metrics.preemptions.inc()

    def _refresh_gauges(self) -> None:
        self.metrics.num_running.set(self.scheduler.num_running)
        self.metrics.num_waiting.set(self.scheduler.num_waiting)
        self.metrics.est_queue_delay.set(
            1e3 * self.estimated_queue_delay_s())
        usage = self.block_mgr.usage
        self.metrics.kv_usage.set(usage)
        self.metrics.hbm_kv_usage.set(usage)
        # two distinct gauges: the block pool's (per-request, in-HBM)
        # and the tiers' (token-weighted) hit rates have different
        # semantics — shadowing one with the other would skew dashboards
        if self.cfg.enable_prefix_caching:
            self.metrics.hbm_prefix_hit_rate.set(self.block_mgr.hit_rate)
        if self.connector is not None:
            self.metrics.prefix_hit_rate.set(self.connector.hit_rate)
        elif self.cfg.enable_prefix_caching:
            self.metrics.prefix_hit_rate.set(self.block_mgr.hit_rate)

    def close(self) -> None:
        """Flush the KV writer and release tier connections."""
        if self.connector is not None:
            self.connector.close()

    # ------------------------------------------------------------------

    def generate(self, prompt: str, options: Optional[SamplingOptions] = None,
                 ) -> str:
        """Blocking single-prompt convenience API (tests, CLI)."""
        toks = self.tokenizer.encode(prompt)
        seq_id = self.add_request(toks, options)
        while True:
            for out in self.step():
                if out.seq_id == seq_id and out.finished:
                    return self.seqs[seq_id].output_text

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work
