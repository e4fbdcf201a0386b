"""Engine configuration.

Mirrors the knobs the reference exposes as `vllm serve` flags rendered by
Helm (reference: helm/templates/deployment-vllm-multi.yaml:68-93 —
--max-model-len, --dtype, --enable-chunked-prefill, --tensor-parallel-size,
--enable-prefix-caching) as a typed config for the in-repo engine.
"""

import dataclasses
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass
class EngineConfig:
    model: str = "debug-tiny"
    tokenizer: Optional[str] = None          # defaults to model path
    chat_template: Optional[str] = None      # Jinja file overriding the
                                             # tokenizer's chat template
    max_model_len: int = 2048                # max prompt+generation length
    max_num_seqs: int = 8                    # concurrent batch slots
    prefill_chunk: int = 512                 # chunked-prefill chunk size
    # prefill lengths are bucketed to these sizes to bound XLA compiles
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    # decode tokens generated per device dispatch (multi-step decoding):
    # one lax.scan-fused executable emits `decode_window` tokens per slot
    # with a single host sync, amortizing Python dispatch overhead.
    # Sequences that stop mid-window discard the tail (vLLM's
    # num-scheduler-steps tradeoff). 1 = token-at-a-time.
    decode_window: int = 8
    # Continuous batching ACROSS fused windows (docs/engine.md
    # "Continuous batching across windows"): when window_adapt is on,
    # every decode dispatch compacts live rows into the low slots and
    # picks the smallest batch bucket covering them (parked rows stop
    # generating pad token-steps), sizes the window from the live
    # rows' remaining token budgets + an EOS-rate horizon (finished
    # tails stop spanning a long window), and prefers the shortest
    # window bucket while requests wait for admission (prefill — and
    # therefore new-row admission — happens sooner). Bucket sets are
    # power-of-two by default and auto-derived in __post_init__; the
    # executable space is (batch bucket x window bucket x kv bucket),
    # so keep both sets SMALL — warmup pre-compiles the grid so
    # steady-state serving never compiles.
    window_adapt: bool = True
    # power-of-two batch buckets <= max_num_seqs the decode dispatch
    # may shrink to (auto: 1, 2, 4, ..., max_num_seqs). Operators may
    # pass arbitrary ascending sizes (e.g. a fleet whose typical
    # concurrency is 6 adds a 6 bucket) at warmup-compile cost.
    decode_batch_buckets: Tuple[int, ...] = ()
    # window-length buckets <= decode_window the dispatch may shrink
    # to (auto: 1, 2, 4, ..., decode_window)
    decode_window_buckets: Tuple[int, ...] = ()
    # decode windows queued on the device at once (engine.step
    # pipelining). 2 keeps the device saturated in the common case:
    # window N+1 is queued while N runs, and the host processes N's
    # tokens during N+1. 3 hides a host round-trip behind two device
    # windows (whether a local chip needs that is not measured);
    # deeper queues add latency to composition changes (admission
    # waits behind every queued window).
    pipeline_depth: int = 2
    # attention is computed over the cache prefix [:kv_len] where kv_len is
    # the smallest bucket covering every live position — decode cost scales
    # with live context, not max_model_len. Auto-derived in __post_init__.
    kv_len_buckets: Tuple[int, ...] = ()
    # paged KV (models/kv.py): pool block size in tokens, and the pool's
    # total KV capacity in tokens (None = worst case max_num_seqs *
    # max_model_len). A bounded pool admits a batch by its LIVE context
    # rather than reserving worst case per slot, with recompute
    # preemption (engine.py _preempt) as the pressure valve — so e.g.
    # batch 32 x 8k-capable slots fit where 8 fully-reserved ones did.
    kv_block_size: int = 64
    kv_pool_tokens: Optional[int] = None
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    tensor_parallel_size: int = 1
    # multi-slice passthrough knobs (SURVEY §2.9: the reference exposes
    # PP/EP only as engine passthrough; same here — the chart forwards
    # them, the engine validates). Values > 1 are rejected until the
    # engine grows pipeline/expert sharding over DCN.
    pipeline_parallel_size: int = 1
    expert_parallel_size: int = 1
    # MoE prefill capacity factor override (ops/moe.py): None keeps the
    # model family default (ModelConfig.moe_capacity_factor)
    moe_capacity_factor: Optional[float] = None
    # weight-only int8 (models/quant.py): halves decode weight-streaming
    # HBM traffic; None serves in --dtype precision
    quantization: Optional[str] = None
    # n-gram (prompt-lookup) speculative decoding: draft length per
    # macro-step (0 = off). Eligibility is PER ROW: greedy, unguided,
    # unshaped, no-alternatives rows speculate; other rows single-step
    # inside the same window (engine/runner._decode_spec_impl).
    speculative_ngram_tokens: int = 0
    # Serving meshes that shard the KV pool's block axis (dp > 1) cannot
    # run the pallas paged-attention kernel shard-local; they fall back
    # to the gathered-view jnp path, which re-materializes ~3x the KV
    # traffic the kernel exists to delete. That perf cliff must be
    # CHOSEN: constructing a runner on such a mesh with flash enabled
    # raises unless this flag acknowledges the fallback (then it's one
    # loud warning). tp-only meshes are unaffected.
    dp_gather_attention_ok: bool = False
    seed: int = 0
    checkpoint: Optional[str] = None         # HF checkpoint dir; random if None
    # real embedding model for /v1/embeddings + rerank/score
    # (models/encoder.py): an ENCODER_PRESETS name or a HF BertModel
    # checkpoint dir. None keeps the causal-mean-pool approximation
    # (flagged in responses as embedding_source=causal-mean-pool).
    embedding_model: Optional[str] = None
    # in-HBM prefix cache (engine/block_manager.py): finished sequences'
    # full KV blocks stay in the pool under chain-hash keys; matching
    # prompts attach them by reference — zero copies, zero extra HBM
    # (the reference's --enable-prefix-caching)
    enable_prefix_caching: bool = False
    max_top_k: int = 64                      # static top-k bound for sampler
    # KV tiering (the reference's --kv-transfer-config JSON; see
    # kvcache/connector.py). Keys: kv_role, chunk_size, local_cpu_gb,
    # local_disk_path, local_disk_gb, remote_url.
    kv_transfer_config: Optional[Dict[str, Any]] = None
    # kvplane intra-replica defrag: when a step's admissions hit the
    # fragmented-failure regime, compact the BlockManager free list
    # between fused windows (block_manager.defrag — host-side index
    # reordering, KV bytes never move)
    kvplane_defrag: bool = True
    # Multi-LoRA serving (reference: --enable-lora + LoraAdapter CRD
    # proposal, helm/templates/deployment-vllm-multi.yaml:65-67).
    # name -> .npz path (models/lora.py format), or name -> "random:SEED"
    # for synthetic adapters (tests/demos). Each adapter is served as its
    # own model id next to the base model.
    lora_adapters: Optional[Dict[str, str]] = None
    lora_rank: int = 8
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q", "v")
    # Overload protection (docs/engine.md "Overload protection"):
    # bounded admission — add_request raises AdmissionRejected (the
    # server answers 503 + Retry-After) once this many sequences are
    # queued un-admitted, instead of growing the waiting deque without
    # bound until every client times out at once. None = unbounded
    # (the pre-overload-protection behavior).
    max_waiting_seqs: Optional[int] = None
    # queue-time cap: a sequence still waiting (never admitted, no
    # output) after this many milliseconds is shed by the scheduler
    # (finish_reason "queue_delay" -> 503 + Retry-After at the server)
    # rather than serviced long after its useful-by time. None = never.
    max_queue_delay_ms: Optional[float] = None
    # Efficiency telemetry (engine/efficiency.py; docs/engine.md
    # "Efficiency telemetry"): the per-chip HBM peak bandwidth the MBU
    # gauge normalizes against (GB/s). None = look it up by the
    # device's kind (efficiency.HBM_PEAK_GBPS); a kind the table does
    # not know reports no MBU. And the bounded ring of per-window
    # breakdowns served on GET /debug/perf.
    hbm_peak_gbps: Optional[float] = None
    perf_ring_entries: int = 256

    def __post_init__(self):
        if self.dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"dtype={self.dtype!r} unsupported: TPU serving runs "
                f"bfloat16 (MXU-native) or float32")
        if self.kv_dtype not in ("bfloat16", "float32", "int8"):
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} unsupported: bfloat16, "
                f"float32, or int8 (quantized cache — halves "
                f"long-context decode HBM traffic, models/kv.py)")
        if self.pipeline_parallel_size != 1:
            raise NotImplementedError(
                "pipeline-parallel SERVING is not implemented: decode "
                "would pipeline one token at a time (pure bubble) "
                "without multi-batch in-flight scheduling. Serving "
                "scales via tensor_parallel_size/expert_parallel_size "
                "within a slice and replicaCount across slices")
        if self.expert_parallel_size < 1:
            raise ValueError("expert_parallel_size must be >= 1")
        if not 0 <= self.speculative_ngram_tokens <= 16:
            raise ValueError("speculative_ngram_tokens must be in 0..16")
        if self.speculative_ngram_tokens and self.window_adapt:
            # the speculative executable is the most expensive compile,
            # and warming it across the full (batch x window) grid
            # would multiply warmup by the grid size — while leaving
            # the grid cold trades that for multi-second mid-serving
            # compile stalls at every geometry the adaptive dispatch
            # reaches. Until the spec grid has its own bounded warmup
            # story, speculation pins the full fixed geometry.
            self.window_adapt = False
        if not 1 <= self.pipeline_depth <= 8:
            raise ValueError("pipeline_depth must be in 1..8 (each queued "
                             "window delays admission by one window)")
        if self.quantization not in (None, "int8"):
            raise ValueError(
                f"quantization={self.quantization!r} unsupported: only "
                f"weight-only 'int8' (models/quant.py) is implemented")
        if self.kv_block_size < 8 or self.kv_block_size % 8:
            raise ValueError(
                f"kv_block_size={self.kv_block_size} must be a multiple "
                f"of 8 (TPU minor-dim tiling of the [Bs, D] block panel)")
        # blocks never need to exceed one sequence's worth of positions
        self.kv_block_size = min(
            self.kv_block_size,
            max(8, (self.max_model_len + 7) // 8 * 8))
        if self.kv_pool_tokens is not None and self.kv_pool_tokens <= 0:
            raise ValueError("kv_pool_tokens must be positive")
        if self.max_waiting_seqs is not None and self.max_waiting_seqs < 0:
            raise ValueError("max_waiting_seqs must be >= 0 "
                             "(0 sheds anything that cannot be admitted "
                             "immediately; None = unbounded)")
        if self.max_queue_delay_ms is not None \
                and self.max_queue_delay_ms <= 0:
            raise ValueError("max_queue_delay_ms must be positive")
        if self.hbm_peak_gbps is not None and self.hbm_peak_gbps <= 0:
            raise ValueError("hbm_peak_gbps must be positive")
        if self.perf_ring_entries < 1:
            raise ValueError("perf_ring_entries must be >= 1")
        # chunks never exceed prefill_chunk (or the cache), so larger
        # buckets would only waste warmup compiles and executable HBM
        self.prefill_chunk = min(self.prefill_chunk, self.max_model_len)
        buckets = sorted(b for b in self.prefill_buckets
                         if b <= self.prefill_chunk)
        if not buckets or buckets[-1] < self.prefill_chunk:
            buckets.append(self.prefill_chunk)
        self.prefill_buckets = tuple(buckets)
        self.decode_window = max(1, min(self.decode_window,
                                        self.max_model_len))

        def _bucket_set(given, cap: int, what: str) -> Tuple[int, ...]:
            """Validate a user bucket set (ascending, positive,
            <= cap, cap always covered) or derive the power-of-two
            default 1, 2, 4, ..., cap."""
            if given:
                buckets = sorted({int(b) for b in given if 0 < b <= cap})
                if not buckets:
                    raise ValueError(
                        f"{what} has no usable entries in [1, {cap}]: "
                        f"{given}")
            else:
                buckets, b = [], 1
                while b < cap:
                    buckets.append(b)
                    b *= 2
            if not buckets or buckets[-1] < cap:
                buckets.append(cap)
            return tuple(buckets)

        self.decode_batch_buckets = _bucket_set(
            self.decode_batch_buckets, self.max_num_seqs,
            "decode_batch_buckets")
        self.decode_window_buckets = _bucket_set(
            self.decode_window_buckets, self.decode_window,
            "decode_window_buckets")
        if not self.kv_len_buckets:
            # powers of two from 512 (or the cache size if smaller) up to
            # max_model_len: at 32k context that's 7 buckets — bounded
            # compile count, per-step attention cost within 2x of live len
            b, buckets = 512, []
            while b < self.max_model_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_model_len)
            self.kv_len_buckets = tuple(
                x for x in buckets if x <= self.max_model_len)
        else:
            # user-supplied buckets: sort, drop over-long ones, and always
            # cover max_model_len — kv_bucket_for must never return a
            # kv_len smaller than a legal live position
            buckets = sorted(b for b in self.kv_len_buckets
                             if 0 < b <= self.max_model_len)
            if not buckets or buckets[-1] < self.max_model_len:
                buckets.append(self.max_model_len)
            self.kv_len_buckets = tuple(buckets)

    @property
    def max_blocks_per_seq(self) -> int:
        """Block-table width MB: blocks covering max_model_len."""
        return -(-self.max_model_len // self.kv_block_size)

    @property
    def num_kv_blocks(self) -> int:
        """Pool size in blocks, INCLUDING trash block 0. Clamped to
        [one full-length sequence, worst case for the whole batch]."""
        worst = self.max_num_seqs * self.max_blocks_per_seq
        if self.kv_pool_tokens is None:
            n = worst
        else:
            n = -(-self.kv_pool_tokens // self.kv_block_size)
        return min(max(n, self.max_blocks_per_seq), worst) + 1

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        return self.prefill_buckets[-1]

    def kv_bucket_for(self, length: int) -> int:
        """Smallest kv-length bucket covering `length` cache positions."""
        for b in self.kv_len_buckets:
            if length <= b:
                return b
        return self.kv_len_buckets[-1]

    # the widest chunk bucket a full-batch prefill dispatch is built
    # for: the default prefill chunk, the widest a deployment ran one
    # at before chunks of 1024 and more existed. The full batch is for
    # a burst of short chunks, where it saves dispatches and weight
    # reads; one row of a wider chunk holds the device for tens of
    # milliseconds by itself, and max_num_seqs rows of it multiply the
    # executable's activations, the experts' row buffers and the
    # logits of every position by the batch for nothing. A rule of the
    # chunk alone: whatever ran with chunks up to 512 tokens
    # dispatches as it did, at any number of slots
    FULL_BATCH_CHUNK_TOKENS = 512

    def prefill_rows_for(self, chunks: int, bucket: int = 0) -> int:
        """Rows of the prefill dispatches that serve ``chunks`` chunks
        due at once in one chunk bucket of ``bucket`` tokens: 1 (a
        dispatch per chunk) up to a quarter of the batch, max_num_seqs
        (one dispatch for all) above, unless the bucket is wider than
        FULL_BATCH_CHUNK_TOKENS: then 1 whatever is due.
        The ends of the decode batch buckets' range and nothing
        between: every row count is one more executable per (chunk
        bucket, kv bucket, variant) to warm or to compile mid-serving,
        and a one-row chunk of 128 tokens already costs a v5e more in
        arithmetic than in reading the weights, so that rows beyond the
        chunks due buy nothing and a few weight reads cost less than
        computing four times the rows."""
        return (1 if chunks <= max(1, self.max_num_seqs // 4)
                or bucket > self.FULL_BATCH_CHUNK_TOKENS
                else self.max_num_seqs)

    def batch_bucket_for(self, rows: int) -> int:
        """Smallest decode batch bucket covering `rows` slots. (The
        window axis has no covering lookup on purpose: the dispatch
        picks the LARGEST window bucket under an expected-dead budget
        — engine._choose_window — not the smallest covering one.)"""
        for b in self.decode_batch_buckets:
            if rows <= b:
                return b
        return self.decode_batch_buckets[-1]
