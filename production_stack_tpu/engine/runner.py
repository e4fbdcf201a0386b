"""ModelRunner: owns params + KV cache and the cached XLA executables.

TPU execution model:
- ``decode``: a *multi-step window* — ``lax.scan`` fuses
  ``engine_cfg.decode_window`` forward+sample steps into ONE executable
  dispatch with ONE device→host sync for the whole window (int32 ids
  [B, W]), amortizing Python dispatch overhead ~W×. Batch is always
  [max_num_seqs] (free slots run as padding rows). Executables are cached
  per (window, kv-length bucket, greedy): attention cost scales with the
  live context (kv bucket), not max_model_len, and all-greedy batches
  skip the [B, V] sampling sort entirely.
- ``prefill``: every admissible sequence's next chunk of one
  chunk-length bucket is prefilled in a dispatch of ``rows`` rows
  (tokens [rows, Tb]; the engine runs one row a chunk, or all
  max_num_seqs for a burst: engine._do_prefill). A row is not a
  slot: ``slots`` [rows] says which slot each row serves, and the
  executable gathers whatever is kept per slot (block tables,
  sampling, guided and penalty state) by it. Spare rows are parked at position S, where
  nothing they compute is written or read. One executable per (rows,
  chunk-length bucket, kv bucket).
- Decode inputs are *device-carried*: each window's last sampled ids and
  advanced positions stay on device and feed the next window directly.
  A row that leaves or joins is an edit of the carry AT ITS SLOT, on
  the device and in device order (``edit_carry``: a parked row, or the
  id a prefill has just sampled, never seen by the host before the
  next window reads it); the host uploads its mirrors only where the
  carry is replaced whole (a batch-bucket change, compaction, a
  preemption, guided or shaped rows: engine._decode_dirty). A steady
  decode window costs exactly one dispatch + one device→host sync.
- Both donate the KV cache => XLA updates it in place in HBM.

The reference has no equivalent (engine external, SURVEY.md §1 L2); this
is the TPU-native core the stack serves from.
"""

import contextlib
import threading
import time
from functools import partial

import numpy as np
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sampler import (SamplingParams,
                                                 adjust_logits, sample)
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models.kv import (KV_HEADS, KVCache, cache_for,
                                            latent_pool_width)
from production_stack_tpu.models import llama
from production_stack_tpu.ops import moe, retention
from production_stack_tpu.ops.gdn import gdn_path
from production_stack_tpu.ops.mamba import mamba_path
from production_stack_tpu.ops.mamba2 import mamba2_path
from production_stack_tpu.ops.pallas_paged import (
    JNP_GATHER, attention_path, kv_append_path)
from production_stack_tpu.ops.rope import rope_table
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


def _named(name: str, fn, **static):
    """``fn`` with ``static`` bound, under a name of its own. jax.jit
    names an executable after its function's ``__name__``
    (``jit_<name>``: the HLO module, and the ``XLA Modules`` events of
    a profiler capture); a bare functools.partial has none and every
    step function would read ``jit__unknown``. (The cache-free helpers
    below are plain local functions and carry their own names.)"""
    bound = partial(fn, **static)
    bound.__name__ = bound.__qualname__ = name
    return bound


def _step_counts(work):
    """What a decode step hands the host beside its tokens, of what the
    forward counted (models/llama.Work, read by name): (the experts
    whose weights it read, a looped model's passes), each None where
    the model has no such part. Two results of the window's executable
    and not one container: the lowered text names a result by its place
    in the output, and a MoE model's window is pinned with the experts'
    count a bare result (tests/test_chip_compile.py)."""
    return (None, None) if work is None else (work.experts_read, work.loop)


def _carry_edit_impl(tokens, positions, gstate, slots, new_tokens,
                     new_positions):
    """The decode carry with rows ``slots`` [R] set to ``new_tokens`` /
    ``new_positions`` [R] and DFA state 0. A slot outside the carry's
    batch (the engine's "no row here" is max_num_seqs) is dropped."""
    return (tokens.at[slots].set(new_tokens, mode="drop"),
            positions.at[slots].set(new_positions, mode="drop"),
            gstate.at[slots].set(0, mode="drop"))


class ModelRunner:
    def __init__(self, model_cfg: ModelConfig, engine_cfg: EngineConfig,
                 params=None, mesh=None, lora_stacked=None,
                 lora_scaling: float = 1.0,
                 weights_loaded_s: float = 0.0):
        """``weights_loaded_s``: the seconds the caller spent loading
        ``params`` (a checkpoint's read), which the ``startup`` block's
        ``weights_s`` counts with what is spent on them here."""
        self.model_cfg = model_cfg
        self.engine_cfg = engine_cfg
        self.mesh = mesh
        if model_cfg.state_layers and not model_cfg.attn_layers:
            # state pages are the model's ONLY cache (models/kv.py
            # "State pages alone"): the page is the sequence's one
            # block and holds any context, so the block is
            # max_model_len tokens, a table row one column, the pool
            # max_num_seqs pages and the trash page, and there is one
            # kv bucket: no executable differs by context. The engine
            # and the scheduler read the same object
            engine_cfg.kv_block_size = engine_cfg.max_model_len
            engine_cfg.kv_pool_tokens = None
            engine_cfg.kv_len_buckets = (engine_cfg.max_model_len,)
        # stacked multi-LoRA adapters, layer axis leading for lax.scan
        # (models/lora.py); row selection comes in via sampling.adapter
        from production_stack_tpu.models import lora as lora_mod
        self._lora = lora_mod.layer_slice(lora_stacked)
        self._lora_scaling = lora_scaling
        # rope table must cover the cache length, not just the model's
        # native max (see ops/rope.py clamping note)
        self.rope = rope_table(engine_cfg.max_model_len, model_cfg.rope_dim_,
                               model_cfg.rope_theta,
                               scaling=model_cfg.rope_scaling)
        t0 = time.monotonic() - weights_loaded_s
        if params is None:
            # quantized leaf by leaf as it is made (llama.init_params):
            # the full-precision tree of a model that needs
            # --quantization to fit never exists
            params = llama.init_params(
                model_cfg, jax.random.PRNGKey(engine_cfg.seed),
                quantization=engine_cfg.quantization)
            logger.info("random-initialized %s (%.2fs)", model_cfg.name,
                        time.monotonic() - t0)
        elif engine_cfg.quantization == "int8":
            from production_stack_tpu.models import quant
            # loaded checkpoint: donate, so XLA may free each fp buffer
            # as its int8 copy is produced. The incoming params are
            # consumed.
            params = jax.jit(quant.quantize_params,
                             donate_argnums=0)(params)
        self.params = params
        # paged pool [L, N, Hkv, Bs, D] + per-slot block tables [B, MB]
        # (models/kv.py); the tables device array is refreshed by the
        # engine whenever its allocator changes a row. Under a mesh the
        # block axis shards over dp (parallel/sharding.cache_pspec), so
        # N is padded up to a dp multiple — the extra blocks are simply
        # allocatable (the engine sizes its BlockManager from
        # cache.num_blocks, not the config).
        n_blocks = engine_cfg.num_kv_blocks
        if mesh is not None:
            dp_size = mesh.shape.get("dp", 1)
            n_blocks = -(-n_blocks // dp_size) * dp_size
        kv_dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                 "int8": jnp.int8}[engine_cfg.kv_dtype]
        if model_cfg.mla and mesh is not None and any(
                size > 1 for size in mesh.shape.values()):
            raise ValueError(
                f"{model_cfg.name}: a latent-attention model (KV pool "
                f"layout 'latent') runs on one chip only; mesh "
                f"{dict(mesh.shape)} shards it (tp, ep, dp must be 1)")
        if model_cfg.index_topk and engine_cfg.speculative_ngram_tokens:
            raise ValueError(
                f"{model_cfg.name}: speculative decoding "
                f"(--speculative-ngram-tokens) is not supported on a "
                f"model that selects what it attends (index_topk "
                f"{model_cfg.index_topk}): the selection is built for "
                f"one query position a row and for prefill chunks")
        if model_cfg.state_layers:
            self._refuse_with_state_pages(lora_stacked)
        if model_cfg.loop_steps > 1:
            self._refuse_looped(lora_stacked)
        # K and V per kv head, or the latent pool, with the index pool
        # beside it where the model selects what it attends, or K and V
        # of the attention layers with a state page a slot (and the
        # trash page) beside them where the model has Gated DeltaNet
        # or Mamba layers, or state pages ALONE, a page a block, where every
        # layer is a power retention layer (models/kv.cache_for; it
        # refuses an int8 latent pool and an int8 pool beside state
        # pages by name)
        cache_t0 = time.monotonic()
        self.cache: KVCache = cache_for(
            model_cfg, n_blocks, engine_cfg.kv_block_size, dtype=kv_dt,
            state_pages=(engine_cfg.max_num_seqs + 1
                         if self._pages_beside_kv else 0))
        self._tables = jnp.zeros(self.table_shape, jnp.int32)
        self._tables_host = np.zeros(self.table_shape, np.int32)
        self._tables_dirty = False
        # the ``startup`` block's spans (GET /debug/perf): the pool and
        # the tables as the host saw them made, and the weights until
        # they are READY ON THE DEVICE, which a thread waits for (below)
        # so that the start itself never does
        self.startup_spans = {
            "weights_s": None,
            "cache_alloc_s": round(time.monotonic() - cache_t0, 4)}
        if mesh is not None:
            # tensor-parallel serving: weights/cache sharded over the
            # slice's chips; XLA derives all ICI collectives from here
            from jax.sharding import NamedSharding
            from production_stack_tpu.parallel.sharding import (
                cache_pspec, param_shardings)
            if (self._attention_path(1, mesh) == JNP_GATHER
                    and self._attention_path(1, None) != JNP_GATHER):
                # block-axis-sharded pools (dp > 1) forfeit the paged
                # kernel this backend would otherwise run
                # (ops/pallas_paged.attention_path): the gathered-view
                # fallback re-materializes ~3x the KV traffic. Never
                # let a helm value stumble into that.
                cliff = (
                    "serving mesh %s shards the KV pool's block axis: "
                    "the pallas paged-attention kernel only runs "
                    "shard-local on tp-only meshes, so this config "
                    "serves on the gathered-view jnp path (~3x decode "
                    "KV traffic). Prefer tp-only serving meshes with "
                    "replicaCount for data parallelism." % dict(
                        mesh.shape))
                if engine_cfg.dp_gather_attention_ok:
                    logger.warning(
                        "dp_gather_attention_ok=True: " + cliff)
                else:
                    raise ValueError(
                        cliff + " Set dp_gather_attention_ok=True to "
                        "serve on the gather path anyway.")
            tp = mesh.shape.get("tp", 1)
            if model_cfg.num_kv_heads % tp:
                raise ValueError(
                    f"tensor_parallel_size {tp} must divide num_kv_heads "
                    f"{model_cfg.num_kv_heads} (KV-head replication is not "
                    f"implemented yet)")
            ep = mesh.shape.get("ep", 1)
            if ep > 1:
                # validated here (not only in LLMEngine) so explicitly
                # passed meshes fail with a clear error too
                if not model_cfg.num_experts:
                    raise ValueError(
                        f"mesh has ep={ep} but model {model_cfg.name!r} "
                        f"is dense (no experts)")
                if model_cfg.num_experts % ep:
                    raise ValueError(
                        f"ep={ep} does not divide num_experts="
                        f"{model_cfg.num_experts}")
            self.params = jax.device_put(
                self.params, param_shardings(mesh, self.params))
            cache_sh = NamedSharding(mesh, cache_pspec())
            if self.cache.quantized:
                from production_stack_tpu.parallel.sharding import (
                    cache_scale_pspec)
                scale_sh = NamedSharding(mesh, cache_scale_pspec())
                self.cache = KVCache(
                    jax.device_put(self.cache.k, cache_sh),
                    jax.device_put(self.cache.v, cache_sh),
                    jax.device_put(self.cache.ks, scale_sh),
                    jax.device_put(self.cache.vs, scale_sh))
            else:
                self.cache = KVCache(
                    jax.device_put(self.cache.k, cache_sh),
                    jax.device_put(self.cache.v, cache_sh))
            from jax.sharding import PartitionSpec as _P
            self._tables_sharding = NamedSharding(mesh, _P())
            self._tables = jax.device_put(self._tables,
                                          self._tables_sharding)
            if self._lora is not None:
                # adapters are small (rank << hidden): replicate
                from jax.sharding import PartitionSpec
                self._lora = jax.device_put(
                    self._lora, NamedSharding(mesh, PartitionSpec()))
        else:
            self._tables_sharding = None

        def stamp_weights(ready=self.params):
            jax.block_until_ready(ready)
            self.startup_spans["weights_s"] = round(
                time.monotonic() - t0, 4)
        threading.Thread(target=stamp_weights, name="pstpu-weights-ready",
                         daemon=True).start()
        self._key = jax.random.PRNGKey(engine_cfg.seed ^ 0x5EED)
        # device-carried decode inputs: (tokens [B], positions [B]);
        # refreshed from host mirrors only when the engine marks them stale
        self._dec_tokens = None
        self._dec_pos = None
        self._dec_gstate = None   # guided-decoding DFA states [B]
        # penalty state (uploaded only when some live row uses OpenAI
        # logit shaping — engine._dispatch_decode): generated-token
        # counts [B, V] ride the decode carry; prompt membership [B, V]
        # is per-window constant
        self._dec_counts = None
        self._dec_prompt_seen = None
        # EOS id for min_tokens masking; the engine sets it from its
        # tokenizer after construction (static per executable)
        self._eos_id = 0

        # compile observer (engine/efficiency.py): an object with
        # compile_started/compile_finished hooks, stamped around every
        # serving-executable build in _compile so compile
        # stalls are attributable (counters, histogram, trace events)
        # instead of bare log lines. None = no accounting (bare runner
        # in tests).
        self.compile_observer = None
        # executable caches: decode keyed (batch, steps, kv_len,
        # variant), prefill keyed (rows, chunk bucket, kv bucket,
        # variant)
        self._decode_fns = {}
        self._prefill_fns = {}
        # the carry's edit by slot (edit_carry): one jitted function;
        # the (carry batch, rows) shapes it has run at, each built when
        # a carry of that batch is first uploaded (set_decode_state)
        self._carry_edit = jax.jit(_named("carry_edit", _carry_edit_impl))
        self._carry_edit_shapes = set()
        # "kind|window|kv|batch" (the compile observer's key) -> the
        # attention path that executable was compiled on (_compile)
        self.attention_paths: Dict[str, str] = {}
        # the same key -> how that executable lands its new K/V or
        # latents in the pool (ops/pallas_paged.kv_append_path): "rows"
        # a decode window on a kernel's path, "blocks" everything else
        self.kv_appends: Dict[str, str] = {}
        # the same key -> the strategy its experts take (ops/moe
        # moe_path); empty on a dense model
        self.moe_paths: Dict[str, str] = {}
        # the same key -> the implementation its Gated DeltaNet layers
        # (ops/gdn.gdn_path) or its power retention layers take
        # (ops/retention.retention_path); empty on a model without them
        self.mixer_paths: Dict[str, str] = {}
        # per-batch-bucket sliced views of the sampling params and
        # block tables (invalidated when the source object changes):
        # batch-bucketed dispatches must not pay a 14-array re-slice
        # per window
        self._sampling_slices = (None, {})
        self._tables_slices = (None, {})
        # KV-tiering primitives (kvcache/connector.py), cached per chunk size
        self._extract_fns = {}
        self._inject_fns = {}
        # embeddings path, cached per (batch, padded length)
        self._embed_fns = {}
        # prompt-logprobs (echo) path, cached per (batch, padded length)
        self._prompt_lp_fns = {}

    @property
    def table_shape(self) -> Tuple[int, int]:
        """[slots, columns] of the table rows: a slot's blocks and,
        where the model keeps state a sequence BESIDE a K/V pool, its
        state page as the last column (models/kv.split_tables). A
        model with state pages alone has the one column: its block is
        its page."""
        return (self.engine_cfg.max_num_seqs,
                self.engine_cfg.max_blocks_per_seq
                + self._pages_beside_kv)

    @property
    def _pages_beside_kv(self) -> bool:
        """Does the model keep state pages BESIDE a K/V pool (Gated
        DeltaNet or Mamba layers among attention layers)?"""
        cfg = self.model_cfg
        return bool(cfg.state_layers and cfg.attn_layers)

    def _refuse_with_state_pages(self, lora_stacked) -> None:
        """What a model with state pages (Gated DeltaNet or power
        retention layers: state a sequence, models/kv.py) cannot run
        with yet, each refused by name at start with its reason."""
        name, ecfg, mesh = self.model_cfg.name, self.engine_cfg, self.mesh
        alone = not self.model_cfg.attn_layers
        refused = [
            (alone and ecfg.kv_dtype == "int8", "an int8 cache "
             "(--kv-cache-dtype int8)", "the state is float32 and no "
             "K or V is cached"),
            (ecfg.enable_prefix_caching, "prefix caching "
             "(--enable-prefix-caching)", "a prefix hit restores a "
             "sequence's blocks, not the state its layers had reached "
             "at the prefix's end"),
            (bool(ecfg.kv_transfer_config), "the KV connector "
             "(--kv-transfer-config: tiering, extract_chunk / "
             "inject_chunk, migrate_out, disaggregated handoff)",
             "a chunk on the wire carries K and V, and no snapshot of "
             "the state pages exists yet"),
            (bool(ecfg.speculative_ngram_tokens), "n-gram speculation "
             "(--speculative-ngram-tokens)", "a rejected draft has "
             "already advanced the state, and there is no rollback"),
            (mesh is not None and any(
                size > 1 for size in mesh.shape.values()),
             f"a mesh ({dict(mesh.shape) if mesh is not None else {}}: "
             f"tp, ep, dp must be 1)", "the state pool and its "
             "kernels run on one chip only"),
            (lora_stacked is not None or bool(ecfg.lora_adapters),
             "LoRA adapters", "the adapters' projections are those of "
             "an attention layer"),
            (bool(ecfg.checkpoint), "the checkpoint loader "
             "(--checkpoint)", "the published tensors' names (and the "
             "grouped columns of in_proj_qkvz) are not mapped yet"),
        ]
        layout = "state" if alone else "kv+state"
        for on, what, why in refused:
            if on:
                raise ValueError(
                    f"{name}: {what} is not supported on a model with "
                    f"state pages (KV pool layout {layout!r}): {why}")

    def _refuse_looped(self, lora_stacked) -> None:
        """What a looped model (cfg.loop_steps > 1: the layer stack run
        several times over a pool layer a layer and pass) cannot run
        with yet, each refused by name at start with its reason. Prefix
        caching, the KV connector, n-gram speculation and an int8 pool
        work over its pool layers as over any (tests/test_ouro.py)."""
        cfg, ecfg, mesh = self.model_cfg, self.engine_cfg, self.mesh
        refused = [
            (mesh is not None and any(
                size > 1 for size in mesh.shape.values()),
             f"a mesh ({dict(mesh.shape) if mesh is not None else {}}: "
             f"tp, ep, dp must be 1)", "the exit gate's leaves have no "
             "partition rule and the pass loop has not been compiled "
             "for several chips"),
            (lora_stacked is not None or bool(ecfg.lora_adapters),
             "LoRA adapters", "an adapter's update would join every "
             "pass of the shared layers, which no adapter of such a "
             "model has been compared with"),
        ]
        for on, what, why in refused:
            if on:
                raise ValueError(
                    f"{cfg.name}: {what} is not supported on a looped "
                    f"model ({cfg.loop_steps} passes over "
                    f"{cfg.pool_layers} pool layers): {why}")

    def set_lora(self, lora_stacked, lora_scaling: float = None) -> None:
        """Swap the stacked adapter pytree in place (runtime adapter
        load, engine.load_adapter). Same layer_slice + replicate-under-
        mesh treatment as construction; per-row selection still rides
        sampling.adapter, so existing executables stay valid — the
        stacked tensors only grew a row along the adapter axis, which
        is a runtime input, not a compile-time shape for the rows in
        use... but a NEW row count IS a new input shape, so touched
        executables recompile once on next dispatch (expected, bounded:
        one build per adapter-count change per bucket)."""
        from production_stack_tpu.models import lora as lora_mod
        lora = lora_mod.layer_slice(lora_stacked)
        if lora is not None and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            lora = jax.device_put(
                lora, NamedSharding(self.mesh, PartitionSpec()))
        self._lora = lora
        if lora_scaling is not None:
            self._lora_scaling = lora_scaling
        # adapter-count change means new stacked shapes: drop the
        # serving executables so the next dispatch builds against them
        # instead of feeding mismatched shapes to a stale jit cache
        # (the base-only paths — embed, prompt-logprobs, KV
        # extract/inject — never see the stack and keep their caches)
        self._decode_fns = {}
        self._prefill_fns = {}

    # ------------------------------------------------------------------
    # jitted impls (pure)
    # ------------------------------------------------------------------

    @jax.named_scope("sample")
    def _sample_position(self, last, sampling: SamplingParams, counts,
                         prompt_seen, pos, gstate, guide_next, guide_id,
                         key, *, greedy: bool, seeded: bool, plain: bool,
                         guided: bool, penalized: bool, eos_id: int,
                         topk: int):
        """The full single-position sampling treatment downstream of a
        forward's [B, V] logits, SHARED verbatim by _decode_impl (every
        step) and _decode_spec_impl (draft position 0 of every
        macro-step) so a row emits identically whichever executable its
        window ran on:

        penalty shaping (sampler.adjust_logits — counts ride the scan
        carry; the token being sampled is output index
        pos + 1 - prompt_len), the guided-DFA mask + state advance
        (one [B, V] gather per step, engine/guided.py), argmax or
        sample() (the sampled token lands at pos + 1 — the
        deterministic per-seed index; seeded/plain fork executables so
        default batches skip per-row PRNG / the [B, V] sort), the
        counts update, and the chosen-token logprob + top-K
        alternatives under the same post-shaping f32 distribution.

        Returns (ids [B], logprob [B], top_ids [B, K], top_lps [B, K],
        gstate', counts')."""
        B = last.shape[0]
        if penalized:
            last = adjust_logits(last, sampling, counts, prompt_seen,
                                 pos + 1 - sampling.prompt_len, eos_id)
        if guided:
            nxt_row = guide_next[guide_id, gstate, :]
            is_g = (guide_id > 0)[:, None]
            last = jnp.where(is_g & (nxt_row < 0), -jnp.inf, last)
        if greedy:
            ids = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            ids = sample(last, sampling, key,
                         positions=pos + 1 if seeded else None,
                         plain=plain)
        if guided:
            adv = jnp.take_along_axis(nxt_row, ids[:, None],
                                      axis=-1)[:, 0]
            gstate = jnp.where(guide_id > 0,
                               jnp.maximum(adv, 0), gstate)
        if penalized:
            counts = counts.at[jnp.arange(B), ids].add(1)
        lsm = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(lsm, ids[:, None], axis=-1)[:, 0]
        if topk:
            tl, ti = jax.lax.top_k(lsm, topk)
        else:
            tl = jnp.zeros((B, 1), jnp.float32)
            ti = jnp.zeros((B, 1), jnp.int32)
        return ids, lp, ti, tl, gstate, counts

    def _decode_impl(self, params, cache: KVCache, tables: jnp.ndarray,
                     tokens: jnp.ndarray,
                     positions: jnp.ndarray, sampling: SamplingParams,
                     key: jax.Array, guide_next: jnp.ndarray,
                     guide_id: jnp.ndarray, guide_state: jnp.ndarray,
                     out_counts: jnp.ndarray, prompt_seen: jnp.ndarray,
                     *, steps: int, kv_len: int,
                     greedy: bool, seeded: bool = False,
                     guided: bool = False, plain: bool = False,
                     penalized: bool = False, eos_id: int = 0,
                     topk: int = 0):
        """tokens/positions [B] -> (ids [B, steps], logprobs [B, steps],
        tokens', positions', cache', experts read [steps]: the experts
        whose weights each step read, summed over the layers; None on a
        dense model, a looped model's passes as each step counted them,
        llama.LoopWork of [steps] leaves; None on every other).

        `steps` forwards are fused via lax.scan; each step feeds its
        sampled ids back as the next step's tokens, and the final
        (tokens, positions) come back as device arrays to carry into the
        next window without a host round-trip. K/V writes go through the
        block tables; rows whose position has reached max_model_len
        (parked rows, finished windows' tails) are masked invalid and
        write to the trash block. Attention reads the first
        ceil(kv_len/Bs) blocks of every slot; the host guarantees every
        live position stays < kv_len AND its table row covers the whole
        window (engine._ensure_blocks).

        logprobs are the chosen tokens' log p under the PRE-temperature
        but POST-shaping distribution — after penalties/logit_bias
        (adjust_logits) and the guided-DFA mask, before temperature/
        top-p/top-k. For unshaped, unguided rows that is exactly the
        raw model distribution; shaped rows report the distribution
        they were actually decoded from (documented in docs/engine.md
        and protocol.py). One [B, V] log_softmax per step, noise next
        to the weight streaming, so they're always computed rather
        than forking the executable cache.
        """
        S = self.engine_cfg.max_model_len
        # where the model's power retention layers take the window form
        # at this step count, the window's own keys ride beside the
        # cache: the steps read the pages and the window's end writes
        # them, once (None for every other model and step count: the
        # steps are ``llama.forward``'s)
        window = llama.open_window(self.model_cfg, tables, positions,
                                   steps, positions < S)

        def body(carry, i):
            cache, toks, pos, gstate, counts, window = carry
            logits, cache, work, window = llama.forward_in_window(
                params, self.model_cfg, toks[:, None], pos[:, None],
                cache, window, block_tables=tables,
                rope=self.rope, kv_len=kv_len, mesh=self.mesh,
                lora_params=self._lora, adapter_ids=sampling.adapter,
                lora_scaling=self._lora_scaling,
                token_valid=(pos < S)[:, None])
            ids, lp, ti, tl, gstate, counts = self._sample_position(
                logits[:, 0, :], sampling, counts, prompt_seen, pos,
                gstate, guide_next, guide_id,
                jax.random.fold_in(key, i), greedy=greedy,
                seeded=seeded, plain=plain, guided=guided,
                penalized=penalized, eos_id=eos_id, topk=topk)
            return ((cache, ids, pos + 1, gstate, counts, window),
                    (ids, lp, ti, tl, *_step_counts(work)))

        ((cache, toks, pos, gstate, counts, window),
         (ids, lps, tis, tls, read, loop)) = jax.lax.scan(
            body, (cache, tokens, positions, guide_state, out_counts,
                   window), jnp.arange(steps))
        cache = llama.close_window(cache, window)
        # ids/lps [B, steps]; tis/tls [B, steps, K]
        return (ids.T, lps.T, tis.transpose(1, 0, 2),
                tls.transpose(1, 0, 2), toks, pos, gstate, counts,
                cache, read, loop)

    def _decode_spec_impl(self, params, cache: KVCache,
                          tables: jnp.ndarray,
                          tokens: jnp.ndarray, positions: jnp.ndarray,
                          history: jnp.ndarray, spec_ok: jnp.ndarray,
                          sampling: SamplingParams, key: jax.Array,
                          guide_next: jnp.ndarray, guide_id: jnp.ndarray,
                          guide_state: jnp.ndarray,
                          out_counts: jnp.ndarray,
                          prompt_seen: jnp.ndarray, *, steps: int,
                          kv_len: int, spec: int, mixed: bool = False,
                          seeded: bool = False, guided: bool = False,
                          plain: bool = False, penalized: bool = False,
                          eos_id: int = 0, topk: int = 0):
        """Decode window with PER-ROW n-gram (prompt-lookup) speculation.

        tokens/positions [B]; history [B, S] device-resident token ids
        (hist[b, t] = sequence b's token at position t, live through
        `positions[b]`); spec_ok [B] bool marks rows that speculate —
        greedy, unshaped, unguided, no-alternatives rows (the engine
        computes eligibility per row). Each of the `steps` macro-steps
        drafts `spec` tokens per row by copying what followed the most
        recent PRIOR occurrence of the current bigram in the history,
        verifies all spec+1 positions in one forward, and emits the
        agreeing prefix plus the bonus token — between 1 and spec+1
        tokens per macro-step, exact greedy semantics by construction
        (every emitted token is an argmax given the true prefix).

        Rows with spec_ok=False emit exactly one token per macro-step
        (acceptance forced to 0) and get the full single-step treatment
        at draft position 0: penalty shaping (adjust_logits), the
        guided-DFA mask + state advance, temperature sampling for
        non-greedy rows (`mixed`), and top-K alternatives. One shaped,
        guided, sampled, or top_logprobs row therefore no longer
        collapses speculation for the whole batch — it just declines it
        for itself.

        Returns (ids [B, steps, spec+1], logprobs same, top-K ids/lps
        [B, steps, K], counts [B, steps] valid-token counts, tokens',
        positions', history', gstate', out_counts', cache', experts
        read [steps] and a looped model's passes as _decode_impl). Rejected
        draft positions hold garbage K/V past the live length; the
        write-then-attend invariant (models/kv.py) makes them
        unobservable, exactly like window tail waste.
        """
        B = tokens.shape[0]
        S = history.shape[1]
        K = spec
        S_max = self.engine_cfg.max_model_len

        def draft_row(hist, pos):
            # latest i < pos with (hist[i-1], hist[i]) == current bigram
            a = hist[jnp.maximum(pos - 1, 0)]
            c = hist[pos]
            idx = jnp.arange(S)
            m = ((idx >= 1) & (idx < pos)
                 & (jnp.roll(hist, 1) == a) & (hist == c))
            j = jnp.max(jnp.where(m, idx, 0))     # 0 = no match
            return jax.lax.dynamic_slice(hist, (j + 1,), (K,))

        def body(carry, i):
            cache, toks, pos, hist, gstate, counts = carry
            draft = jax.vmap(draft_row)(hist, pos)          # [B, K]
            step_toks = jnp.concatenate([toks[:, None], draft], axis=1)
            step_pos = pos[:, None] + jnp.arange(K + 1)[None, :]
            logits, cache, work = llama.forward(
                params, self.model_cfg, step_toks, step_pos, cache,
                block_tables=tables,
                rope=self.rope, kv_len=kv_len, mesh=self.mesh,
                lora_params=self._lora, adapter_ids=sampling.adapter,
                lora_scaling=self._lora_scaling,
                token_valid=step_pos < S_max)
            expected = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # draft position 0 = the ordinary next token: the SHARED
            # single-position treatment (_sample_position) so every row
            # emits exactly what _decode_impl would have emitted. For
            # spec-eligible rows (greedy, unshaped, unguided) every
            # transform in it is identity and tok0 == the raw argmax,
            # so substituting it for expected[:, 0] changes nothing on
            # the speculative fast path.
            tok0, lp0, ti, tl, gstate, counts = self._sample_position(
                logits[:, 0, :], sampling, counts, prompt_seen, pos,
                gstate, guide_next, guide_id,
                jax.random.fold_in(key, i), greedy=not mixed,
                seeded=seeded, plain=plain, guided=guided,
                penalized=penalized, eos_id=eos_id, topk=topk)
            expected = expected.at[:, 0].set(tok0)
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                expected[..., None], axis=-1)[..., 0]       # [B, K+1]
            lp = lp.at[:, 0].set(lp0)
            agree = (draft == expected[:, :K])
            accepted = jnp.sum(jnp.cumprod(
                agree.astype(jnp.int32), axis=1), axis=1)   # [B] in 0..K
            accepted = jnp.where(spec_ok, accepted, 0)
            count = accepted + 1                            # emitted
            new_pos = pos + count
            new_toks = jnp.take_along_axis(
                expected, (count - 1)[:, None], axis=1)[:, 0]

            def write_row(h, p, emitted):
                return jax.lax.dynamic_update_slice(h, emitted,
                                                    (p + 1,))
            hist = jax.vmap(write_row)(hist, pos, expected)
            return ((cache, new_toks, new_pos, hist, gstate, counts),
                    (expected, lp, ti, tl, count, *_step_counts(work)))

        ((cache, toks, pos, hist, gstate, counts),
         (ids, lps, tis, tls, cnt, read, loop)) = jax.lax.scan(
            body, (cache, tokens, positions, history, guide_state,
                   out_counts),
            jnp.arange(steps))
        # scan stacks on axis 0: -> [B, steps, K+1] / [B, steps]
        return (ids.transpose(1, 0, 2), lps.transpose(1, 0, 2),
                tis.transpose(1, 0, 2), tls.transpose(1, 0, 2),
                cnt.T, toks, pos, hist, gstate, counts, cache, read, loop)

    def _prefill_impl(self, params, cache: KVCache, tables: jnp.ndarray,
                      slots: jnp.ndarray, tokens: jnp.ndarray,
                      starts: jnp.ndarray, lengths: jnp.ndarray,
                      sampling: SamplingParams, key: jax.Array,
                      guide_next: jnp.ndarray, guide_id: jnp.ndarray,
                      guide_state: jnp.ndarray,
                      out_counts: jnp.ndarray, prompt_seen: jnp.ndarray,
                      finishing: Optional[jnp.ndarray] = None,
                      *, kv_len: int, guided: bool = False,
                      penalized: bool = False, eos_id: int = 0,
                      topk: int = 0):
        """Chunk prefill of R rows. tokens [R, Tb], starts/lengths/
        slots [R]; everything else that has a leading axis is per SLOT
        ([max_num_seqs, ...]: tables, every leaf of sampling, guide_id,
        guide_state, out_counts, prompt_seen) and is gathered by
        ``slots`` here, before use, so a row may serve any slot.

        Every row writes its chunk at its own offset through its slot's
        block table; spare rows (parked at start S, whatever slot they
        name) and right-padding tokens are masked invalid and write to
        the trash block. Attention reads the first ceil(kv_len/Bs)
        blocks; host guarantees start + real chunk length <= kv_len for
        every participating row, whose table covers its whole chunk
        (blocks are allocated for the full prompt at admission). On MoE
        models the experts' capacity is reckoned on max_num_seqs rows
        whatever R is (ops/moe.moe_mlp ``capacity_tokens``): fewer rows
        never hold less per expert than the full dispatch does.
        A model whose plan has two depths (cfg.self_layers <
        num_layers: a decoder-hybrid-decoder) runs its later layers,
        the final norm and the head on each row's LAST real position
        alone, and not at all unless ``finishing`` (a bool scalar: some
        row's prompt ends in this chunk; llama.forward ``last``): no
        chunk makes logits for more than one position a row.
        Returns (sampled id of each row's last real token [R], its
        logprob [R], top ids and logprobs [R, K], cache', the experts'
        counts summed over the layers, int32 [3]: the rows they
        multiplied, the assignments kept and the rounds run where the
        layers hold a share of their experts (ops/moe.Work
        ``expert_rows``, ``held_rows``, ``rounds``); None on a dense
        model).
        """
        Tb = tokens.shape[1]
        S = self.engine_cfg.max_model_len
        tables, sampling, guide_id, guide_state, out_counts, \
            prompt_seen = jax.tree_util.tree_map(
                lambda x: jnp.take(x, slots, axis=0),
                (tables, sampling, guide_id, guide_state, out_counts,
                 prompt_seen))
        positions = starts[:, None] + jnp.arange(Tb)[None, :]
        # real tokens per row: right-padding and idle rows must not
        # write K/V, route in MoE layers, or steal expert capacity
        token_valid = ((jnp.arange(Tb)[None, :] < lengths[:, None])
                       & (starts < S)[:, None])
        two_depths = self.model_cfg.self_layers < self.model_cfg.num_layers
        at_last = jnp.maximum(lengths - 1, 0)
        logits, cache, work = llama.forward(
            params, self.model_cfg, tokens, positions, cache,
            block_tables=tables,
            rope=self.rope, kv_len=kv_len, mesh=self.mesh,
            lora_params=self._lora, adapter_ids=sampling.adapter,
            lora_scaling=self._lora_scaling, token_valid=token_valid,
            moe_capacity_tokens=self.engine_cfg.max_num_seqs * Tb,
            **(dict(last=at_last, finishing=finishing) if two_depths
               else {}))
        expert_rows = (
            None if work is None or work.expert_rows is None
            else jnp.stack([work.expert_rows, work.held_rows, work.rounds]))
        with jax.named_scope("sample"):
            last = (logits if two_depths else jnp.take_along_axis(
                logits, at_last[:, None, None], axis=1))[:, 0, :]
            if penalized:
                # first sampled token: counts cover any already-emitted
                # output (preemption-resume rows), prompt_seen the prompt
                last = adjust_logits(
                    last, sampling, out_counts, prompt_seen,
                    starts + lengths - sampling.prompt_len, eos_id)
            if guided:
                # first output token: mask from each guided row's
                # start state
                nxt_row = guide_next[guide_id, guide_state, :]
                is_g = (guide_id > 0)[:, None]
                last = jnp.where(is_g & (nxt_row < 0), -jnp.inf, last)
            ids = sample(last, sampling, key,
                         positions=starts + jnp.maximum(lengths, 1))
            lsm = jax.nn.log_softmax(last, axis=-1)
            lp = jnp.take_along_axis(lsm, ids[:, None], axis=-1)[:, 0]
            if topk:
                tl, ti = jax.lax.top_k(lsm, topk)
            else:
                B2 = last.shape[0]
                tl = jnp.zeros((B2, 1), jnp.float32)
                ti = jnp.zeros((B2, 1), jnp.int32)
            return ids, lp, ti, tl, cache, expert_rows

    # ------------------------------------------------------------------
    # host API
    # ------------------------------------------------------------------

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def set_block_tables(self, tables) -> None:
        """Note a change to the host block-table mirror [B, MB] int32.

        The upload is DEFERRED to the next dispatch that reads the
        tables (`_dev_tables`): the engine touches table rows several
        times per window (per-sequence block growth, admission,
        parking), and eager uploads would pay one host->device transfer
        per touch. Deferral coalesces them into at most one upload per
        dispatch (the saving is not measured on a local chip)."""
        self._tables_host = tables
        self._tables_dirty = True

    def _dev_tables(self) -> jnp.ndarray:
        if self._tables_dirty:
            t = jnp.asarray(self._tables_host, jnp.int32)
            if self._tables_sharding is not None:
                t = jax.device_put(t, self._tables_sharding)
            self._tables = t
            self._tables_dirty = False
        return self._tables

    def set_decode_state(self, tokens, positions,
                         guide_states=None, history=None) -> None:
        """Upload fresh decode inputs (host mirrors -> device carry).
        history [B, S] token ids (speculative n-gram drafting) is only
        uploaded when the engine runs with speculation enabled."""
        self._dec_tokens = jnp.asarray(tokens, jnp.int32)
        self._dec_pos = jnp.asarray(positions, jnp.int32)
        self._dec_gstate = (jnp.zeros_like(self._dec_tokens)
                            if guide_states is None
                            else jnp.asarray(guide_states, jnp.int32))
        self._dec_hist = (None if history is None
                          else jnp.asarray(history, jnp.int32))
        # a carry of a new batch brings its edits with it: whoever
        # warms a decode shape (warmup(), a benchmark's launcher) has
        # then warmed what a finish and a join run at that batch
        B = int(self._dec_tokens.shape[0])
        for R in (1, self.engine_cfg.max_num_seqs):
            if self._carry_edit_key(R) not in self._carry_edit_shapes:
                self.edit_carry(np.full((R,), B, np.int32),
                                np.zeros((R,), np.int32),
                                np.zeros((R,), np.int32))

    def _carry_edit_key(self, rows: int):
        """What an edit of ``rows`` rows of the present carry compiles
        for: the carry's batch and where it lies (under a mesh an
        uploaded carry and a window's result are placed differently)."""
        return (int(self._dec_tokens.shape[0]), rows,
                self._dec_tokens.sharding)

    def edit_carry(self, slots, tokens, positions) -> None:
        """Edit the device carry by slot, behind whatever is queued on
        the device and with no host sync: rows ``slots`` [R] get
        ``tokens`` / ``positions`` [R] as their next decode input (DFA
        state 0). ``tokens`` may be a device array: the ids a prefill
        dispatch has just sampled join the carry without the host
        seeing them. Parking a row is token 0 at position
        max_model_len. A slot outside the carry's batch is dropped
        (rows that are not to be touched name max_num_seqs)."""
        key = self._carry_edit_key(len(slots))
        args = (self._dec_tokens, self._dec_pos, self._dec_gstate,
                jnp.asarray(slots, jnp.int32),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(positions, jnp.int32))
        if key in self._carry_edit_shapes:
            out = self._carry_edit(*args)
        else:
            # the call compiles: stamped like every serving executable
            with self._observed("carry_edit", key[1], 0, key[0]):
                out = self._carry_edit(*args)
            self._carry_edit_shapes.add(key)
        self._dec_tokens, self._dec_pos, self._dec_gstate = out

    def set_penalty_state(self, out_counts, prompt_seen) -> None:
        """Upload OpenAI logit-shaping state: generated-token counts
        [B, V] int32 (rides the decode carry like tokens/positions) and
        prompt membership [B, V] bool. Only called when some live row
        uses penalties/min_tokens/logit_bias."""
        self._dec_counts = jnp.asarray(out_counts, jnp.int32)
        self._dec_prompt_seen = jnp.asarray(prompt_seen, bool)

    def _batch_sized(self, x, B: int):
        """Slice a host/device array's leading axis to the dispatch
        batch B (identity when already sized — the common steady
        case pays nothing)."""
        return x if x.shape[0] == B else x[:B]

    def _cached_slice(self, store_attr: str, source, B: int, make):
        """Memoize per-batch-bucket sliced views of a source object
        (sampling params, block tables) until the source is replaced:
        steady bucketed windows re-dispatch with the same inputs and
        must not re-slice per window."""
        src, cache = getattr(self, store_attr)
        if src is not source:
            cache = {}
            setattr(self, store_attr, (source, cache))
        out = cache.get(B)
        if out is None:
            out = cache[B] = make()
        return out

    def decode(self, sampling: SamplingParams, steps: int = 1,
               kv_len: Optional[int] = None, greedy: bool = False,
               seeded: bool = False, guide_table=None, guide_ids=None,
               spec: int = 0, spec_ok=None, plain: bool = False,
               penalized: bool = False, topk: int = 0):
        """Multi-step decode window over the CARRIED batch: the batch
        axis is whatever ``set_decode_state`` last uploaded — the
        engine's batch-bucketed compaction (docs/engine.md "Continuous
        batching across windows") uploads only the low ``B_bucket``
        slots, and every input here (sampling mirrors, block tables,
        guided ids, penalty carry) is sliced to that bucket, so parked
        rows beyond it are simply not computed. Executables are cached
        per (batch, steps, kv bucket, variant). Returns
        (ids, logprobs, counts, tops, work): without speculation ids/logprobs
        are [B, steps] and counts is None; with spec > 0 they are
        [B, steps, spec+1] plus counts [B, steps] of valid tokens per
        macro-step (_decode_spec_impl) — speculation is PER-ROW via
        spec_ok [B] bool (rows with False single-step with the full
        shaping/guided/sampling treatment). tops is None unless
        topk > 0: then (ids [B, steps, K], logprobs [B, steps, K])
        top-K alternatives per step. work (llama.Work of [steps]
        leaves, by name, each None where the model has no such part):
        ``experts_read`` int32, the experts whose weights each step
        read, summed over the layers (a MoE model), and ``loop``, what
        each step's passes counted (a looped one). The first
        np.asarray() is the window's single sync.

        guide_table [G, S, V] device int32 + guide_ids [B] activate
        constrained sampling (engine/guided.py); the per-row DFA state
        rides the device carry like tokens/positions."""
        kv_len = kv_len or self.engine_cfg.max_model_len
        seeded = seeded and not greedy
        plain = plain and not greedy
        guided = guide_table is not None
        gshape = guide_table.shape if guided else (1, 1, 1)
        # the dispatch batch IS the carried batch: the engine's
        # compaction uploads bucketed mirrors, everything else here
        # follows that shape
        B = int(self._dec_tokens.shape[0])
        src_sampling = sampling
        sampling = self._cached_slice(
            "_sampling_slices", src_sampling, B,
            lambda: jax.tree_util.tree_map(
                lambda x: self._batch_sized(x, B), src_sampling))
        full_tables = self._dev_tables()
        tables = self._cached_slice(
            "_tables_slices", full_tables, B,
            lambda: self._batch_sized(full_tables, B))
        if not guided:
            guide_table = jnp.zeros((1, 1, 1), jnp.int32)
            guide_ids = jnp.zeros((B,), jnp.int32)
        else:
            guide_ids = self._batch_sized(
                jnp.asarray(guide_ids, jnp.int32), B)
        if penalized:
            counts = self._batch_sized(self._dec_counts, B)
            seen = self._batch_sized(self._dec_prompt_seen, B)
        else:
            # dummy carries: the unpenalized executable never reads or
            # writes them, so keep them tiny
            counts = jnp.zeros((B, 1), jnp.int32)
            seen = jnp.zeros((B, 1), bool)
        if spec:
            mixed = not greedy
            args = (self.params, self.cache, tables,
                    self._dec_tokens, self._dec_pos, self._dec_hist,
                    self._batch_sized(jnp.asarray(spec_ok, bool), B),
                    sampling,
                    self._next_key(), guide_table,
                    guide_ids, self._dec_gstate,
                    counts, seen)
            key = ("spec", B, steps, kv_len, spec, mixed, seeded, guided,
                   gshape, plain, penalized, topk)

            def make_spec():
                logger.info("compiling speculative decode window "
                            "(batch=%d steps=%d kv=%d draft=%d%s%s%s%s)",
                            B, steps, kv_len, spec,
                            " mixed" if mixed else "",
                            " guided" if guided else "",
                            " penalized" if penalized else "",
                            f" topk={topk}" if topk else "")
                return jax.jit(
                    _named("decode_spec_window", self._decode_spec_impl,
                           steps=steps, kv_len=kv_len, spec=spec,
                           mixed=mixed, seeded=seeded, guided=guided,
                           plain=plain, penalized=penalized,
                           eos_id=self._eos_id, topk=topk),
                    donate_argnums=(1,))

            fn = self._compile(self._decode_fns, key, make_spec, args,
                               kind="decode_spec", window=steps,
                               kv_len=kv_len, batch=B,
                               positions=spec + 1)
            (ids, lps, tis, tls, cnt, self._dec_tokens, self._dec_pos,
             self._dec_hist, self._dec_gstate, counts_out,
             self.cache, read, loop) = fn(*args)
            if penalized:
                self._dec_counts = counts_out
            return (ids, lps, cnt, (tis, tls) if topk else None,
                    llama.Work(experts_read=read, loop=loop))
        cache_key = (B, steps, kv_len, greedy, seeded, guided, gshape,
                     plain, penalized, topk)
        args = (self.params, self.cache, tables,
                self._dec_tokens, self._dec_pos,
                sampling, self._next_key(), guide_table,
                guide_ids, self._dec_gstate,
                counts, seen)

        def make_decode():
            logger.info("compiling decode window (batch=%d steps=%d "
                        "kv=%d greedy=%s%s%s%s)", B, steps, kv_len,
                        greedy,
                        " seeded" if seeded else "",
                        " guided" if guided else "",
                        " penalized" if penalized else "")
            return jax.jit(
                _named("decode_window", self._decode_impl, steps=steps,
                       kv_len=kv_len, greedy=greedy, seeded=seeded,
                       guided=guided, plain=plain, penalized=penalized,
                       eos_id=self._eos_id, topk=topk),
                donate_argnums=(1,))

        fn = self._compile(self._decode_fns, cache_key, make_decode,
                           args, kind="decode", window=steps,
                           kv_len=kv_len, batch=B, positions=1)
        (ids, lps, tis, tls, self._dec_tokens, self._dec_pos,
         self._dec_gstate, counts_out, self.cache, read, loop) = fn(*args)
        if penalized:
            self._dec_counts = counts_out
        return (ids, lps, None, (tis, tls) if topk else None,
                llama.Work(experts_read=read, loop=loop))

    def selects(self, kv_len: Optional[int]) -> bool:
        """Does an executable of this kv bucket select what it attends
        (models/kv.selects: the model has an indexer and the bucket
        holds more positions than it keeps)?"""
        return kv_pool.selects(kv_len, self.engine_cfg.max_blocks_per_seq,
                               self.engine_cfg.kv_block_size,
                               self.model_cfg.index_topk)

    def _attention_path(self, positions: int, mesh,
                        kv_len: Optional[int] = None) -> str:
        """ops/pallas_paged.attention_path for this model's head
        geometry, this engine's block size and, where the model selects
        what it attends, the executable's kv bucket."""
        cfg = self.model_cfg
        if cfg.mla:     # every head on the one cached vector a token
            return attention_path(
                positions, cfg.num_heads, latent_pool_width(cfg.latent_dim),
                self.engine_cfg.kv_block_size, mesh,
                value_dim=cfg.kv_lora_rank,
                selects=kv_len is not None and self.selects(kv_len),
                head_dims=(cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim))
        # (the pool's own heads: differential attention pairs them,
        # cfg.pool_kv_heads of cfg.pool_head_dim)
        return attention_path(
            positions, cfg.num_heads // cfg.pool_kv_heads,
            cfg.pool_head_dim, self.engine_cfg.kv_block_size, mesh)

    def _mixer_path(self, positions: int, steps: int = 1) -> Optional[str]:
        """The implementation the layers that keep state pages take in
        an executable of ``positions`` query positions a row, ``steps``
        of them fused (a decode window's; 1: a prefill chunk) (None:
        the model has no such layer)."""
        cfg = self.model_cfg
        if cfg.ret_layers:
            return retention.retention_path(
                positions, cfg.head_dim_, cfg.num_kv_heads, steps)
        if cfg.kind_layers("mamba2"):
            return mamba2_path(positions, cfg.mamba_d_inner,
                               cfg.mamba_heads, cfg.mamba_groups,
                               cfg.mamba_d_state)
        if cfg.kind_layers("mamba", "mamba_mem"):
            return mamba_path(positions)
        return gdn_path(positions) if cfg.gdn_layers else None

    def state_pages_moved(self, steps: int) -> int:
        """State pages a row's decode window of ``steps`` steps reads
        and writes, a layer: a read and a write a step, but where power
        retention layers take the window form (ops/retention.windowed)
        a read a step and the fold's read and write."""
        return retention.pages_moved(
            steps, bool(self.model_cfg.ret_layers)
            and retention.windowed(1, steps))

    def prefill_attention_path(self, bucket: int, kv_len: int) -> str:
        """The attention path of the prefill executables of a chunk
        bucket and kv bucket, whatever their rows (``_compile`` names
        them by the same call); of a model with no attention layer,
        its mixers' path."""
        if not self.model_cfg.attn_layers:
            return self._mixer_path(bucket)
        return self._attention_path(bucket, self.mesh, kv_len)

    def _moe_path(self, rows: int, positions: int) -> str:
        """ops/moe.moe_path for this model's experts, as a forward of
        ``rows`` x ``positions`` tokens calls them (a prefill reckons
        its capacity on max_num_seqs rows: _prefill_impl)."""
        cfg = self.model_cfg
        return moe.moe_path(
            rows, positions, cfg.router_experts_, cfg.num_experts_per_tok,
            cfg.hidden_size, cfg.moe_stored_size,
            moe.stored_dtype(self.expert_stacks()["up"]), cfg.dtype,
            self.mesh, capacity_factor=cfg.moe_capacity_factor,
            capacity_tokens=self.engine_cfg.max_num_seqs * positions,
            gated=cfg.expert_gate)

    def expert_stacks(self) -> Dict[str, Any]:
        """The routed experts' stacks by name (gate, up, down; up and
        down where the experts have no gate), every expert layer's."""
        group = self.params.get("moe_layers") or self.params["layers"]
        return {n: group[n] for n in ("gate", "up", "down") if n in group}

    @contextlib.contextmanager
    def _observed(self, kind: str, window: int, kv_len: int, batch: int):
        """Stamp the build made inside through ``compile_observer``:
        while it is open on this thread, what JAX says it traced,
        lowered, compiled or loaded is booked to it
        (efficiency.BuildEvents)."""
        obs = self.compile_observer
        t0 = time.monotonic()
        if obs is not None:
            obs.compile_started(kind, window, kv_len, batch)
        try:
            yield
        finally:
            if obs is not None:
                obs.compile_finished(kind, window, kv_len, t0,
                                     time.monotonic() - t0, batch)

    def _compile(self, cache: dict, key, make_fn, args, *, kind: str,
                 window: int, kv_len: int, batch: int, positions: int):
        """Fetch-or-build an executable. ``positions`` is its query
        positions per row (1 decode, draft+1 speculative, the chunk
        bucket for prefill): with the static config that fixes its
        attention path (``_attention_path``), which is logged once and
        kept for the ``device`` block of GET /debug/perf. The path is
        chosen by shape before compiling and never changed after: a
        kernel the compiler refuses raises, naming the executable.
        The build is an explicit lower+compile BEFORE any buffers are
        donated, so the error leaves the cache buffer alive.

        Every miss of ``cache`` (the runner's own table) is a build,
        stamped through ``compile_observer`` (kind, window, kv bucket,
        wall duration, and by its parts: traced, lowered, then compiled
        OR loaded from JAX's persistent cache, which only the
        accounting's ``cache_hit`` tells apart): builds block the
        engine loop for seconds, so they must be countable and visible
        in /debug/traces, not just log lines. A hit of the table makes
        no call into the accounting."""
        fn = cache.get(key)
        if fn is not None:
            return fn
        # a model with no attention layer has no attention path: its
        # executables stand in ``mixer_paths`` alone
        attends = bool(self.model_cfg.attn_layers)
        mixer = self._mixer_path(positions, window if kind == "decode" else 1)
        path = (self._attention_path(positions, self.mesh, kv_len)
                if attends else mixer)
        logger.info("%s executable (batch=%d window=%d kv=%d): "
                    "%s path %s", kind, batch, window, kv_len,
                    "attention" if attends else "mixer", path)
        with self._observed(kind, window, kv_len, batch):
            try:
                fn = make_fn()
                fn.lower(*args).compile()   # donation applies at execution
            except Exception as e:
                raise RuntimeError(
                    f"{kind} executable {key!r} failed to compile on the "
                    f"{path} {'attention' if attends else 'mixer'} "
                    f"path: {e}") from e
        cache[key] = fn
        name = f"{kind}|{window}|{kv_len}|{batch}"
        if attends:
            self.attention_paths[name] = path
            # (models/kv.append asks the same of the same four things
            # as it is traced: the pool's arrays, the forward's rows
            # and positions, the mesh the layers are handed)
            self.kv_appends[name] = kv_append_path(
                self.cache.carried(), batch, positions, self.mesh)
        if self.model_cfg.num_experts:
            self.moe_paths[name] = self._moe_path(batch, positions)
        if self.model_cfg.state_layers:
            self.mixer_paths[name] = mixer
        return fn

    def prefill(self, tokens, starts, lengths, sampling: SamplingParams,
                kv_len: int, guide_table=None, guide_ids=None,
                guide_states=None, penalized: bool = False,
                topk: int = 0, slots=None, finishing: bool = True):
        """Chunk prefill of ``tokens.shape[0]`` rows (see
        _prefill_impl; ``finishing``: some row's prompt ends in this
        chunk, which only a model whose plan has two depths reads).
        tokens [R, Tb] int32 np; starts/lengths [R];
        slots [R] the slot each row serves (None: rows are slots
        0..R-1, which at R == max_num_seqs is the full-batch dispatch).
        sampling, guide_ids and guide_states stay per slot
        ([max_num_seqs]), as the engine keeps them. Returns device
        (ids, logprobs, tops) — ids/logprobs [R], by row; tops None
        unless topk > 0, then ([R, K] ids, [R, K] logprobs)
        alternatives — and the experts' counts (a device int32 [3]:
        the rows they multiplied, the assignments kept, the rounds;
        ``_prefill_impl``; None on a dense model).

        Prefill executables compile lazily per (rows, chunk, kv
        bucket), each on the attention path its shape selects
        (_compile). A shape (chunk bucket, kv bucket, variant) first
        built at several rows is built at one row in the same call (a
        parked dispatch): one chunk due is the steady case of every
        shape (cfg.prefill_rows_for), and whoever warms a shape at
        max_num_seqs rows (warmup(), a benchmark's launcher) has then
        warmed what serving runs, and no row count compiles mid-serving.
        """
        R, Tb = tokens.shape
        guided = guide_table is not None
        B = self.engine_cfg.max_num_seqs
        if R > 1 and self.engine_cfg.prefill_rows_for(R, Tb) == 1:
            # more rows than a dispatch of this chunk bucket takes
            # (cfg.FULL_BATCH_CHUNK_TOKENS; the engine never asks, a
            # launcher that warms max_num_seqs rows of every shape
            # does): served a row at a time, by the one-row executable
            if slots is None:
                slots = np.arange(R, dtype=np.int32)
            outs = [self.prefill(
                tokens[r:r + 1], starts[r:r + 1], lengths[r:r + 1],
                sampling, kv_len, guide_table=guide_table,
                guide_ids=guide_ids, guide_states=guide_states,
                penalized=penalized, topk=topk, slots=slots[r:r + 1],
                finishing=finishing)
                for r in range(R)]
            ids, lps, tops, rows = zip(*outs)
            return (jnp.concatenate(ids), jnp.concatenate(lps),
                    tuple(map(jnp.concatenate, zip(*tops))) if topk
                    else None,
                    None if rows[0] is None else sum(rows))
        gshape = guide_table.shape if guided else None
        key = (R, Tb, kv_len, guided, gshape, penalized, topk)
        if R > 1 and key not in self._prefill_fns:
            S = self.engine_cfg.max_model_len
            self.prefill(np.zeros((1, Tb), np.int32),
                         np.full((1,), S, np.int32),
                         np.ones((1,), np.int32), sampling, kv_len,
                         guide_table=guide_table, guide_ids=guide_ids,
                         guide_states=guide_states, penalized=penalized,
                         topk=topk)
        if slots is None:
            slots = np.arange(R, dtype=np.int32)
        if not guided:
            guide_table = jnp.zeros((1, 1, 1), jnp.int32)
            guide_ids = np.zeros((B,), np.int32)
            guide_states = np.zeros((B,), np.int32)
        if penalized:
            counts, seen = self._dec_counts, self._dec_prompt_seen
        else:
            counts = jnp.zeros((B, 1), jnp.int32)
            seen = jnp.zeros((B, 1), bool)
        args = (self.params, self.cache, self._dev_tables(),
                jnp.asarray(slots, jnp.int32),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(starts, jnp.int32),
                jnp.asarray(lengths, jnp.int32), sampling, self._next_key(),
                guide_table, jnp.asarray(guide_ids, jnp.int32),
                jnp.asarray(guide_states, jnp.int32), counts, seen,
                jnp.asarray(finishing, bool))

        def make_prefill():
            logger.info("compiling prefill (rows=%d chunk=%d kv=%d%s%s)",
                        R, Tb, kv_len, " guided" if guided else "",
                        " penalized" if penalized else "")
            return jax.jit(_named("prefill_chunk", self._prefill_impl,
                                  kv_len=kv_len, guided=guided,
                                  penalized=penalized,
                                  eos_id=self._eos_id, topk=topk),
                           donate_argnums=(1,))

        fn = self._compile(
            self._prefill_fns, key,
            make_prefill, args, kind="prefill", window=Tb,
            kv_len=kv_len, batch=R, positions=Tb)
        ids, lps, tis, tls, self.cache, expert_rows = fn(*args)
        return ids, lps, (tis, tls) if topk else None, expert_rows

    def embed(self, tokens, lengths):
        """Mean-pooled final hidden states for padded prompts.

        tokens [N, Tb] int32 np (right-padded), lengths [N] -> fp32
        [N, H]. Powers /v1/embeddings (and rerank/score built on it);
        no KV cache involved, nothing donated, safe to dispatch from the
        server thread next to the engine loop.
        """
        N, Tb = tokens.shape
        fn = self._embed_fns.get((N, Tb))
        if fn is None:
            logger.info("compiling embed (batch=%d len=%d)", N, Tb)

            def embed(params, toks, lens):
                mask = (jnp.arange(Tb)[None, :] < lens[:, None])
                h = llama.encode(params, self.model_cfg, toks,
                                 rope=self.rope, token_valid=mask)
                pooled = jnp.sum(
                    h.astype(jnp.float32) * mask[:, :, None], axis=1)
                return pooled / jnp.maximum(lens, 1)[:, None]

            fn = self._embed_fns[(N, Tb)] = jax.jit(embed)
        return fn(self.params, jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(lengths, jnp.int32))

    def prompt_logprobs(self, tokens):
        """Teacher-forced logprobs of a prompt batch.

        tokens [N, T] int32 np -> fp32 [N, Tb-1] where Tb is T padded
        to a power-of-two bucket (bounded compile count; callers slice
        their row to [:len-1] — entry t is log p(tokens[t+1] |
        tokens[:t+1]) under the raw model distribution, position 0 has
        none, and entries past a row's real length are padding
        garbage). The LM head runs in 256-token chunks so only a
        [N, 256, vocab] fp32 slab materializes — an 8k echo prompt on a
        150k vocab would otherwise spike ~5 GB of HBM. Like embed(),
        cache-free and nothing donated: safe to dispatch from the
        server thread next to the engine loop."""
        N, T = tokens.shape
        Tb = max(16, 1 << (T - 1).bit_length())
        Tb = min(Tb, self.engine_cfg.max_model_len)
        if Tb < T:
            raise ValueError(f"prompt length {T} exceeds max_model_len")
        pad = np.zeros((N, Tb), np.int32)
        pad[:, :T] = tokens
        fn = self._prompt_lp_fns.get((N, Tb))
        if fn is None:
            logger.info("compiling prompt-logprobs (batch=%d len=%d)",
                        N, Tb)
            C = min(256, Tb)
            n_chunks = -(-(Tb - 1) // C)

            def prompt_logprobs(params, toks):
                h = llama.encode(params, self.model_cfg, toks,
                                 rope=self.rope)
                hh = h[:, :-1]
                tg = toks[:, 1:]
                padded = n_chunks * C
                hh = jnp.pad(hh, ((0, 0), (0, padded - (Tb - 1)),
                                  (0, 0)))
                tg = jnp.pad(tg, ((0, 0), (0, padded - (Tb - 1))))
                hh = hh.reshape(N, n_chunks, C, -1).transpose(1, 0, 2, 3)
                tg = tg.reshape(N, n_chunks, C).transpose(1, 0, 2)

                def body(_, xs):
                    hc, tc = xs
                    logits = llama._lm_head(params, self.model_cfg, hc)
                    lse = jax.nn.logsumexp(logits, axis=-1)
                    tgt = jnp.take_along_axis(
                        logits, tc[..., None], axis=-1)[..., 0]
                    return None, tgt - lse

                _, lps = jax.lax.scan(body, None, (hh, tg))
                return lps.transpose(1, 0, 2).reshape(N, -1)[:, :Tb - 1]

            fn = self._prompt_lp_fns[(N, Tb)] = jax.jit(
                prompt_logprobs)
        return fn(self.params, jnp.asarray(pad, jnp.int32))

    def _slot_block_offsets(self, tables, slot, start, size: int):
        """(block ids [size], intra-block offsets [size]) for a slot's
        virtual positions start..start+size-1 (through its table row)."""
        Bs = self.engine_cfg.kv_block_size
        MB = self.engine_cfg.max_blocks_per_seq
        pos = start + jnp.arange(size)
        row = jnp.take(tables, slot, axis=0)                  # [MB]
        blk = jnp.take(row, jnp.clip(pos // Bs, 0, MB - 1))   # [size]
        return blk, pos % Bs

    def _refuse_latent(self, what: str) -> None:
        """KV chunks on the wire are K and V per kv head
        [L, size, Hkv, D]; the latent pool holds neither, and a
        sequence's state pages are no part of them."""
        if self.cache.layout != KV_HEADS:
            raise ValueError(
                f"{what}: the KV pool of {self.model_cfg.name} has the "
                f"layout {self.cache.layout!r} (one [c | k_rope] vector "
                f"a token, or state pages beside K and V), which KV "
                f"chunks [L, size, Hkv, D] cannot carry")

    def extract_chunk(self, slot: int, start: int, size: int):
        """Gather [L, size, Hkv, D] k/v out of a slot's blocks (no
        donation; the result is an independent buffer, safe to D2H after
        later steps donate the cache). Dispatch is async —
        np.asarray() later blocks."""
        self._refuse_latent("extract_chunk")
        fn = self._extract_fns.get(size)
        if fn is None:
            def kv_extract(cache: KVCache, tables, slot, start):
                blk, off = self._slot_block_offsets(tables, slot, start,
                                                    size)
                # advanced indices (block, offset) put [size] first:
                # [size, L, Hkv, D] -> chunk layout [L, size, Hkv, D]
                k = cache.k[:, blk, :, off, :].transpose(1, 0, 2, 3)
                v = cache.v[:, blk, :, off, :].transpose(1, 0, 2, 3)
                if cache.quantized:
                    # tiers store full-precision chunks (portable across
                    # kv_dtype configs of the same fingerprint
                    # namespace). Multiply in f32 — the same precision
                    # the attention kernels dequantize at — THEN round
                    # to the bf16 wire dtype
                    ks = cache.ks[:, blk, :, off].transpose(1, 0, 2)
                    vs = cache.vs[:, blk, :, off].transpose(1, 0, 2)
                    k = (k.astype(jnp.float32)
                         * ks[..., None]).astype(jnp.bfloat16)
                    v = (v.astype(jnp.float32)
                         * vs[..., None]).astype(jnp.bfloat16)
                return k, v

            fn = self._extract_fns[size] = jax.jit(kv_extract)
        return fn(self.cache, self._dev_tables(), jnp.int32(slot),
                  jnp.int32(start))

    def inject_chunk(self, slot: int, start: int, k_chunk, v_chunk) -> None:
        """Scatter host [L, size, Hkv, D] k/v into a slot's blocks
        (donates cache — in-place HBM update). The slot's table must
        already cover start+size positions (admission allocates the
        full prompt's blocks before tier injection runs)."""
        self._refuse_latent("inject_chunk")
        size = k_chunk.shape[1]
        fn = self._inject_fns.get(size)
        if fn is None:
            def kv_inject(cache: KVCache, tables, k_chunk, v_chunk, slot,
                          start):
                blk, off = self._slot_block_offsets(tables, slot, start,
                                                    size)
                if cache.quantized:
                    # tier chunks are full precision; re-quantize on the
                    # way in ([L, size, Hkv, D] vectors, same recipe as
                    # serving writes — models/kv.quantize_chunk)
                    from production_stack_tpu.models.kv import (
                        quantize_chunk)
                    kq, ksc = quantize_chunk(k_chunk)
                    vq, vsc = quantize_chunk(v_chunk)
                    k = cache.k.at[:, blk, :, off, :].set(
                        kq.transpose(1, 0, 2, 3))
                    v = cache.v.at[:, blk, :, off, :].set(
                        vq.transpose(1, 0, 2, 3))
                    ks = cache.ks.at[:, blk, :, off].set(
                        ksc.transpose(1, 0, 2))
                    vs = cache.vs.at[:, blk, :, off].set(
                        vsc.transpose(1, 0, 2))
                    return KVCache(k, v, ks, vs)
                kc = k_chunk.astype(cache.k.dtype).transpose(1, 0, 2, 3)
                vc = v_chunk.astype(cache.v.dtype).transpose(1, 0, 2, 3)
                k = cache.k.at[:, blk, :, off, :].set(kc)
                v = cache.v.at[:, blk, :, off, :].set(vc)
                return KVCache(k, v)

            fn = self._inject_fns[size] = jax.jit(kv_inject,
                                                  donate_argnums=(0,))
        self.cache = fn(self.cache, self._dev_tables(), jnp.asarray(k_chunk),
                        jnp.asarray(v_chunk), jnp.int32(slot),
                        jnp.int32(start))

    def warmup(self) -> float:
        """Compile the hot executables at the smallest kv bucket:
        with ``window_adapt`` on, the FULL (batch bucket x window
        bucket) grid for the greedy and plain-sampled variants — the
        adaptive dispatch walks that grid in steady state, and a
        combination left cold here is a multi-second compile stall
        mid-serving (the effwatch zero-steady-state-compiles gate
        pins this) — plus the full-sort sampled variant and the
        speculative executable at the full shape only. With adaptation
        off, just the three variants at (max_num_seqs, decode_window).
        Every prefill bucket compiles at its minimal kv bucket, at
        max_num_seqs rows (a burst) and, with it, at one row (one
        chunk due, the steady case: prefill() builds the two
        together), the only row counts the engine dispatches
        (cfg.prefill_rows_for). Larger kv buckets and rarely-hit
        variants (guided/penalized/topk, adapted sampled-sort shapes)
        compile lazily on first use (one-time, logged). Returns
        seconds spent."""
        import numpy as np
        t0 = time.time()
        cfg = self.engine_cfg
        B = cfg.max_num_seqs
        S = cfg.max_model_len
        kv0 = cfg.kv_len_buckets[0]
        sampling = SamplingParams.filled(B)

        def park(b: int, history: bool = False) -> None:
            # park every row at S: warmup writes only clamp onto S-1
            self.set_decode_state(
                np.zeros((b,), np.int32), np.full((b,), S, np.int32),
                history=np.zeros((b, S), np.int32) if history else None)

        if cfg.speculative_ngram_tokens:
            # spec-enabled greedy windows use the speculative executable,
            # not the plain greedy one — compile the real hot path
            park(B, history=True)
            self.decode(sampling, steps=cfg.decode_window,
                        kv_len=kv0, greedy=True,
                        spec=cfg.speculative_ngram_tokens,
                        spec_ok=np.ones((B,), bool))
        batches = cfg.decode_batch_buckets if cfg.window_adapt else (B,)
        windows = (cfg.decode_window_buckets if cfg.window_adapt
                   else (cfg.decode_window,))
        for b in batches:
            for w in windows:
                park(b)
                self.decode(sampling, steps=w, kv_len=kv0, greedy=True)
                # the API default (temperature=1, top_p=1, top_k=0)
                # runs the sort-free plain variant — warm it across
                # the grid too so default-sampling storms never pay a
                # mid-serving compile either
                park(b)
                self.decode(sampling, steps=w, kv_len=kv0,
                            greedy=False, plain=True)
        # truncated sampling (top_p<1 / top_k / min_p) runs the
        # full-sort executable: warm the full shape only (adapted
        # shapes compile lazily — the sort dominates its cost anyway)
        park(B)
        self.decode(sampling, steps=cfg.decode_window, kv_len=kv0,
                    greedy=False)
        for bucket in cfg.prefill_buckets:
            self.prefill(np.zeros((B, bucket), np.int32),
                         np.full((B,), S, np.int32),
                         np.ones((B,), np.int32), sampling,
                         cfg.kv_bucket_for(bucket))
        jax.block_until_ready(self.cache)
        dt = time.time() - t0
        logger.info(
            "warmup compiled decode grid (batch %s x window %s, kv %d) "
            "+ %d prefill buckets (rows 1 and %d) in %.1fs",
            list(batches), list(windows), kv0,
            len(cfg.prefill_buckets), B, dt)
        return dt
