"""Paged KV cache: a global block pool + per-slot block tables.

Layout: ``k, v [L, N, Hkv, Bs, D]`` — N fixed-size blocks of Bs token
positions each, shared by every sequence, with blocks stored
HEAD-MAJOR: a (block, kv-head) panel is a contiguous ``[Bs, D]`` tile,
the shape both the XLA gather path and the Pallas paged-attention
kernel (ops/pallas_paged.py) want as their minor dims on TPU. A
sequence owns an ordered list of blocks; its *block table* row maps
virtual position ``p`` to pool location ``(table[p // Bs], p % Bs)``.
HBM is sized by ``EngineConfig.kv_pool_tokens``, not
``max_num_seqs × max_model_len``: batch capacity scales with *live*
context, and prefix caching is block *sharing* (refcounts in
engine/block_manager.py) instead of copies.

TPU-first invariants:
- Static shapes everywhere: the pool, the tables [B, MB], and the
  attention view are all fixed-size; block allocation is pure host
  bookkeeping and never recompiles anything.
- **Block 0 is the trash block.** It is never allocated; writes from
  parked rows, padding tokens, and beyond-capacity window tails are
  routed to it via the ``valid`` mask. Invalid writes all land in a
  block no table references.
- **The pool is carried, never stacked.** A step program takes the
  whole ``[L, N, Hkv, Bs, D]`` pool as a donated argument and gives it
  back as its result, and in between it is ONE buffer: the layer loop
  (``models/llama.forward``) carries it next to the hidden state, each
  layer appends its chunk in place (``append_chunk(..., layer=i)``)
  and the kernels stream blocks from ``pool[layer]`` through their
  index maps. No layer's pool is ever sliced out or stacked back: a
  scan's ``xs`` cannot alias its ``ys``, and handing the pool through
  them cost a read and a write of a layer's pool per layer and of the
  whole pool per step — half of a decode step on the chip (PERF.md,
  PR 25).
- **Appends are rows or whole blocks, by what the call observes**
  (``append``; ops/pallas_paged.kv_append_path; GET /debug/perf
  ``device.kv_appends`` names the outcome per executable). A token's
  K/V is ``Hkv`` rows of ``D``, one in each head's panel of its block.
  Scattered token by token (``pool.at[layer, blk, :, off].set``), the
  TPU compiler wants the pool token-major ([.., Bs, Hkv, D]) for the
  scatter and head-major for the kernel, and copies the whole pool
  between the two layouts around every layer's write. So:
  BLOCKS, ``append_chunk``: gather the few blocks a chunk touches,
  merge the new rows into them and scatter whole ``[Hkv, Bs, D]``
  blocks back; every pool-shaped operation keeps the pool's own layout
  and updates it in place. The grain of a PREFILL chunk, which fills
  most of the blocks it rewrites; of the int8 pool, whose scale rows
  ``[.., Bs]`` are no tile a kernel's copy can address; of a mesh,
  where the pool is sharded and XLA places the rewrite; and of the CPU,
  where the kernels are off.
  ROWS, ops/pallas_paged.append_rows: a decode or speculative window
  (at most ``DECODE_T_MAX`` positions a row) on one device, the
  kernels on, a pool without scales, no more rows than one call holds
  slabs in flight (128 at one position, past every cell's 16; beyond
  it the blocks, which compile at any batch). One kernel call a layer
  for K and V (or the latents, with the index keys beside them) that
  holds the pools in place and moves, a row, the one tile of the block
  its token lands in (8 rows, every kv head). The block rewrite there
  cost three or four XLA operations a pool and layer, each with
  its launch, and a whole block in and out for one row: 67 us a layer
  application at Ouro's 16 rows x 16 heads, 12.8 ms of a 44 ms decode
  step (PERF.md, PR 58). No cell can say the int8 pool or a mesh would
  gain, so they keep the blocks.
  The same bytes either way on every block a table references
  (``write_chunk`` is the contract, tests/test_pallas_paged.py).
- **A layer sees two calls.** ``append`` writes its chunk and
  ``attend`` reads it back under the queries; whether the pool is
  quantized, which implementation reads it and that a kernel exists
  are this module's business and ops/pallas_paged.attention_path's,
  not the model's (models/llama._layer_body).
- Reads go through the Pallas paged kernel (blocks streamed straight
  from the pool through scalar-prefetched tables — each KV byte read
  once) or, on backends/meshes the kernel does not cover, a *gathered
  view* (``gather_view``): the first ``nb`` table entries pull
  [B, nb*Bs, Hkv, D] out of the pool for the position-masked jnp
  attention (ops/attention.py). View index s IS virtual position s,
  so the causal position mask also hides any stale/garbage block
  contents: a query at position p only attends s <= p, and every
  position <= p of a live row has been written by construction.
- Sharding: heads over tp, block axis over dp
  (parallel/sharding.py cache_pspec). Under a tp-only serving mesh
  both the kernel (shard_map over the head axis) and the gather
  (indices replicated, gathered axis unsharded) are shard-local: no
  extra collectives.

- **The latent pool** (latent attention, MLA: GLM-4.7-Flash). Per
  token and layer ONE vector ``[c | k_rope]`` of ``kv_lora_rank +
  qk_rope_head_dim`` values, after the inner RMSNorm and the rotation,
  shared by every query head, zero-padded to whole lanes of 128
  (``latent_pool_width``): ``k [L, N, 1, Bs, W]`` and no ``v`` at
  all — the values are the first ``kv_lora_rank`` columns of the keys'
  own block, which the kernels slice out of the block they already
  hold, so a decode step reads each live token's W values once. The
  same tables, trash block, carried buffer and appends; a
  pool of ONE array is the latent pool (``KVCache.layout``). What
  assumes K and V per head (the int8 pool, tp meshes, the KV
  connector's chunks) refuses it by name.

- **The index pool** (learned sparse attention over the latent pool:
  GLM-5, ops/dsa.py). A second kind of per-token state under the SAME
  block tables: per token and layer one index key of
  ``index_head_dim`` values, ``idx [L, N, 1, Bs, Di]``, beside the
  latents (``KVCache.layout`` "latent+index"). A block id names a block
  of both pools, so admission, preemption, reuse and prefix sharing
  count one block and move both; the step programs carry both buffers
  and append to both in place (``append_chunk``). ``attend_selected``
  reads it: a query scores every live position against the index keys,
  keeps the ``index_topk`` best and attends those positions' latents
  alone.

- **State pages** (Gated DeltaNet layers: Qwen3-Next, ops/gdn.py). A
  second KIND of cache: state a SEQUENCE, not a token. Such a layer
  keeps, a sequence, ``gdn_value_heads`` float32 matrices ``[Dk, Dv]``
  and the last ``gdn_conv - 1`` inputs of its convolution, whatever
  the context: ``state [Lg, P, Hv, Dk, Dv]`` float32 and ``conv [Lg,
  P, taps - 1, Ch]`` in the pool's dtype, beside the K/V pool of the
  model's attention layers (``KVCache.layout`` "kv+state"), P =
  ``max_num_seqs + 1`` pages. engine/block_manager.py hands a sequence
  ONE page at admission and takes it back with its blocks; the page's
  id rides as one more column of the sequence's block-table row
  (``split_tables``), so a slot move rewrites a row and nothing is
  copied. **Page 0 is the trash page**, as block 0 is the trash block:
  never allocated, named by every empty table row, written by rows
  that are not real. The same carried buffers: a step program's layer
  loop carries ``(state, conv)`` next to the K/V arrays
  (``state_carried``) and each layer updates its rows' pages in place.
  A chunk whose first position is 0 starts from a zero state inside
  the layer: no page is ever cleared.

- **State pages alone** (power retention layers: Brumby,
  ops/retention.py). A model whose EVERY mixer keeps state a sequence
  has no K/V pool at all: ``KVCache.k`` is None, ``layout`` "state",
  ``bytes_per_token`` 0. A layer keeps, a sequence and key-value head,
  a float32 ``S`` over the ``F = D (D + 1) / 2`` monomials of the key's
  symmetric square and the normaliser ``z``: ``state [L, P, Hkv, F,
  D]`` and ``norm [L, P, F Hkv / D, D]`` (the layout inside a page is
  ops/retention.py's), 34 MB a layer and sequence at 8 heads of 128,
  whatever the context. The page IS the sequence's one block: the
  engine runs such a model at a block of ``max_model_len`` tokens, so
  a table row is one column, the page's id, and the block manager's
  counts (free, active, admission, preemption) are counts of pages;
  page 0 is the trash block and the trash page at once.

The reference stack's KV management is configuration around LMCache env
vars (reference: helm/templates/deployment-vllm-multi.yaml:154-178) and
its engine's paged KV lives inside vLLM (the stack passes
--enable-prefix-caching, deployment-vllm-multi.yaml:73-75); this module
is the TPU-native equivalent of that engine layer.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.ops import dsa, pallas_paged
from production_stack_tpu.ops.attention import attention_with_cache


LATENT = "latent"        # KVCache.layout: [c | k_rope], no v
LATENT_INDEX = "latent+index"   # and the indexer's key in its own pool
KV_HEADS = "kv_heads"     # separate K and V per kv head
KV_STATE = "kv+state"     # and a state page a sequence beside them
STATE = "state"           # state pages alone: no K, no V


class KVCache(NamedTuple):
    # [L, N, Hkv, Bs, D]; latent pool [L, N, 1, Bs, W]; None where
    # state pages are the model's only cache
    k: Optional[jnp.ndarray] = None
    v: Optional[jnp.ndarray] = None  # [L, N, Hkv, Bs, D]; latent: None
    # int8 KV mode only: symmetric per-(token, head) dequant scales
    # (models/quant.py recipe applied to the cache): value = int8 *
    # scale. None = full-precision cache.
    ks: Optional[jnp.ndarray] = None  # [L, N, Hkv, Bs] f32
    vs: Optional[jnp.ndarray] = None
    # learned sparse attention only: the index pool [L, N, 1, Bs, Di]
    # beside the latent pool, under the same block tables
    idx: Optional[jnp.ndarray] = None
    # Gated DeltaNet layers only: the state pages [Lg, P, Hv, Dk, Dv]
    # float32 and the convolutions' inputs [Lg, P, taps - 1, Ch]
    state: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    # power retention layers only: the state pages [L, P, Hkv, F, D]
    # float32 are ``state``, and their normalisers [L, P, F Hkv / D, D]
    norm: Optional[jnp.ndarray] = None

    @property
    def num_blocks(self) -> int:
        """Blocks of the pool, the trash block among them; of a model
        with state pages alone, its pages (a page is its block)."""
        return self.state_pages if self.k is None else self.k.shape[1]

    @property
    def block_size(self) -> int:
        """Tokens a block holds (0: state pages alone, any number)."""
        return 0 if self.k is None else self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.ks is not None

    @property
    def layout(self) -> str:
        if self.idx is not None:
            return LATENT_INDEX
        if self.k is None:
            return STATE
        if self.state is not None:
            return KV_STATE
        return LATENT if self.v is None else KV_HEADS

    @property
    def bytes_per_token(self) -> int:
        """Bytes one token takes in the pools, all layers, as allocated
        (payload and, int8, scales; the index pool's keys too; not the
        state pages, which a sequence takes whatever its tokens)."""
        if self.k is None:
            return 0
        tokens = self.num_blocks * self.block_size
        return sum(a.dtype.itemsize * (a.size // tokens)
                   for a in self.carried())

    @property
    def state_pages(self) -> int:
        """Pages of the state pool, the trash page 0 among them (0: the
        model keeps no state a sequence)."""
        return 0 if self.state is None else self.state.shape[1]

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes one sequence's state page takes, all layers."""
        return sum(a.dtype.itemsize * (a.size // a.shape[1])
                   for a in self.state_carried())

    @property
    def index_bytes_per_token(self) -> int:
        """bytes_per_token's part that is the index pool's (0: none)."""
        if self.idx is None:
            return 0
        return self.idx.dtype.itemsize * (
            self.idx.size // (self.num_blocks * self.block_size))

    def _per_token(self):
        """(name, array) of the fields that hold state a TOKEN."""
        return [(n, getattr(self, n)) for n in ("k", "v", "ks", "vs", "idx")]

    def carried(self) -> "Pool":
        """The per-token arrays a step program's layer loop carries:
        those that are there, in the fields' order."""
        return tuple(a for _, a in self._per_token() if a is not None)

    def _per_sequence(self):
        """(name, array) of the fields that hold state a SEQUENCE."""
        return [(n, getattr(self, n)) for n in ("state", "conv", "norm")]

    def state_carried(self) -> "Pool":
        """The state pages the loop carries beside them: (state, conv)
        of Gated DeltaNet layers, (state, norm) of power retention
        layers, or () where the model has no such layer."""
        return tuple(a for _, a in self._per_sequence() if a is not None)

    def carried_back(self, pool: "Pool", state: "Pool" = ()) -> "KVCache":
        """``carried`` and ``state_carried`` undone: the same fields,
        the loop's arrays."""
        names = [n for n, a in self._per_token() if a is not None]
        kinds = [n for n, a in self._per_sequence() if a is not None]
        return KVCache(**dict(zip(names, pool)), **dict(zip(kinds, state)))


# the pool as a step program's layer loop carries it: a KVCache's
# arrays without the Nones — (k, v), int8 (k, v, ks, vs), the latent
# pool's one array (k,), or the latent and the index pool (k, idx)
Pool = Tuple[jnp.ndarray, ...]


def make_cache(num_layers: int, num_blocks: int, block_size: int,
               num_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> KVCache:
    """Block pool. num_blocks INCLUDES the reserved trash block 0.

    dtype jnp.int8 allocates the quantized pool: int8 payload plus
    per-(token, head) fp32 scales — halving decode's KV HBM traffic
    (the dominant long-context cost) for ~0.4% the scale overhead
    (4 bytes per D=64..128 values)."""
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    if dtype == jnp.int8:
        sshape = shape[:-1]
        return KVCache(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       ks=jnp.zeros(sshape, jnp.float32),
                       vs=jnp.zeros(sshape, jnp.float32))
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def latent_pool_width(latent_dim: int) -> int:
    """The latent pool's minor dimension: latent_dim padded up to whole
    lanes of 128. The TPU lays an array's minor dimension out in tiles
    of 128 anyway (GLM-4.7-Flash's 576 values take 640 in HBM), and a
    kernel's copy out of HBM must cover whole tiles: the padding is
    allocated either way, so it is made explicit, zero, and counted
    (KVCache.bytes_per_token)."""
    return -(-latent_dim // 128) * 128


def make_latent_cache(num_layers: int, num_blocks: int, block_size: int,
                      latent_dim: int, dtype=jnp.bfloat16) -> KVCache:
    """The latent pool [L, N, 1, Bs, W], W = latent_pool_width(
    latent_dim) (module text). num_blocks includes the trash block. No
    quantized form: the int8 pool's scales are per (token, kv head)
    over K and over V."""
    if dtype == jnp.int8:
        raise ValueError(
            "the latent KV pool (layout 'latent': one [c | k_rope] "
            "vector a token, MLA) has no int8 form; use "
            "--kv-cache-dtype bfloat16 or float32")
    return KVCache(k=jnp.zeros(
        (num_layers, num_blocks, 1, block_size,
         latent_pool_width(latent_dim)), dtype))


def cache_for(cfg, num_blocks: int, block_size: int,
              dtype=jnp.bfloat16, state_pages: int = 0) -> KVCache:
    """The pool a model's layers append to and attend over
    (cfg: models/config.ModelConfig): the latent pool for latent
    attention (with the index pool beside it where the model selects
    what it attends), K and V per kv head for everything else (a pool
    layer a layer, and of a looped model a layer and pass): of the
    model's ATTENTION layers, with ``state_pages`` state pages (the
    trash page 0 among them) beside them where it has Gated DeltaNet
    layers; state pages ALONE, ``num_blocks`` of them, where every
    layer is a power retention layer (module text)."""
    if cfg.ret_layers:
        if cfg.attn_layers or cfg.gdn_layers:
            raise ValueError(
                f"{cfg.name}: power retention layers beside another "
                f"kind of mixer are not built (layer_pattern "
                f"{cfg.layer_pattern})")
        if num_blocks < 2:
            raise ValueError("a state pool needs the trash page and at "
                             "least one more")
        D, F, H = cfg.head_dim_, cfg.ret_features, cfg.num_kv_heads
        return KVCache(
            state=jnp.zeros((cfg.ret_layers, num_blocks, H, F, D),
                            jnp.float32),
            norm=jnp.zeros((cfg.ret_layers, num_blocks, F * H // D, D),
                           jnp.float32))
    if cfg.gdn_layers or cfg.mamba_layers:
        if dtype == jnp.int8:
            raise ValueError(
                f"{cfg.name}: a model with state pages (layout "
                f"'kv+state') has no int8 KV pool; use --kv-cache-dtype "
                f"bfloat16 or float32")
        if state_pages < 2:
            raise ValueError("a state pool needs the trash page and at "
                             "least one more (state_pages >= 2)")
        # the layers that OWN K and V alone: a cross layer reads
        # another layer's pool layer (cfg.reader_layers read these)
        kv = make_cache(cfg.attn_layers, num_blocks, block_size,
                        cfg.pool_kv_heads, cfg.pool_head_dim, dtype)
        if cfg.mamba_layers:
            return kv._replace(
                state=jnp.zeros((cfg.mamba_layers, state_pages,
                                 cfg.mamba_d_state, cfg.mamba_d_inner),
                                jnp.float32),
                conv=jnp.zeros((cfg.mamba_layers, state_pages,
                                cfg.mamba_d_conv - 1,
                                cfg.mamba_conv_channels), dtype))
        return kv._replace(
            state=jnp.zeros((cfg.gdn_layers, state_pages,
                             cfg.gdn_value_heads, cfg.gdn_key_dim,
                             cfg.gdn_value_dim), jnp.float32),
            conv=jnp.zeros((cfg.gdn_layers, state_pages,
                            cfg.gdn_conv - 1, cfg.gdn_channels), dtype))
    if cfg.mla:
        cache = make_latent_cache(cfg.num_layers, num_blocks, block_size,
                                  cfg.latent_dim, dtype)
        if cfg.index_topk:
            cache = cache._replace(idx=jnp.zeros(
                (cfg.num_layers, num_blocks, 1, block_size,
                 cfg.index_head_dim), dtype))
        return cache
    # (a looped model keeps K and V a layer and PASS: cfg.pool_layers
    # = num_layers x loop_steps, pass t's from t x num_layers on)
    return make_cache(cfg.pool_layers, num_blocks, block_size,
                      cfg.num_kv_heads, cfg.head_dim_, dtype)


def split_tables(tables: jnp.ndarray, has_state: bool):
    """(block tables [B, MB], state page ids [B] or None) of the table
    rows as the engine keeps them: where the model has state pages, a
    row's last column is the sequence's page (0, the trash page, on an
    empty row)."""
    if not has_state:
        return tables, None
    return tables[:, :-1], tables[:, -1]


def linear_tables(num_slots: int, max_len: int,
                  block_size: int) -> jnp.ndarray:
    """Identity block tables [B, MB]: slot b owns blocks
    1 + b*MB .. 1 + (b+1)*MB - 1 (block 0 stays trash). With a pool of
    num_slots*MB + 1 blocks this reproduces the contiguous per-slot
    cache — the simple configuration for tests and single-sequence
    use (models/__init__.make_slot_cache)."""
    mb = -(-max_len // block_size)
    return (1 + jnp.arange(num_slots * mb, dtype=jnp.int32)
            ).reshape(num_slots, mb)


def make_slot_cache(num_layers: int, num_slots: int, max_len: int,
                    num_kv_heads: int, head_dim: int,
                    dtype=jnp.bfloat16, block_size: int = 64,
                    ) -> Tuple[KVCache, jnp.ndarray]:
    """(pool, tables) equivalent to the old per-slot contiguous cache."""
    block_size = min(block_size, max(8, max_len))
    mb = -(-max_len // block_size)
    cache = make_cache(num_layers, num_slots * mb + 1, block_size,
                       num_kv_heads, head_dim, dtype)
    return cache, linear_tables(num_slots, max_len, block_size)


def _chunk_addresses(tables: jnp.ndarray, positions: jnp.ndarray,
                     block_size: int,
                     valid: Optional[jnp.ndarray],
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(flat block ids, flat intra-block offsets) for a [B, T] chunk of
    virtual positions — the ONE addressing contract every pool writer
    shares: tables map position//Bs to a block; tokens that are invalid,
    negative, or beyond the virtual capacity MB*Bs route to trash
    block 0 (collisions there are irrelevant by construction)."""
    Bs = block_size
    MB = tables.shape[1]
    bi = jnp.clip(positions // Bs, 0, MB - 1)
    blk = jnp.take_along_axis(tables, bi, axis=1)           # [B, T]
    off = positions % Bs
    oob = (positions < 0) | (positions >= MB * Bs)
    if valid is not None:
        oob = oob | ~valid
    blk = jnp.where(oob, 0, blk)                            # block 0
    return blk.reshape(-1), off.reshape(-1)


def write_chunk(cache_layer: jnp.ndarray, new: jnp.ndarray,
                tables: jnp.ndarray, positions: jnp.ndarray,
                valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Scatter new [B,T,Hkv,D] into the pool layer [N,Hkv,Bs,D].

    positions [B,T] are virtual positions; tables [B,MB] map them to
    blocks. Tokens with valid == False (padding, parked rows, window
    tails past capacity) are routed to trash block 0. Callers on the
    serving path MUST pass valid; None (tests, single-sequence loops)
    treats every in-range token as real, which is only safe when
    positions never exceed the virtual capacity MB*Bs.
    """
    new = new.astype(cache_layer.dtype)
    B, T = positions.shape
    blk, off = _chunk_addresses(tables, positions, cache_layer.shape[2],
                                valid)
    # advanced indices on the block and offset axes land the [Hkv, D]
    # slab of every token at its (block, head-major row) home
    return cache_layer.at[blk, :, off, :].set(
        new.reshape((B * T,) + new.shape[2:]))


def _span_addresses(tables: jnp.ndarray, starts: jnp.ndarray, T: int,
                    block_size: int, valid: Optional[jnp.ndarray],
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """_chunk_addresses by block, for rows of T CONTIGUOUS positions
    starts[b]..starts[b]+T-1: the J blocks such a span can touch as
    (pool block ids [B, J], the token each of their rows takes
    [B, J*Bs], which rows take one [B, J, Bs]). Same contract: a row is
    live only for a valid token at a position in [0, MB*Bs); a (b, j)
    with no live row is routed to trash block 0, so every real block
    appears at most once."""
    Bs, (B, MB) = block_size, tables.shape
    J = (T + Bs - 2) // Bs + 1
    vb = starts[:, None] // Bs + jnp.arange(J)                # [B, J]
    pos = vb[..., None] * Bs + jnp.arange(Bs)                 # [B, J, Bs]
    t = pos - starts[:, None, None]
    live = (t >= 0) & (t < T) & (pos >= 0) & (pos < MB * Bs)
    t = jnp.clip(t, 0, T - 1).reshape(B, J * Bs)
    if valid is not None:
        live = live & jnp.take_along_axis(valid, t, axis=1
                                          ).reshape(B, J, Bs)
    ids = jnp.take_along_axis(tables, jnp.clip(vb, 0, MB - 1), axis=1)
    return jnp.where(live.any(-1), ids, 0), t, live


def _merge_blocks(pool: jnp.ndarray, new: jnp.ndarray, layer,
                  ids: jnp.ndarray, t: jnp.ndarray,
                  live: jnp.ndarray) -> jnp.ndarray:
    """pool [L,N,Hkv,Bs(,D)] with the live rows of blocks ``ids`` of
    ``layer`` taken from new [B,T,Hkv(,D)]: the blocks gathered, merged
    and scattered back whole, in place."""
    B, J, Bs = live.shape
    tail = (1,) * (new.ndim - 3)                              # D, if any
    rows = jnp.take_along_axis(new.astype(pool.dtype),
                               t.reshape(B, J * Bs, 1, *tail), axis=1)
    # [B, J*Bs, Hkv(, D)] -> head-major blocks [B, J, Hkv, Bs(, D)]
    rows = jnp.moveaxis(rows.reshape((B, J, Bs) + new.shape[2:]), 2, 3)
    return pool.at[layer, ids].set(
        jnp.where(live.reshape(B, J, 1, Bs, *tail), rows,
                  pool[layer, ids]))


def append_chunk(pool: jnp.ndarray, new: jnp.ndarray,
                 tables: jnp.ndarray, starts: jnp.ndarray,
                 valid: Optional[jnp.ndarray], layer) -> jnp.ndarray:
    """The serving path's write: new [B,T,Hkv,D] — row b's tokens at
    the contiguous positions starts[b]..starts[b]+T-1 — into layer
    ``layer`` of the whole pool [L,N,Hkv,Bs,D], which comes back
    updated in place (module text: appends by BLOCKS).
    Every block a table references ends up as write_chunk would leave
    it on that layer; invalid, negative and beyond-capacity tokens are
    written nowhere a table points."""
    return _merge_blocks(pool, new, layer, *_span_addresses(
        tables, starts, new.shape[1], pool.shape[3], valid))


def append_chunk_q(pool: jnp.ndarray, scales: jnp.ndarray,
                   new: jnp.ndarray, tables: jnp.ndarray,
                   starts: jnp.ndarray, valid: Optional[jnp.ndarray],
                   layer) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """append_chunk for the int8 pool: quantize new [B,T,Hkv,D] and
    append payload and scales ([L,N,Hkv,Bs,D] int8, [L,N,Hkv,Bs] f32)
    through the same addresses."""
    q, scale = quantize_chunk(new)
    at = _span_addresses(tables, starts, new.shape[1], pool.shape[3],
                         valid)
    return (_merge_blocks(pool, q, layer, *at),
            _merge_blocks(scales, scale, layer, *at))


def quantize_chunk(new: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(token, head) int8 over the head dim.

    new [B,T,Hkv,D] -> (int8 same shape, fp32 scale [B,T,Hkv]) with
    value = int8 * scale. Mirrors models/quant.quantize_tensor's
    recipe, with the channel axis per cached token (K/V vectors are
    consumed whole per position, so one scale per vector loses
    nothing to outlier columns)."""
    f = new.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def write_chunk_q(cache_layer: jnp.ndarray, scale_layer: jnp.ndarray,
                  new: jnp.ndarray, tables: jnp.ndarray,
                  positions: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """write_chunk for the int8 pool: quantize new [B,T,Hkv,D] and
    scatter payload + scales ([N,Hkv,Bs,D] int8, [N,Hkv,Bs] f32)
    through the same (block, offset) addressing (_chunk_addresses)."""
    q, scale = quantize_chunk(new)
    B, T = positions.shape
    blk, off = _chunk_addresses(tables, positions, cache_layer.shape[2],
                                valid)
    layer = cache_layer.at[blk, :, off, :].set(
        q.reshape((B * T,) + q.shape[2:]))
    scales = scale_layer.at[blk, :, off].set(
        scale.reshape(B * T, -1))
    return layer, scales


def _blocks(pool: jnp.ndarray, tables: jnp.ndarray, nb: int, layer):
    """The first nb blocks of every slot, [B, nb, Hkv, Bs(, D)]: from
    one layer [N, ...] or, with ``layer``, straight out of the whole
    pool [L, N, ...]."""
    t = tables[:, :nb]
    return pool[t] if layer is None else pool[layer, t]


def gather_view(pool: jnp.ndarray, tables: jnp.ndarray,
                nb: int, layer=None) -> jnp.ndarray:
    """Materialize the first nb blocks of every slot as a contiguous
    [B, nb*Bs, Hkv, D] view; view index s is virtual position s. pool
    is one layer [N,Hkv,Bs,D] or, with ``layer``, the whole pool.
    Unallocated table entries read trash block 0 — garbage that the
    causal position mask always hides (a query at position p only
    attends positions <= p, all of which are allocated and written)."""
    g = _blocks(pool, tables, nb, layer)                     # [B,nb,Hkv,Bs,D]
    B, _, Hkv, Bs, D = g.shape
    g = g.transpose(0, 1, 3, 2, 4)                           # [B,nb,Bs,Hkv,D]
    return g.reshape(B, nb * Bs, Hkv, D)


def gather_view_q(pool: jnp.ndarray, scales: jnp.ndarray,
                  tables: jnp.ndarray, nb: int,
                  dtype=jnp.bfloat16, layer=None) -> jnp.ndarray:
    """gather_view for the int8 pool: dequantized [B, nb*Bs, Hkv, D]
    in `dtype`. The HBM read is int8 + one scale per vector — half the
    bf16 pool's traffic; the dequantized product is a fused temporary
    feeding attention, never resident."""
    # dequantize in f32 and cast the PRODUCT — the pallas kernels
    # dequantize at f32 too, so the fallback and kernel paths stay
    # numerically identical (greedy streams must not depend on which
    # backend served a window)
    g = _blocks(pool, tables, nb, layer).astype(jnp.float32)
    s = _blocks(scales, tables, nb, layer).astype(jnp.float32)
    B, _, Hkv, Bs, D = g.shape                        # s [B,nb,Hkv,Bs]
    g = (g * s[..., None]).astype(dtype)
    g = g.transpose(0, 1, 3, 2, 4)
    return g.reshape(B, nb * Bs, Hkv, D)


def append(pool: Pool, k: jnp.ndarray, v: jnp.ndarray,
           tables: jnp.ndarray, starts: jnp.ndarray,
           valid: Optional[jnp.ndarray], layer, *, mesh) -> Pool:
    """One layer's write: k, v [B,T,Hkv,D] — row b's tokens at
    positions starts[b]..starts[b]+T-1 — appended to layer ``layer``
    of the whole pool, which comes back as the same tuple it came in
    as. The latent pool (one array) takes k [B,T,1,W] and no v; with
    the index pool beside it (two arrays), v [B,T,1,Di] are the index
    keys.

    By ROWS (pallas_paged.append_rows: one kernel for all the layer's
    pools, a tile of the block each row lands in) where
    pallas_paged.kv_append_path says so from the pool, B, T and
    ``mesh``, the mesh the layer's read is given (None: one device; it
    has no default, because a caller that forgot it under a mesh would
    run the single-device kernel over a sharded pool): a decode or
    speculative window on a kernel's attention path. By BLOCKS
    (append_chunk, or append_chunk_q where the pool carries scales)
    everywhere else (module text). The same bytes either way on every
    block a table references."""
    path = pallas_paged.kv_append_path(pool, k.shape[0], k.shape[1], mesh)
    if path == pallas_paged.KV_APPEND_ROWS:
        return pallas_paged.append_rows(
            pool, (k,) if len(pool) == 1 else (k, v), tables, starts,
            valid, layer, interpret=pallas_paged.needs_interpret())
    if len(pool) == 1:
        return (append_chunk(pool[0], k, tables, starts, valid, layer),)
    k_cache, v_cache, *scales = pool
    if scales:
        k_cache, k_scales = append_chunk_q(k_cache, scales[0], k, tables,
                                           starts, valid, layer)
        v_cache, v_scales = append_chunk_q(v_cache, scales[1], v, tables,
                                           starts, valid, layer)
        return k_cache, v_cache, k_scales, v_scales
    return (append_chunk(k_cache, k, tables, starts, valid, layer),
            append_chunk(v_cache, v, tables, starts, valid, layer))


def attend(q: jnp.ndarray, pool: Pool, tables: jnp.ndarray,
           starts: jnp.ndarray, positions: jnp.ndarray,
           kv_len: Optional[int], layer, *, window: Optional[int],
           scale: float, softcap: Optional[float],
           mesh=None, value_dim: int = 0,
           select: Optional[jnp.ndarray] = None,
           expand=None) -> jnp.ndarray:
    """One layer's read: q [B,T,H,D] at ``positions`` [B,T] (contiguous
    from starts [B]) over layer ``layer`` of the pool, which already
    holds the chunk's own K/V (append, then attend) -> [B,T,H,D].

    kv_len (static) bounds the read to the first ceil(kv_len/Bs)
    blocks of every slot; the caller guarantees every real query
    position is < kv_len. window: sliding window (None/0 = full
    causal); softcap: tanh cap on the raw scores (None/0 = off).

    On a kernel path (pallas_paged.attention_path) the K/V blocks are
    streamed straight from pool[layer] through the tables — no slice
    of the pool, no gathered copy, no [T, S] score materialization,
    per-row causal block skipping; prefill chunks AND decode/spec
    windows, shard-local per head via shard_map under a tp-only mesh.
    Elsewhere the gathered view feeds the position-masked jnp
    attention (ops/attention.py).

    The latent pool (one array; value_dim = kv_lora_rank, static): q
    [B,T,H,W] are the ABSORBED queries ``[q_lat | q_rope]``, every head
    on the one cached vector a token, and the values are the first
    value_dim columns of the keys -> [B,T,H,value_dim].

    select [B, T, nb*Bs] of 0 / 1 (the latent pool;
    ``attend_selected``): a query attends only the positions marked
    for it, a mask inside the kernel (the decode kernel's for one
    position a row, else the prefill kernel's).

    expand (the latent pool, where ``expands`` says so): W_kvb's two
    halves and their scales (pallas_paged.paged_attention ``expand``);
    q [B,T,H,nope+rope] are then the heads' own queries and the prefill
    kernel makes each head's keys and values from the cached latents
    -> [B,T,H,v]."""
    k_cache, v_cache, *scales = pool if len(pool) > 1 else (pool[0], None)
    Bs, MB = k_cache.shape[-2], tables.shape[1]
    nb = MB if kv_len is None else min(-(-kv_len // Bs), MB)
    T, H, D = q.shape[1:]
    if expand is not None:      # ``expands`` chose the kernel already
        return pallas_paged.paged_attention(
            q, k_cache, None, tables, starts, nb=nb,
            interpret=pallas_paged.needs_interpret(), scale=scale,
            layer=layer, value_dim=value_dim, select=select,
            expand=expand)
    path = pallas_paged.attention_path(T, H // k_cache.shape[2], D, Bs,
                                       mesh, value_dim=value_dim)
    if path != pallas_paged.JNP_GATHER:
        kw = dict(nb=nb, interpret=pallas_paged.needs_interpret(),
                  window=window or 0, scale=scale,
                  softcap=softcap or 0.0, layer=layer)
        if v_cache is None:
            kw.update(value_dim=value_dim)
        if select is not None:
            kw.update(select=select)
        if scales:
            kw.update(k_scales=scales[0], v_scales=scales[1])
        if mesh is not None:
            return pallas_paged.paged_attention_sharded(
                q, k_cache, v_cache, tables, starts, mesh, **kw)
        # a mask of selected positions: the decode kernel takes one
        # query position a row, anything wider is the prefill kernel's
        decode = (path.startswith("pallas_paged_decode")
                  and (select is None or T == 1))
        if decode and select is not None:
            kw.update(select=select[:, 0])
        paged_fn = (pallas_paged.paged_decode_attention if decode
                    else pallas_paged.paged_attention)
        return paged_fn(q, k_cache, v_cache, tables, starts, **kw)
    if scales:
        k_att = gather_view_q(k_cache, scales[0], tables, nb,
                              dtype=q.dtype, layer=layer)
        v_att = gather_view_q(v_cache, scales[1], tables, nb,
                              dtype=q.dtype, layer=layer)
    else:
        k_att = gather_view(k_cache, tables, nb, layer=layer)
        v_att = (k_att[..., :value_dim] if v_cache is None
                 else gather_view(v_cache, tables, nb, layer=layer))
    return attention_with_cache(q, k_att, v_att, positions, scale=scale,
                                sliding_window=window,
                                logit_softcap=softcap, select=select)


def expands(T: int, heads: int, latents: jnp.ndarray, value_dim: int,
            head_dims: Tuple[int, int, int], mesh=None) -> bool:
    """Does a forward of T positions a row over the latent pool attend
    EXPANDED (each head's keys and values made from the cached latents
    inside the prefill kernel) and not absorbed? pallas_paged.
    attention_path's answer from the shapes (``expanded_cheaper``):
    static, like the chunk bucket; engine/runner.py names the
    executable's attention path by the same call. The pool, what is
    cached and every shorter forward are the same either way."""
    return "_expanded" in pallas_paged.attention_path(
        T, heads, latents.shape[-1], latents.shape[-2], mesh,
        value_dim=value_dim, head_dims=head_dims)


def selects(kv_len: Optional[int], max_blocks: int, block_size: int,
            topk: int) -> bool:
    """Does a forward that reads the first ceil(kv_len/Bs) blocks of a
    row (all ``max_blocks`` of its table where kv_len is None) select
    what it attends? Only where those hold more than ``topk``
    positions: under that every live position is among the topk best,
    and the layer is plain latent attention (its index keys are written
    all the same: a longer context will score them). Static, like the
    kv bucket: engine/runner.py names the executable's attention path
    by the same rule."""
    nb = max_blocks if kv_len is None else min(-(-kv_len // block_size),
                                               max_blocks)
    return bool(topk) and nb * block_size > topk


def attend_selected(q: jnp.ndarray, latents: jnp.ndarray,
                    index: jnp.ndarray, iq: jnp.ndarray, iw: jnp.ndarray,
                    tables: jnp.ndarray, starts: jnp.ndarray,
                    positions: jnp.ndarray, kv_len: Optional[int], layer,
                    *, topk: int, scale: float, value_dim: int,
                    mesh=None, expand=None) -> jnp.ndarray:
    """``attend`` over the latent pool for a layer that selects what it
    attends (ops/dsa.py): q [B,T,H,W] the absorbed queries, latents
    [L,N,1,Bs,W] and index [L,N,1,Bs,Di] the two pools (both already
    hold the chunk's own tokens), iq [B,T,Hi,Di] the index queries, iw
    [B,T,Hi] fp32 their weights with the scales folded in. With
    ``expand`` (``attend``) q are the heads' own queries and the
    prefill kernel attends expanded under the same marks.

    Three stages, each under its scope. ``dsa_indexer``: the row's
    index keys gathered through the tables (a sixth of the latents'
    bytes) and every query scored against them. ``dsa_select``: the
    ``topk`` best positions at or before each query, ties to the lower
    position, as a mask. ``sparse_attention``: the mask goes to the
    paged kernel of the forward's kind, the decode kernel for one
    position a row and the prefill kernel for a chunk; either reads
    every live block and attends the marked positions alone (the same
    sum as over the selected set; on the v5e fetching 2048 selected
    latents row by row ran at a ninth of the bandwidth at which the
    kernel streams live blocks: PERF.md, PR 40). Where the kernels are
    off, the gathered view and the masked jax.numpy attention."""
    B, T = q.shape[:2]
    Bs, MB = latents.shape[-2], tables.shape[1]
    nb = MB if kv_len is None else min(-(-kv_len // Bs), MB)
    S = nb * Bs
    with jax.named_scope("dsa_indexer"):
        keys = gather_view(index, tables, nb, layer=layer)[:, :, 0, :]
        scores = dsa.index_scores(iq, iw, keys)              # [B,T,S]
    with jax.named_scope("dsa_select"):
        mask = dsa.select(scores.reshape(B * T, S),
                          positions.reshape(B * T), topk,
                          dtype=q.dtype).reshape(B, T, S)
        if dsa.tap is not None:
            jax.debug.callback(dsa.tap, layer, positions, mask,
                               ordered=True)
    with jax.named_scope("sparse_attention"):
        return attend(q, (latents,), tables, starts, positions, kv_len,
                      layer, window=None, scale=scale, softcap=None,
                      mesh=mesh, value_dim=value_dim, select=mask,
                      expand=expand)
