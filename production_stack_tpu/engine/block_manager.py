"""Host-side allocator + prefix cache for the paged KV pool.

Pure bookkeeping over the block pool in models/kv.py — never touches the
device. Called only under the engine lock (admission, decode-window
extension, finish/abort), so it needs no locking of its own.

Prefix caching here is block *sharing*: a finished sequence's full
blocks stay in the pool, registered under chain hashes of their token
content (kvcache/chunks.ChunkHasher — chunk i's key digests chunk i's
tokens AND chunk i-1's key, so equal keys imply an identical full
prefix). A new prompt that matches a chain of registered blocks simply
points its block table at them (refcount++), paying zero copies and
zero HBM — the reference's --enable-prefix-caching semantics
(reference: helm/templates/deployment-vllm-multi.yaml:73-75) the way
vLLM's own paged KV implements them, rebuilt for the static-shape TPU
pool. This replaces the earlier HBMPrefixPool, which kept a separate
pool buffer and *copied* matched prefixes into slots (doubling resident
bytes for hot prefixes).

Invariants:
- Block 0 (trash) is never allocated.
- A sequence writes only into blocks it exclusively owns: matching is
  capped so shared blocks are always fully-written full blocks, and a
  prompt always recomputes at least its final position (a sampled
  token needs live logits).
- Registered blocks with refcount 0 sit in an LRU; allocation prefers
  the free list and evicts LRU-registered blocks only when it is empty.
"""

import collections
from typing import Dict, List, Optional, Sequence, Tuple

from production_stack_tpu.kvcache.chunks import ChunkHasher
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


class BlockManager:
    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = False,
                 namespace: str = "", bytes_per_token: int = 0,
                 layout: str = "kv_heads", index_bytes_per_token: int = 0,
                 state_pages: int = 0, state_bytes_per_slot: int = 0,
                 pool_layers: int = 0, reader_layers: int = 0,
                 weight_layers: int = 0):
        if num_blocks < 2:
            raise ValueError("pool needs at least one non-trash block")
        self.num_blocks = num_blocks          # includes trash block 0
        self.block_size = block_size
        # what a token takes in the device pool, all layers, as
        # allocated, and how it lies there (models/kv.KVCache:
        # "kv_heads" | "latent"); 0 where no pool was described
        self.bytes_per_token = bytes_per_token
        self.layout = layout
        # the pool's layers, and the model's layers that read one: more
        # where some read another layer's K/V and append nothing (a
        # decoder-hybrid-decoder's cross layers); reported, not used
        self.pool_layers = pool_layers
        self.reader_layers = reader_layers
        # the model's layers as its weights count them: fewer than the
        # pool's where a looped model runs them several times, each
        # pass over pool layers of its own; reported, not used
        self.weight_layers = weight_layers
        # bytes_per_token's part that a second pool under the same
        # tables takes ("latent+index": the sparse-attention indexer's
        # keys): a block id names a block of both pools, so every
        # count here (free, active, cached, reuse) is of both at once
        self.index_bytes_per_token = index_bytes_per_token
        # the second KIND of cache (models/kv.py "State pages"): state a
        # SEQUENCE, not a token. A model with Gated DeltaNet layers
        # keeps one page a sequence in a pool of ``state_pages`` pages,
        # page 0 the trash page (never handed out, as block 0); a
        # sequence takes ONE at admission beside its blocks and gives
        # it back with them. 0 pages: the model keeps no such state
        # A model whose ONLY cache is state pages (layout "state":
        # power retention layers) has no second pool: its page IS its
        # block, of max_model_len tokens, so every count here is of
        # pages, a sequence holds one, and the pages' own list stays
        # empty (``pages_are_blocks``; the caller's ``state_pages`` is
        # then the pool's size again and is not kept)
        self.pages_are_blocks = layout == "state"
        if self.pages_are_blocks:
            state_pages = 0
        self.state_pages = state_pages
        self.state_bytes_per_slot = state_bytes_per_slot
        self._free_pages: List[int] = list(range(state_pages - 1, 0, -1))
        self.pages_alloc = 0
        self.pages_freed = 0
        self.page_alloc_failures = 0
        self.hasher = (ChunkHasher(block_size, namespace="blk|" + namespace)
                       if enable_prefix_caching else None)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}        # block -> refcount (>= 1)
        self._by_key: Dict[bytes, int] = {}   # chain key -> block
        self._key_of: Dict[int, bytes] = {}   # block -> chain key
        # registered blocks with refcount 0, insertion order = LRU
        self._evictable: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        # fragmentation telemetry (plain ints — read at scrape time and
        # on /debug/perf; docs/observability.md "Engine efficiency"):
        # allocation failures split by WHY the pool refused. A request
        # arriving at a pool with zero allocatable blocks hit true
        # exhaustion; one refused while allocatable blocks remain
        # (just fewer than it needs) hit the fragmentation regime —
        # free capacity exists but is insufficient for this request,
        # the admission-failure class fleet-level migration/defrag
        # (the kvplane) exists to erase.
        self.allocs = 0
        self.blocks_allocated = 0
        self.alloc_failures_exhausted = 0
        self.alloc_failures_fragmented = 0
        self.cache_evictions = 0
        # kvplane intra-replica defrag: the engine runs defrag()
        # between fused windows when fragmented failures rose
        self.defrag_runs = 0
        self.defrag_block_moves = 0
        # optional occupancy observer (the engine wires this to the
        # metrics layer's plain-int histogram): called with the pool
        # usage fraction at every allocation attempt, so the histogram
        # shows which occupancy regime allocations actually run in
        self.on_alloc_occupancy = None

    # -- capacity --------------------------------------------------------

    @property
    def available(self) -> int:
        """Blocks allocatable right now (free + evictable-cached)."""
        return len(self._free) + len(self._evictable)

    @property
    def active_blocks(self) -> int:
        """Blocks held by live sequences."""
        return len(self._ref)

    @property
    def usage(self) -> float:
        return self.active_blocks / float(self.num_blocks - 1)

    @property
    def free_blocks(self) -> int:
        """Blocks on the free list (never-written or fully released)."""
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 registered blocks (evictable prefix cache)."""
        return len(self._evictable)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def frag_report(self) -> dict:
        """Point-in-time fragmentation view (plain-int reads, safe from
        any thread): block-state census + allocation-failure
        classification. The scrape-time sync (EngineMetrics.sync_kvpool)
        and ``GET /debug/perf`` both serve exactly this dict."""
        return {
            "num_blocks": self.num_blocks - 1,   # allocatable, no trash
            "bytes_per_token": self.bytes_per_token,
            "index_bytes_per_token": self.index_bytes_per_token,
            "layout": self.layout,
            "pool_layers": self.pool_layers,
            "reader_layers": self.reader_layers,
            "weight_layers": self.weight_layers,
            "free": self.free_blocks,
            "active": self.active_blocks,
            "cached": self.cached_blocks,
            "usage": round(self.usage, 4),
            "allocs": self.allocs,
            "blocks_allocated": self.blocks_allocated,
            # (a missing state page counts as exhaustion: nothing of
            # that kind was allocatable)
            "alloc_failures_exhausted": (self.alloc_failures_exhausted
                                         + self.page_alloc_failures),
            "alloc_failures_fragmented": self.alloc_failures_fragmented,
            "cache_evictions": self.cache_evictions,
            "free_contiguity": round(self.free_contiguity(), 4),
            "defrag_runs": self.defrag_runs,
            "defrag_block_moves": self.defrag_block_moves,
            # state pages (0 / empty where the model keeps none)
            "state_bytes_per_slot": self.state_bytes_per_slot,
            "state_pages": {"total": self.total_pages,
                            "live": self.live_pages},
        }

    # -- state pages -----------------------------------------------------

    @property
    def keeps_pages(self) -> bool:
        """Does the model keep state a sequence, beside a K/V pool or
        alone?"""
        return bool(self.state_pages) or self.pages_are_blocks

    @property
    def total_pages(self) -> int:
        """State pages a sequence can be given (the trash page is not
        one)."""
        if self.pages_are_blocks:
            return self.num_blocks - 1
        return max(self.state_pages - 1, 0)

    @property
    def live_pages(self) -> int:
        """State pages held by live sequences."""
        if self.pages_are_blocks:
            return self.active_blocks
        return self.total_pages - len(self._free_pages)

    def page_counts(self) -> Dict[str, int]:
        """The pages' counters, for ``totals.state`` of /debug/perf."""
        if self.pages_are_blocks:
            return {"pages_alloc": self.blocks_allocated,
                    "pages_freed": self.pages_freed,
                    "alloc_failures": (self.alloc_failures_exhausted
                                       + self.alloc_failures_fragmented)}
        return {"pages_alloc": self.pages_alloc,
                "pages_freed": self.pages_freed,
                "alloc_failures": self.page_alloc_failures}

    def alloc_page(self) -> Optional[int]:
        """One state page, or None when every page is held (counted
        with the blocks' allocation failures: kv_alloc_failures reads
        both). A model without state pages is never asked."""
        if not self._free_pages:
            self.page_alloc_failures += 1
            return None
        self.pages_alloc += 1
        return self._free_pages.pop()

    def free_page(self, page: int) -> None:
        """Give a page back (0, "none", is ignored). What it holds is
        left as it is: the next sequence's first chunk starts from a
        zero state inside the layer (ops/gdn.py)."""
        if page:
            self._free_pages.append(page)
            self.pages_freed += 1

    def free_contiguity(self) -> float:
        """Fraction of adjacent free-block-id pairs: 1.0 when the free
        list is one dense run, ->0 as frees scatter across the pool.
        Device DMA batches contiguous block ranges, so scattered frees
        cost extra descriptors per transfer — the quantity defrag()
        restores between fused windows."""
        if len(self._free) < 2:
            return 1.0
        s = sorted(self._free)
        runs = sum(1 for a, b in zip(s, s[1:]) if b == a + 1)
        return runs / (len(s) - 1)

    def defrag(self) -> int:
        """Compact the free list: reorder it so subsequent pops hand
        out ascending, maximally dense block-id runs (pops take from
        the list tail). Pure host-side bookkeeping over indices — KV
        bytes never move, refcounts and the prefix cache are untouched,
        so this is safe between any two fused windows. Returns the
        number of list positions that changed."""
        self.defrag_runs += 1
        target = sorted(self._free, reverse=True)
        moved = sum(1 for a, b in zip(self._free, target) if a != b)
        self._free = target
        self.defrag_block_moves += moved
        return moved

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    # -- allocation ------------------------------------------------------

    def _take_one(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        if self._evictable:
            blk, _ = self._evictable.popitem(last=False)   # LRU out
            key = self._key_of.pop(blk)
            del self._by_key[key]
            self.cache_evictions += 1
            return blk
        return None

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh exclusive blocks (refcount 1), or None — all-or-
        nothing, so a failed admission/extension never leaks blocks."""
        if n <= 0:
            # n == 0 requests (fully prefix-shared prompts) are not
            # allocation attempts; keep them out of the telemetry
            return None if n < 0 else []
        self.allocs += 1
        if self.on_alloc_occupancy is not None:
            self.on_alloc_occupancy(self.usage)
        if self.available < n:
            if self.available == 0:
                self.alloc_failures_exhausted += 1
            else:
                self.alloc_failures_fragmented += 1
            return None
        out = []
        for _ in range(n):
            blk = self._take_one()
            self._ref[blk] = 1
            out.append(blk)
        self.blocks_allocated += n
        return out

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; refcount-0 registered blocks
        become LRU-evictable (their KV stays valid in the pool), others
        return to the free list."""
        for blk in blocks:
            r = self._ref.get(blk, 0) - 1
            if r > 0:
                self._ref[blk] = r
                continue
            self._ref.pop(blk, None)
            self.pages_freed += self.pages_are_blocks
            if blk in self._key_of:
                self._evictable[blk] = None    # MRU end
            else:
                self._free.append(blk)

    # -- prefix sharing --------------------------------------------------

    def prefix_keys(self, tokens: Sequence[int],
                    salt: str = "") -> List[bytes]:
        """Chain keys for the matchable prefix of a prompt: full blocks
        covering at most len(tokens)-1 positions (the sequence never
        writes into a shared block and always recomputes at least one
        position). Deterministic — callers may cache per prompt to
        avoid re-hashing on deferred admissions."""
        if self.hasher is None or len(tokens) < 2:
            return []
        usable = (len(tokens) - 1) // self.block_size
        if not usable:
            return []
        return self.hasher.chunk_keys(
            list(tokens[:usable * self.block_size]), salt=salt)

    def match_keys(self, keys: Sequence[bytes],
                   record_stats: bool = True) -> Tuple[List[int], int]:
        """Longest registered block chain along `keys` -> (pinned block
        ids, covered token count). Matched blocks are pinned
        (refcount++) — the caller owns them like alloc'd ones and must
        free() them. record_stats=False skips the hit/miss counters
        (retries of a deferred admission must count once, not once per
        scheduler pass)."""
        blocks: List[int] = []
        for key in keys:
            blk = self._by_key.get(key)
            if blk is None:
                break
            blocks.append(blk)
        if record_stats and self.hasher is not None:
            if blocks:
                self.hits += 1
            else:
                self.misses += 1
        for blk in blocks:
            r = self._ref.get(blk, 0)
            if r == 0:
                self._evictable.pop(blk, None)
            self._ref[blk] = r + 1
        return blocks, len(blocks) * self.block_size

    def match_prefix(self, tokens: Sequence[int],
                     salt: str = "") -> Tuple[List[int], int]:
        """prefix_keys + match_keys in one call (tests, simple users)."""
        if self.hasher is None or len(tokens) < 2:
            return [], 0
        return self.match_keys(self.prefix_keys(tokens, salt=salt))

    def register(self, tokens: Sequence[int], blocks: Sequence[int],
                 salt: str = "") -> int:
        """Register a finished sequence's full blocks for sharing.
        `tokens` must be exactly the WRITTEN positions' tokens
        (prompt + output[:-1]); only blocks fully covered by them are
        registered. Duplicate content (key already registered from
        another sequence) keeps the existing block. Call BEFORE
        free()ing the sequence's blocks. Returns blocks registered."""
        if self.hasher is None:
            return 0
        n = min(len(tokens) // self.block_size, len(blocks))
        if not n:
            return 0
        keys = self.hasher.chunk_keys(
            list(tokens[:n * self.block_size]), salt=salt)
        count = 0
        for key, blk in zip(keys, blocks):
            if key in self._by_key or blk in self._key_of:
                # shared-prefix blocks re-register under their own key
                # (skip), duplicates keep the first copy
                continue
            self._by_key[key] = blk
            self._key_of[blk] = key
            count += 1
        return count

    def register_incremental(self, tokens: Sequence[int],
                             blocks: Sequence[int], state,
                             salt: str = ""):
        """Progressive register() for live sequences: key and register
        only blocks completed SINCE the previous call, threading the
        hasher's (chunks_keyed, digest) chain state — O(new blocks)
        per prefill chunk where re-keying from scratch would make a
        long prompt's hashing quadratic (kvcache/chunks.chain_keys).
        Returns the new state; pass it back on the next call."""
        if self.hasher is None:
            return state
        n = min(len(tokens) // self.block_size, len(blocks))
        start = state[0] if state else 0
        if n <= start:
            return state
        new_keys, state = self.hasher.chain_keys(
            list(tokens[:n * self.block_size]), salt=salt, state=state)
        for key, blk in zip(new_keys, blocks[start:n]):
            if key in self._by_key or blk in self._key_of:
                continue
            self._by_key[key] = blk
            self._key_of[blk] = key
        return state
