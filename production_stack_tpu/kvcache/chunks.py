"""Token-chunk prefix hashing for KV reuse.

KV for a token depends on the whole prefix before it, so chunk keys are a
hash *chain*: chunk i's key digests chunk i's tokens together with chunk
i-1's key. Two prompts sharing a prefix produce identical keys exactly up to
their longest common chunk-aligned prefix — lookup walks the chain until the
first miss. Only full chunks are stored (a partial tail is recomputed),
mirroring chunk-granular KV stores like the reference's LMCache tier
(reference: deployment-vllm-multi.yaml:154-178 sets LMCACHE_CHUNK_SIZE).

Keys must be identical across processes/replicas (router affinity sends
same-session requests to the same replica, but the remote tier is shared by
all replicas) — so hashing is hashlib.blake2b over a canonical little-endian
int32 packing, never Python's salted hash().
"""

import hashlib
import struct
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple

if TYPE_CHECKING:   # annotation only — keep this module import-light:
    # the ROUTER hashes prompt chunks through chain_digest_bytes, and
    # models.config would drag jax into its process
    from production_stack_tpu.models.config import ModelConfig

DEFAULT_CHUNK_SIZE = 256


def chain_digest_bytes(data: bytes, chunk_bytes: int,
                       digest_size: int = 12) -> List[bytes]:
    """Chained digests of ``data``'s full ``chunk_bytes`` chunks.

    The byte-level analogue of ``ChunkHasher.chain_keys``: digest i
    folds digest i-1, so two byte strings produce identical digests
    exactly up to their longest common chunk-aligned prefix, and a
    match on digest i implies the whole leading prefix matches. Shared
    by the router's cache-aware prefix ring and the fake engine's KV
    simulation (tests/fake_engine.py) so the two sides of the kvshare
    rig can never drift apart."""
    out: List[bytes] = []
    prev = b""
    for i in range(0, len(data) - chunk_bytes + 1, chunk_bytes):
        h = hashlib.blake2b(digest_size=digest_size)
        h.update(prev)
        h.update(data[i:i + chunk_bytes])
        prev = h.digest()
        out.append(prev)
    return out


def model_fingerprint(cfg: "ModelConfig",
                      kv_dtype: str = "bfloat16") -> str:
    """Cache-key namespace: everything the KV layout/values depend on."""
    # (a looped model's passes keep K and V of their own: their count
    # is part of the layout; every other model's string is unchanged)
    loops = f"x{cfg.loop_steps}" if cfg.loop_steps > 1 else ""
    raw = (f"{cfg.name}|L{cfg.num_layers}{loops}|H{cfg.num_kv_heads}"
           f"|D{cfg.head_dim_}|rope{cfg.rope_theta}|{kv_dtype}")
    return hashlib.blake2b(raw.encode(), digest_size=8).hexdigest()


class ChunkHasher:
    def __init__(self, chunk_size: int = DEFAULT_CHUNK_SIZE,
                 namespace: str = ""):
        if chunk_size <= 0:
            raise ValueError("chunk_size must be positive")
        self.chunk_size = chunk_size
        self.namespace = namespace

    def num_full_chunks(self, num_tokens: int) -> int:
        return num_tokens // self.chunk_size

    def chunk_keys(self, tokens: Sequence[int],
                   salt: str = "") -> List[bytes]:
        """Keys for every *full* chunk of `tokens`, in order.

        ``salt`` extends the namespace for variants that produce
        different KV from the same tokens under the same model geometry
        — e.g. a LoRA adapter name (adapters with k/v targets color the
        cache, so adapter and base chunks must never collide)."""
        keys, _ = self.chain_keys(tokens, salt=salt)
        return keys

    def chain_keys(self, tokens: Sequence[int], salt: str = "",
                   state: Optional[Tuple[int, bytes]] = None,
                   ) -> Tuple[List[bytes], Tuple[int, bytes]]:
        """Incremental chunk_keys: returns (new_keys, state').

        ``state`` = (chunks_already_keyed, previous_digest) from an
        earlier call over a PREFIX of the same token stream — the chain
        extends in O(new chunks) instead of rehashing from the start
        (progressive publish calls this once per prefill chunk; without
        the state a long prompt's hashing would be quadratic)."""
        start = 0
        prev = (self.namespace + ("|" + salt if salt else "")).encode()
        if state is not None:
            start, prev = state
        keys: List[bytes] = []
        n = self.num_full_chunks(len(tokens))
        for i in range(start, n):
            chunk = tokens[i * self.chunk_size:(i + 1) * self.chunk_size]
            h = hashlib.blake2b(digest_size=16)
            h.update(prev)
            h.update(struct.pack(f"<{len(chunk)}i", *chunk))
            digest = h.digest()
            keys.append(self.namespace.encode() + b":" + digest.hex().encode())
            prev = digest
        return keys, (max(n, start), prev)
