"""Attention ops (grouped-query, causal, cache-aware).

TPU design notes:
- GQA is computed with *grouped einsums* — q is viewed as
  [B, T, Hkv, G, D] so K/V are never materialized at H query heads,
  saving HBM bandwidth (the usual TPU bottleneck).
- Softmax statistics are fp32; matmuls stay bf16 for the MXU.
- All shapes are static under jit: the serving path attends over the full
  preallocated cache [B, S, Hkv, D] with a position mask rather than
  dynamically slicing to the live length (dynamic shapes would defeat XLA
  tiling). The serving path's Pallas kernels stream the paged pool
  instead (ops/pallas_paged.py, which also decides who runs); these jnp
  versions are the reference semantics.

Reference behavior lives inside the external vLLM engine (reference repo
ships no kernels; see SURVEY.md §2.9) — this module is new TPU-first work.
"""

from typing import Optional

import jax.numpy as jnp

_NEG_INF = -1e30


def _grouped_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q [B,T,Hkv,G,D] x k [B,S,Hkv,D] -> fp32 scores [B,Hkv,G,T,S]."""
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32
    )
    return scores * scale


def _grouped_out(probs: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """probs [B,Hkv,G,T,S] x v [B,S,Hkv,D] -> [B,T,Hkv,G,D] in v.dtype."""
    return jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)


def _softcap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """Gemma-2 logit softcapping: s -> cap * tanh(s / cap). Applied to
    RAW scores, before any -inf masking (capping a masked score would
    resurrect it at -cap)."""
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Full-sequence causal GQA. q [B,T,H,D]; k,v [B,T,Hkv,D] -> [B,T,H,D].

    Used by the single-shot (non-incremental) forward: encode and the
    no-cache reference forward.
    Optional segment_ids [B,T] confine attention within packed segments.
    sliding_window W (Mistral/Gemma-2 local layers) further confines a
    query at t to keys in (t - W, t]. logit_softcap applies Gemma-2's
    tanh cap to the raw scores.
    """
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if scale is None:
        scale = D ** -0.5
    q5 = q.reshape(B, T, Hkv, G, D)
    scores = _softcap(_grouped_scores(q5, k, scale),
                      logit_softcap)  # [B,Hkv,G,T,S] fp32
    t = jnp.arange(T)
    mask = t[:, None] >= t[None, :]  # [T,S] causal
    if sliding_window is not None:
        mask = mask & (t[None, :] > t[:, None] - sliding_window)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B,T,S]
        mask = mask[None] & same
        mask = mask[:, None, None]  # [B,1,1,T,S]
    else:
        mask = mask[None, None, None]  # [1,1,1,T,S]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = _grouped_out(probs, v)
    return out.reshape(B, T, H, v.shape[-1])


def attention_with_cache(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_positions: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    select: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Incremental GQA over a preallocated per-slot cache.

    q           [B,T,H,D]   — the new chunk (T=1 for decode, >1 for prefill)
    k_cache     [B,S,Hkv,D] — cache ALREADY containing the new chunk's K
    v_cache     [B,S,Hkv,Dv] — Dv = D, or narrower (latent attention)
    q_positions [B,T]       — absolute position of each query token

    Query token at position p attends to cache slots s <= p (and
    s > p - sliding_window when windowed). Padding query rows
    (q_positions < 0) produce garbage rows the caller discards.
    select [B,T,S] of 0 / 1 (learned sparse attention, ops/dsa.py):
    a query attends only the slots marked for it.
    """
    B, T, H, D = q.shape
    S = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv
    if scale is None:
        scale = D ** -0.5
    q5 = q.reshape(B, T, Hkv, G, D)
    scores = _softcap(_grouped_scores(q5, k_cache, scale),
                      logit_softcap)  # [B,Hkv,G,T,S] fp32
    s_idx = jnp.arange(S)
    mask = s_idx[None, None, :] <= q_positions[:, :, None]  # [B,T,S]
    if sliding_window is not None:
        mask = mask & (s_idx[None, None, :]
                       > q_positions[:, :, None] - sliding_window)
    if select is not None:
        mask = mask & (select > 0)
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = _grouped_out(probs, v_cache)
    return out.reshape(B, T, H, v_cache.shape[-1])
