"""The least time the steps of a latent-attention mixture of experts
with a learned sparse-attention indexer (GLM-5, ``glm_moe_dsa``) could
take on the chip, from the configuration's published keys: the
yardstick of ``sparse_decode_step_roofline``,
``sparse_prefill_chunk_roofline``, ``indexer_kernel_roofline`` and
``sparse_attention_kernel_roofline``.

The same work whatever implements it: a query scores every key of its
context against the index pool (``index_head_dim`` values a token) and
attends min(context, ``index_topk``) latents, however the program finds
and fetches them. ``chipbench/roofline_latent.py`` would count every
live latent, no indexer, and the held experts as all the router's.
Peaks and ``least_seconds`` are ``chipbench/roofline.py``'s.

ONE input is not the file's, as in roofline_latent: how many of the
experts HELD here a layer of a decode step read (``touched``), from the
program's counter ``totals.moe``.
"""

from typing import Dict, Sequence

from chipbench import roofline_latent

CACHE_BYTES = 2.0       # bfloat16 pools


def attention_weights(hf: Dict) -> int:
    """roofline_latent's five matrices and the indexer's three: index
    queries from the query bottleneck, the index key and the heads'
    weights from the layer's input."""
    hi, di = hf["index_n_heads"], hf["index_head_dim"]
    return (roofline_latent.attention_weights(hf)
            + hf["q_lora_rank"] * hi * di + hf["hidden_size"] * (di + hi))


def index_call_needs(hf: Dict, contexts: Sequence[int]) -> Dict[str, float]:
    """ONE layer's index scores of one query a row: every key of the
    row's context once, two operations per index head and key value."""
    keys = float(sum(contexts))
    return {"bytes": keys * hf["index_head_dim"] * CACHE_BYTES,
            "ops": 2.0 * keys * hf["index_n_heads"] * hf["index_head_dim"]}


def attention_call_needs(hf: Dict, contexts: Sequence[int]
                         ) -> Dict[str, float]:
    """ONE layer's absorbed attention of one query a row over the
    positions it selected: min(context, index_topk) latents a row."""
    attended = sum(min(c, hf["index_topk"]) for c in contexts)
    return roofline_latent.attention_call_needs(hf, attended, CACHE_BYTES)


def _layer_weights(hf: Dict, experts_read: float, experts_pass: float):
    """(weights a forward reads once, weights a token passes through):
    all layers and the output head, the router at its published width
    (``deployment.router_experts``), of the held experts
    ``experts_read`` a layer read and ``experts_pass`` a token's
    share."""
    h, L = hf["hidden_size"], hf["num_hidden_layers"]
    dense_layers = min(hf.get("first_k_dense_replace", 0), L)
    router = h * (hf.get("deployment") or {}).get(
        "router_experts", hf["n_routed_experts"])
    expert = 3 * h * hf["moe_intermediate_size"]
    shared = hf.get("n_shared_experts", 0) * expert
    fixed = (L * attention_weights(hf)
             + dense_layers * 3 * h * hf["intermediate_size"]
             + h * hf["vocab_size"])
    moe_layers = L - dense_layers
    return (fixed + moe_layers * (router + shared + experts_read * expert),
            fixed + moe_layers * (router + shared + experts_pass * expert))


def held_share(hf: Dict) -> float:
    """The share of a token's top-k assignments that land on experts
    held here under even routing."""
    return hf["n_routed_experts"] / (hf.get("deployment") or {}).get(
        "router_experts", hf["n_routed_experts"])


def decode_step_needs(hf: Dict, contexts: Sequence[int], touched: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of len(contexts) live rows: every weight read
    once (``touched`` of the held experts a layer), each row's index
    keys and selected latents in every layer; two operations per weight
    a token passes (its top-k's share that is held here)."""
    L = hf["num_hidden_layers"]
    read, passed = _layer_weights(
        hf, touched, hf["num_experts_per_tok"] * held_share(hf))
    index, attend = (index_call_needs(hf, contexts),
                     attention_call_needs(hf, contexts))
    return {"bytes": read * weight_bytes_per_param
            + L * (index["bytes"] + attend["bytes"]),
            "ops": 2.0 * len(contexts) * passed
            + L * (index["ops"] + attend["ops"])}


def prefill_chunk_needs(hf: Dict, tokens: int, in_context: float,
                        scored: float, attended: float,
                        weight_bytes_per_param: float = 1.0
                        ) -> Dict[str, float]:
    """ONE prefill chunk of ``tokens`` queries that have on average
    ``in_context`` keys at or before them, score ``scored`` and attend
    ``attended`` of them (the program's counters ``totals.sparse``
    a query). Bytes: every weight once (every held expert) and the
    context's latents and index keys once in every layer. Operations:
    two per weight a token passes, and per query the index scores and
    the attention over what it attends. The logits are reckoned for
    every position, as the program computes them."""
    L, nh, r = (hf["num_hidden_layers"], hf["num_attention_heads"],
                hf["kv_lora_rank"])
    read, passed = _layer_weights(
        hf, hf["n_routed_experts"],
        hf["num_experts_per_tok"] * held_share(hf))
    width = roofline_latent.latent_width(hf)
    context = in_context + tokens / 2.0         # the chunk's last query's
    per_query = (2.0 * scored * hf["index_n_heads"] * hf["index_head_dim"]
                 + 2.0 * attended * nh * (2 * r + hf["qk_rope_head_dim"]))
    return {"bytes": read * weight_bytes_per_param
            + L * context * (width + hf["index_head_dim"]) * CACHE_BYTES,
            "ops": tokens * (2.0 * passed + L * per_query)}
