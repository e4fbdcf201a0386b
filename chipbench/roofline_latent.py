"""The least time a decode step of a latent-attention mixture of
experts (GLM-4.7-Flash, ``glm4_moe_lite``) could take on the chip, from
the configuration's published keys: the yardstick of
``latent_decode_step_roofline`` and ``latent_attention_kernel_roofline``.

``chipbench/roofline.py`` reads a dense or Qwen-style file (K and V per
kv head, ``num_experts``); on this family's file it would count a
quarter of the bytes. Peaks and ``least_seconds`` are that module's,
imported by the readers, not copied.

The count of bytes and operations is kept with the benchmark. ONE
input is not the file's: how many routed experts a layer of a step
read (``touched``). The step's reader takes it from the program's
counter ``totals.moe`` (``experts_read`` of ``experts_resident``, what
``moe_read_share`` reads too), because this family's selection bias
makes routing uneven and even routing's expectation counts half again
the bytes the rows chose (PERF.md, PR 35). What that counter counts is
therefore part of this yardstick: a PR that changes it moves
``latent_decode_step_roofline`` without moving the device's time, and
may claim nothing by it.
"""

from typing import Dict


def latent_width(hf: Dict) -> int:
    """Values the cache holds per token and layer: ``kv_lora_rank +
    qk_rope_head_dim``, or the padded width the file states under
    ``assumed.latent_pool_width`` (what is allocated is what is read)."""
    return (hf.get("assumed", {}).get("latent_pool_width")
            or hf["kv_lora_rank"] + hf["qk_rope_head_dim"])


def attention_weights(hf: Dict) -> int:
    """The five attention matrices of one layer: q_a, q_b, kv_a (with
    the rope part), kv_b, o."""
    h, nh = hf["hidden_size"], hf["num_attention_heads"]
    qr, r = hf["q_lora_rank"], hf["kv_lora_rank"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    return (h * qr + qr * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def attention_call_needs(hf: Dict, context_tokens: int,
                         kv_bytes_per_value: float = 2.0
                         ) -> Dict[str, float]:
    """ONE layer's absorbed attention over the live contexts, as the
    decode kernel runs it. Bytes: each live token's cached vector once.
    Operations, per query head: two per cached key element
    (``kv_lora_rank + qk_rope_head_dim``: the padding multiplies zeros)
    and two per cached value element (``kv_lora_rank``)."""
    nh, r = hf["num_attention_heads"], hf["kv_lora_rank"]
    key = r + hf["qk_rope_head_dim"]
    return {"bytes": context_tokens * latent_width(hf)
            * kv_bytes_per_value,
            "ops": 2.0 * context_tokens * nh * (key + r)}


def decode_step_needs(hf: Dict, rows: int, context_tokens: int,
                      touched: float,
                      weight_bytes_per_param: float = 1.0,
                      kv_bytes_per_value: float = 2.0) -> Dict[str, float]:
    """Bytes and operations ONE decode step needs for ``rows`` live
    sequences whose contexts sum to ``context_tokens`` and whose routing
    chose ``touched`` distinct routed experts a layer (even routing
    would choose E x (1 - (1 - 1/E)^(rows k)); a selection bias makes
    it fewer).

    Bytes: every weight the step must read once — per layer the five
    attention matrices; for a layer before ``first_k_dense_replace``
    the dense MLP, else the router, the shared experts and the
    ``touched`` routed experts, NOT all of them — the output head, and
    the cached vector of every live token in every layer. Operations:
    two per weight per row for every weight a token passes through (its
    top-k experts only; in the absorbed form kv_b's two halves are
    passed once each), plus the attention calls'. Embedding rows,
    norms, the router's bias and activations are left out: under a
    thousandth of the rest."""
    h, L = hf["hidden_size"], hf["num_hidden_layers"]
    dense_layers = min(hf.get("first_k_dense_replace", 0), L)
    E, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    expert = 3 * h * hf["moe_intermediate_size"]
    shared = hf.get("n_shared_experts", 0) * expert
    dense = 3 * h * hf["intermediate_size"]
    attn, head = attention_weights(hf), h * hf["vocab_size"]
    moe_layers = L - dense_layers
    weights_read = (L * attn + dense_layers * dense + moe_layers
                    * (h * E + shared + touched * expert) + head)
    weights_pass = (L * attn + dense_layers * dense + moe_layers
                    * (h * E + shared + k * expert) + head)
    call = attention_call_needs(hf, context_tokens, kv_bytes_per_value)
    return {"bytes": weights_read * weight_bytes_per_param
            + L * call["bytes"],
            "ops": 2.0 * rows * weights_pass + L * call["ops"]}
