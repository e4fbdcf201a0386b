"""Weight-only int8 quantization for the decoder's projection matmuls.

The reference passes --quantization down to vllm serve (reference:
helm/values.yaml modelSpec args / SURVEY.md §2.9 config surface); here
the engine implements the TPU-appropriate variant natively:

- **Symmetric per-output-channel int8** on every large matmul weight
  (q/k/v/o, dense gate/up/down, MoE expert stacks, embed, lm_head).
  Norm weights, biases, and the MoE router (tiny, accuracy-critical)
  stay in the model dtype.
- **Weight-only**: activations stay bf16. The matmul reads int8
  weights from HBM and converts in-register; XLA fuses the
  convert+scale into the dot epilogue. Decode is weight-bandwidth
  bound, so halving weight bytes approaches a 2x step-time headroom
  without the accuracy risk of activation quantization.
- A quantized leaf is ``{"w8": int8 [..., in, out], "scale": fp32
  [..., out]}`` in place of the raw array — same pytree *names*, so
  checkpoint loaders and sharding-by-name rules keep working
  (parallel/sharding.py maps the nested leaves' specs from the base
  rule: w8 keeps the weight's spec, scale keeps (leading..., out)).

Dequantized matmul identity: ``x @ (w8 * scale) == (x @ w8) * scale``
(scale broadcasts over the out axis), so projections compute
``(x @ w8.astype(dtype)) * scale`` — one fused multiply per output.
"""

from typing import Any, Dict

import jax.numpy as jnp

# layer-dict entries that stay un-quantized (small or accuracy-critical)
_SKIP_LAYER = ("attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm",
               "q_bias", "k_bias", "v_bias", "router", "s_gate_w",
               "q_a_norm", "kv_a_norm", "router_bias",
               # the sparse-attention indexer's norm and head weights
               "idx_k_norm", "idx_k_norm_bias", "idx_w",
               # a Gated DeltaNet mixer's small leaves, and the gated
               # attention's per-head norms
               "ba", "conv", "A_log", "dt_bias", "gdn_norm", "q_norm",
               "k_norm",
               # a power retention mixer's gate a key-value head
               "ret_gate", "ret_gate_bias",
               # a decoder-hybrid-decoder's LayerNorm biases; a Mamba
               # mixer's small projections, skip term and bias; the
               # differential attention's biases, lambdas and norm
               "attn_norm_bias", "mlp_norm_bias", "conv_bias", "x_proj",
               "dt_proj", "D", "qkv_bias", "o_bias", "subln", "lambda_q1",
               "lambda_k1", "lambda_q2", "lambda_k2",
               # a one-sublayer block's norm; a Mamba-2 mixer's gated
               # group norm
               "norm", "gate_norm")
# the groups of stacked layers a tree may hold: the scanned layers and
# a layer plan's leading dense ones (models/llama.py)
_LAYER_GROUPS = ("layers", "dense_layers", "gdn_layers", "attn_layers",
                 "mamba_layers", "diff_layers", "gmu_layers",
                 "cross_layers", "mamba2_layers", "gqa_layers",
                 "moe_layers")


def quantize_tensor(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Symmetric per-output-channel int8 over the last axis.

    w [..., in, out] -> {"w8": int8 same shape, "scale": fp32 [..., out]}
    with per-channel scale = max|w| / 127 reduced over the `in` axis
    (leading axes — layer/expert stacks — keep independent channels).
    """
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2)               # [..., out]
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    w8 = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127
                  ).astype(jnp.int8)
    return {"w8": w8, "scale": scale}


def quantize_embed(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Per-ROW int8 for the [V, H] embedding table: scale [V]. A row
    scale serves both roles — the token gather dequantizes the gathered
    rows, and the tied lm_head applies it per logit AFTER x @ w8.T."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1), 1e-8) / 127.0
    w8 = jnp.clip(jnp.round(wf / scale[:, None]), -127, 127
                  ).astype(jnp.int8)
    return {"w8": w8, "scale": scale}


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "w8" in leaf


def dequant_matmul(x: jnp.ndarray, w: Any, dtype=None,
                   exact_scale: bool = False) -> jnp.ndarray:
    """x @ w for raw or quantized w, in x.dtype (or `dtype`).
    ``exact_scale``: the float32 sums times the float32 scales, rounded
    to ``dtype`` ONCE; without it the sums are rounded to ``dtype``,
    the scales are, and their product is (three roundings a matmul,
    the scales' the same for every token). A model whose depth brings
    the logit probe to its limit asks for it
    (ModelConfig.exact_dequant_scale)."""
    if not is_quantized(w):
        return x @ w if dtype is None else (x @ w).astype(dtype)
    dtype = dtype or x.dtype
    if exact_scale:
        y = jnp.matmul(x, w["w8"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        return (y * w["scale"]).astype(dtype)
    y = x @ w["w8"].astype(dtype)
    return y * w["scale"].astype(dtype)


def leaf_quantizer(path):
    """The quantizer the standard int8 recipe applies to the leaf at
    ``path`` — ("embed",), ("lm_head",) or (group, name), group one
    of _LAYER_GROUPS, in the models/llama.py layout — or None where the leaf stays in the model
    dtype. Embed quantizes per row so the gather and tied-lm_head roles
    share one scale axis."""
    if path == ("embed",):
        return quantize_embed
    if path == ("lm_head",) or (path[0] in _LAYER_GROUPS
                                and path[1] not in _SKIP_LAYER):
        return quantize_tensor
    return None


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a stacked-params pytree (models/llama.py layout) in the
    standard int8 recipe (leaf_quantizer). Returns a new pytree."""
    def q(w, *path):
        fn = leaf_quantizer(path)
        return w if fn is None else fn(w)

    return {group: ({name: q(w, group, name) for name, w in tree.items()}
                    if group in _LAYER_GROUPS else q(tree, group))
            for group, tree in params.items()}


def dequant_rows(w: Any, rows: jnp.ndarray, dtype) -> jnp.ndarray:
    """Gather rows of a (possibly quantized) [V, H] table: the embedding
    lookup path (per-row scale from quantize_embed)."""
    if not is_quantized(w):
        return w[rows].astype(dtype)
    return (w["w8"][rows].astype(dtype)
            * w["scale"][rows].astype(dtype)[..., None])
