"""PartitionSpecs for the stacked-params Llama pytree (megatron-style).

Column-parallel projections (q/k/v/gate/up) shard the output feature dim
over ``tp``; row-parallel (o/down) shard the input feature dim, so each
layer needs exactly one psum (inserted automatically by XLA from the
sharding propagation) on the attention output and one on the MLP output —
riding ICI within the slice.

Embedding and lm_head shard the vocab dim; norms are replicated.
The KV cache shards over heads (tp) and slots (dp).
"""

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_LAYER_SPECS: Dict[str, P] = {
    # [L, in, out] column-parallel: shard out over tp
    "q": P(None, None, "tp"),
    "k": P(None, None, "tp"),
    "v": P(None, None, "tp"),
    "gate": P(None, None, "tp"),
    "up": P(None, None, "tp"),
    # [L, in, out] row-parallel: shard in over tp
    "o": P(None, "tp", None),
    "down": P(None, "tp", None),
    # column-parallel biases [L, out] follow their projection's out shard
    "q_bias": P(None, "tp"),
    "k_bias": P(None, "tp"),
    "v_bias": P(None, "tp"),
    # norms replicated (incl. Gemma-2's sandwich norms)
    "attn_norm": P(None, None),
    "mlp_norm": P(None, None),
    "post_attn_norm": P(None, None),
    "post_mlp_norm": P(None, None),
}


_MOE_SPECS: Dict[str, P] = {
    # router [L, h, E] replicated: every device routes every token
    "router": P(None, None, None),
    # expert-stacked FFN: experts over ep, hidden features over tp —
    # column-parallel gate/up ([L, E, h, i] shard i), row-parallel down
    # ([L, E, i, h] shard i), same one-psum-per-layer structure as the
    # dense path but within each expert
    "gate": P(None, "ep", None, "tp"),
    "up": P(None, "ep", None, "tp"),
    "down": P(None, "ep", "tp", None),
    # Qwen2-MoE shared expert: an ordinary dense MLP, megatron-sharded
    # over tp; its scalar sigmoid gate is replicated
    "s_gate": P(None, None, "tp"),
    "s_up": P(None, None, "tp"),
    "s_down": P(None, "tp", None),
    "s_gate_w": P(None, None, None),
}


def _qspec(leaf: Any, spec: P, per_row: bool = False) -> Any:
    """Expand a weight's spec for int8-quantized leaves (models/quant.py
    {"w8", "scale"} dicts): w8 keeps the weight's spec; scale drops the
    reduced axis — the in axis (-2) for per-output-channel weights, the
    last axis for the per-row embed table."""
    from production_stack_tpu.models.quant import is_quantized
    if not is_quantized(leaf):
        return spec
    dims = tuple(spec)
    scale_spec = P(*dims[:-1]) if per_row else P(*dims[:-2], dims[-1])
    return {"w8": spec, "scale": scale_spec}


def param_pspecs(params: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/llama.py's params layout."""
    moe = "router" in params["layers"]
    layer_specs = dict(_LAYER_SPECS, **_MOE_SPECS) if moe else _LAYER_SPECS
    specs: Dict[str, Any] = {
        "embed": _qspec(params["embed"], P("tp", None), per_row=True),
        "layers": {name: _qspec(leaf, layer_specs[name])
                   for name, leaf in params["layers"].items()},
        "final_norm": P(None),
    }
    if "lm_head" in params:
        specs["lm_head"] = _qspec(params["lm_head"], P(None, "tp"))
    return specs


def param_shardings(mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        param_pspecs(params),
                        is_leaf=lambda x: isinstance(x, P))


def cache_pspec() -> P:
    """KV pool [L, N, Hkv, Bs, D]: blocks over dp, kv heads over tp."""
    return P(None, "dp", "tp", None, None)


def cache_scale_pspec() -> P:
    """int8-KV dequant scales [L, N, Hkv, Bs]: same placement as the
    pool minus the head-dim axis (models/kv.py)."""
    return P(None, "dp", "tp", None)


def shard_params(mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
    """Place an (unsharded) params pytree onto the mesh."""
    return jax.device_put(params, param_shardings(mesh, params))
