"""Load HuggingFace Llama-family checkpoints into the stacked-params layout.

Accepts either a state-dict-like mapping (name -> numpy/torch tensor) or a
checkpoint directory (safetensors preferred, torch .bin fallback). Torch is
used only as a host-side file reader — nothing torch touches the device.

HF stores projections as [out, in]; we store [in, out] (x @ W), so every
projection is transposed on load, and per-layer tensors are stacked along
the leading layer axis to match models/llama.py's scan layout.
"""

import glob
import json
import os
from typing import Any, Dict, Mapping

import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

_LAYER_MAP = {
    # our-name: (hf-suffix, transpose)
    "attn_norm": ("input_layernorm.weight", False),
    "q": ("self_attn.q_proj.weight", True),
    "k": ("self_attn.k_proj.weight", True),
    "v": ("self_attn.v_proj.weight", True),
    "o": ("self_attn.o_proj.weight", True),
    "mlp_norm": ("post_attention_layernorm.weight", False),
    "gate": ("mlp.gate_proj.weight", True),
    "up": ("mlp.up_proj.weight", True),
    "down": ("mlp.down_proj.weight", True),
}


def _to_numpy(t: Any) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (possibly bf16, which numpy can't represent) — go via fp32
    return t.detach().to(dtype=__import__("torch").float32).cpu().numpy()


def params_from_state_dict(cfg: ModelConfig, sd: Mapping[str, Any]) -> Dict:
    """Build the stacked-params pytree from an HF LlamaForCausalLM state dict."""
    import jax.numpy as jnp

    if cfg.mla or cfg.first_dense_layers:
        # the tree is there (models/llama._init_params_mla); what is
        # missing is the checkpoint's naming (q_a_proj, kv_a_proj_with_mqa,
        # kv_b_proj, e_score_correction_bias, shared_experts) and the
        # permutation of its INTERLEAVED rotary columns into the
        # half-split layout ops/rope.py turns
        raise NotImplementedError(
            f"loading a checkpoint of the {cfg.moe_naming!r} family "
            f"(latent attention, leading dense layers) is not "
            f"supported yet: {cfg.name} runs on seeded random weights")

    def get(name: str, bare: bool = False) -> np.ndarray:
        return _to_numpy(_lookup(sd, name, bare=bare))

    def cast(x: np.ndarray, transpose: bool) -> Any:
        if transpose:
            x = x.T
        return jnp.asarray(x, dtype=cfg.dtype)

    layer_map = dict(_LAYER_MAP)
    # which of the two namings of sandwich norms the CHECKPOINT uses is
    # read off its own tensor names, not off another field of the model
    if cfg.sandwich_norms and any(
            f"{prefix}layers.0.input_layernorm_2.weight" in sd
            for prefix in ("model.", "")):
        # Ouro's norm naming (modeling_ouro.py): the two pre-norms keep
        # Llama's names and each sandwich norm is its pre-norm's "_2"
        layer_map["post_attn_norm"] = ("input_layernorm_2.weight", False)
        layer_map["post_mlp_norm"] = (
            "post_attention_layernorm_2.weight", False)
    elif cfg.sandwich_norms:
        # Gemma-2 norm naming: post_attention_layernorm is the SANDWICH
        # post-attn norm (not the MLP pre-norm as in Llama), the MLP
        # pre-norm is pre_feedforward_layernorm, and there is a
        # post_feedforward_layernorm too
        layer_map["mlp_norm"] = ("pre_feedforward_layernorm.weight",
                                 False)
        layer_map["post_attn_norm"] = (
            "post_attention_layernorm.weight", False)
        layer_map["post_mlp_norm"] = (
            "post_feedforward_layernorm.weight", False)
    if cfg.attention_bias:
        # Qwen2: q/k/v projection biases ([out] vectors; no transpose)
        layer_map.update({
            "q_bias": ("self_attn.q_proj.bias", False),
            "k_bias": ("self_attn.k_proj.bias", False),
            "v_bias": ("self_attn.v_proj.bias", False),
        })
    if cfg.num_experts:
        # Mixtral block_sparse_moe replaces the dense MLP (stacked along
        # a leading expert axis; w1=gate, w3=up, w2=down)
        for name in ("gate", "up", "down"):
            del layer_map[name]
    layers: Dict[str, Any] = {}
    for ours, (suffix, transpose) in layer_map.items():
        stacked = np.stack(
            [get(f"layers.{i}.{suffix}") for i in range(cfg.num_layers)])
        if transpose:
            stacked = np.swapaxes(stacked, -1, -2)
        layers[ours] = jnp.asarray(stacked, dtype=cfg.dtype)
    if cfg.num_experts:
        # Mixtral: block_sparse_moe.{gate,experts.N.w1/w3/w2};
        # Qwen2-MoE: mlp.{gate,experts.N.gate_proj/up_proj/down_proj}
        # + an always-on shared expert
        qwen_moe = cfg.moe_naming == "qwen2"
        prefix = "mlp" if qwen_moe else "block_sparse_moe"
        moe_map = ({"gate": "gate_proj", "up": "up_proj",
                    "down": "down_proj"} if qwen_moe
                   else {"gate": "w1", "up": "w3", "down": "w2"})
        for ours, hf in moe_map.items():
            stacked = np.stack([
                np.stack([
                    get(f"layers.{i}.{prefix}.experts.{e}.{hf}.weight").T
                    for e in range(cfg.num_experts)])
                for i in range(cfg.num_layers)])     # [L, E, in, out]
            layers[ours] = jnp.asarray(stacked, dtype=cfg.dtype)
        router = np.stack(
            [get(f"layers.{i}.{prefix}.gate.weight").T
             for i in range(cfg.num_layers)])        # [L, h, E]
        layers["router"] = jnp.asarray(router, dtype=cfg.dtype)
        if qwen_moe and cfg.shared_expert_size:
            for ours, hf in (("s_gate", "gate_proj"), ("s_up", "up_proj"),
                             ("s_down", "down_proj")):
                stacked = np.stack([
                    get(f"layers.{i}.mlp.shared_expert.{hf}.weight").T
                    for i in range(cfg.num_layers)])
                layers[ours] = jnp.asarray(stacked, dtype=cfg.dtype)
            sg = np.stack(
                [get(f"layers.{i}.mlp.shared_expert_gate.weight").T
                 for i in range(cfg.num_layers)])    # [L, h, 1]
            layers["s_gate_w"] = jnp.asarray(sg, dtype=cfg.dtype)

    params = {
        "embed": cast(get("embed_tokens.weight"), False),
        "layers": layers,
        "final_norm": cast(get("norm.weight"), False),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = cast(get("lm_head.weight", bare=True), True)
    if cfg.exit_gate:
        # Linear(hidden, 1) and its bias, float32 as init_params makes them
        params["exit_gate"] = jnp.asarray(
            get("early_exit_gate.weight").reshape(-1), jnp.float32)
        params["exit_gate_bias"] = jnp.asarray(
            get("early_exit_gate.bias").reshape(()), jnp.float32)
    return params


def _lookup(sd: Mapping[str, Any], name: str, bare: bool = False) -> Any:
    candidates = [name] if bare else []
    candidates += [f"model.{name}", name]
    for c in candidates:
        if c in sd:
            return sd[c]
    raise KeyError(f"missing weight {name!r}")


def read_state_dict(path: str) -> Dict[str, Any]:
    """Raw tensors from an HF checkpoint dir (safetensors or .bin)."""
    st_files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    sd: Dict[str, Any] = {}
    if st_files:
        from safetensors.numpy import load_file
        for f in st_files:
            sd.update(load_file(f))
    else:
        import torch
        for f in sorted(glob.glob(os.path.join(path, "*.bin"))):
            sd.update(torch.load(f, map_location="cpu", weights_only=True))
    if not sd:
        raise FileNotFoundError(f"no weights (*.safetensors|*.bin) in {path}")
    logger.info("loaded %d tensors from %s", len(sd), path)
    return sd


def load_checkpoint(cfg: ModelConfig, path: str) -> Dict:
    """Load params from an HF checkpoint directory on disk."""
    if cfg.layer_pattern:
        raise ValueError(
            f"{cfg.name}: the checkpoint loader is not supported on a "
            f"model with two kinds of mixer (state pages): the "
            f"published tensors' names and the grouped columns of "
            f"in_proj_qkvz are not mapped yet")
    return params_from_state_dict(cfg, read_state_dict(path))
