"""Llama-family decoder as pure JAX functions over a stacked-params pytree.

Design (TPU-first, not a torch translation):
- All L layers' weights are stacked along a leading layer axis and the
  forward pass runs ``lax.scan`` over layers: one traced layer body, O(1)
  compile time in depth.
- Weights live in bf16 (MXU-native); norms/softmax/logits in fp32.
- Two entry points: ``forward`` (incremental, serving; appends to and
  attends over the paged KV pool through models/kv.py) and
  ``forward_train`` (full-sequence, no cache: the reference forward the
  numerics tests and the on-chip benchmark compare against; ``encode``
  is the same without the LM head, for the embeddings endpoints).
- Sharding is NOT baked in here — parallel/sharding.py assigns
  PartitionSpecs to this pytree by path (megatron-style column/row rules),
  so the same model code runs single-chip or on any mesh.

The reference repo contains no model code (models are strings passed to
``vllm serve``, reference: helm/templates/deployment-vllm-multi.yaml:57-64);
this module is the TPU-native engine's compute core.
"""

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import lora, quant
from production_stack_tpu.models.config import SUBLAYER_KINDS, ModelConfig
from production_stack_tpu.models.kv import KVCache
from production_stack_tpu.ops import gdn, mamba, mamba2, moe, retention
from production_stack_tpu.ops.attention import causal_attention
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.ops.rope import apply_rope, rope_table

Params = Dict[str, Any]


class LoopWork(NamedTuple):
    """What a looped model's passes (cfg.loop_steps > 1) counted:
    ``passes_run`` int32, the passes its valid tokens ran (every one
    runs them all: loop_steps a token), ``row_steps`` int32, those
    tokens, and ``exit_mass`` float32 [loop_steps], their summed share
    of the exit distribution a pass (p_t = lambda_t prod_{j<t}
    (1 - lambda_j), the rest on the last)."""
    passes_run: jnp.ndarray
    row_steps: jnp.ndarray
    exit_mass: jnp.ndarray


class Work(NamedTuple):
    """What a forward counted beside its logits, by named member, each
    None where the model has no such part (and None for the whole where
    it has none at all): the experts' work summed over the layers
    (ops/moe.Work's four members under their names) and a looped
    model's passes."""
    experts_read: Optional[jnp.ndarray] = None
    expert_rows: Optional[jnp.ndarray] = None
    held_rows: Optional[jnp.ndarray] = None
    rounds: Optional[jnp.ndarray] = None
    loop: Optional[LoopWork] = None


def _counted(experts: Optional[moe.Work],
             loop: Optional[LoopWork] = None) -> Optional[Work]:
    """The experts' summed work and a looped model's passes as the one
    ``Work`` a forward hands back; None where it counted neither."""
    return (None if experts is None and loop is None
            else Work(*(experts or ()), loop=loop))


# random weights only (init_params): what the two SANDWICH norms of a
# looped stack start at. A branch whose output is normed joins the
# stream at the norm's weight whatever its projections' scale, so the
# depth-scaled initialisation of residual branches (GPT-2's
# 1/sqrt(2 L) on the output projections: 0.102 at Ouro-2.6B's 48
# layers) has to stand here to take effect; at one, four passes over
# the same layers pile up a stream that no precision tells apart
# (PERF.md section 2, PR 56)
LOOPED_SANDWICH_NORM_INIT = 0.1


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "quantizer",
                                             "std"))
def _random_leaf(key: jax.Array, *, shape: Tuple[int, ...], dtype,
                 quantizer=None, std: float = 0.02):
    """One weight leaf, built (and quantized) in one executable: the
    float32 draw fuses into the cast, so the transient is the leaf in
    ``dtype`` plus its int8 copy — never a float32 tensor, never a
    second full-precision tree."""
    # reduce_precision to float32's own 8/23 bits is an exact no-op that
    # XLA does not reassociate across: without it the 0.02 is folded
    # into the draw's own sqrt(2) and a quarter of the values move by
    # one float32 step — enough to flip a near-tied greedy token in the
    # seeded parity tests, which were written against the unfused
    # ``normal(key) * 0.02``
    w = jax.lax.reduce_precision(
        jax.random.normal(key, shape, jnp.float32), 8, 23) * std
    # round to ``dtype`` where XLA cannot elide it either: a bare
    # f32 -> bf16 -> f32 convert pair in front of the quantizer is
    # simplified away, and the int8 leaf would not be that of the
    # bf16 weights
    info = jnp.finfo(dtype)
    w = jax.lax.reduce_precision(w, info.nexp, info.nmant).astype(dtype)
    return w if quantizer is None else quantizer(w)


def init_params(cfg: ModelConfig, key: jax.Array,
                quantization: Optional[str] = None) -> Params:
    """Random init (normal 0.02) in cfg.dtype, stacked-layer layout.

    ``quantization="int8"`` quantizes every leaf models/quant.py would
    AS IT IS MADE — what ``quant.quantize_params`` gives over the plain
    tree (to a rounding step), without that tree ever existing. A 7B-width model is
    14.5 GB in bf16: built tree-then-tree it cannot reach its 7.3 GB
    int8 form on a 16 GB chip; leaf by leaf the peak is the finished
    leaves plus one leaf's transient."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, cfg.num_layers
    keys = iter(jax.random.split(key, 16))
    if quantization not in (None, "int8"):
        raise ValueError(f"quantization={quantization!r} unsupported")

    def w(k, shape, *path, std=0.02):
        # path = the leaf's place in the tree: quant.leaf_quantizer
        # holds the one rule for which leaves quantize, and how
        q = quant.leaf_quantizer(path) if quantization else None
        return _random_leaf(k, shape=shape, dtype=cfg.dtype, quantizer=q,
                            std=std)

    if cfg.mla:
        return _init_params_mla(cfg, key, w)
    if cfg.gdn_layers:
        return _init_params_hybrid(cfg, key, w)
    if cfg.layer_plan:
        return (_init_params_sublayers if cfg.sublayer_plan
                else _init_params_plan)(cfg, key, w)
    norm_init = jnp.zeros if cfg.rms_norm_offset else jnp.ones
    E = cfg.num_experts
    params: Params = {
        "embed": w(next(keys), (v, h), "embed"),
        "layers": {
            "attn_norm": norm_init((L, h), cfg.dtype),
            "q": w(next(keys), (L, h, nh * hd), "layers", "q"),
            "k": w(next(keys), (L, h, nkv * hd), "layers", "k"),
            "v": w(next(keys), (L, h, nkv * hd), "layers", "v"),
            "o": w(next(keys), (L, nh * hd, h), "layers", "o"),
            "mlp_norm": norm_init((L, h), cfg.dtype),
        },
        "final_norm": norm_init((h,), cfg.dtype),
    }
    if cfg.sandwich_norms:
        # post-attention and post-feedforward norms too (Gemma-2's as
        # every other norm; a looped stack's: LOOPED_SANDWICH_NORM_INIT)
        post = norm_init((L, h), cfg.dtype) if cfg.loop_steps == 1 \
            else jnp.full((L, h), LOOPED_SANDWICH_NORM_INIT, cfg.dtype)
        params["layers"]["post_attn_norm"] = post
        params["layers"]["post_mlp_norm"] = post
    # key order matters: dense models must draw gate/up/down from the
    # same key positions as before MoE existed (seeded tests pin outputs)
    if E:
        mi = cfg.moe_intermediate_size or i
        params["layers"].update({
            "gate": w(next(keys), (L, E, h, mi), "layers", "gate"),
            "up": w(next(keys), (L, E, h, mi), "layers", "up"),
            "down": w(next(keys), (L, E, mi, h), "layers", "down"),
            "router": w(next(keys), (L, h, E), "layers", "router"),
        })
        if cfg.shared_expert_size:
            si = cfg.shared_expert_size
            params["layers"].update({
                "s_gate": w(next(keys), (L, h, si), "layers", "s_gate"),
                "s_up": w(next(keys), (L, h, si), "layers", "s_up"),
                "s_down": w(next(keys), (L, si, h), "layers", "s_down"),
                "s_gate_w": w(next(keys), (L, h, 1), "layers", "s_gate_w"),
            })
    else:
        params["layers"].update({
            "gate": w(next(keys), (L, h, i), "layers", "gate"),
            "up": w(next(keys), (L, h, i), "layers", "up"),
            "down": w(next(keys), (L, i, h), "layers", "down"),
        })
    if cfg.attention_bias:
        # Qwen2: biases on the q/k/v projections only
        params["layers"]["q_bias"] = jnp.zeros((L, nh * hd), cfg.dtype)
        params["layers"]["k_bias"] = jnp.zeros((L, nkv * hd), cfg.dtype)
        params["layers"]["v_bias"] = jnp.zeros((L, nkv * hd), cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (h, v), "lm_head")
    if cfg.qk_norm:
        params["layers"]["q_norm"] = norm_init((L, hd), cfg.dtype)
        params["layers"]["k_norm"] = norm_init((L, hd), cfg.dtype)
    if cfg.ret_layers:
        # a power retention mixer's gate, one number a key-value head:
        # log g = logsigmoid(x W_g + b_g). The bias is drawn U(2, 8), so
        # that the heads' memories run from about eight tokens
        # (sigmoid(2) = 0.88) to about three thousand (sigmoid(8)); at
        # zero every head would forget all but its last few tokens and
        # a carried state would hold nothing to compare
        params["layers"]["ret_gate"] = w(next(keys), (L, h, nkv), "layers",
                                         "ret_gate")
        params["layers"]["ret_gate_bias"] = jax.random.uniform(
            next(keys), (L, nkv), jnp.float32, 2.0, 8.0)
    if cfg.exit_gate:
        # a looped model's exit gate, Linear(hidden, 1): float32, never
        # quantized (drawn last: every other leaf is the plain model's)
        params["exit_gate"] = 0.02 * jax.random.normal(
            next(keys), (h,), jnp.float32)
        params["exit_gate_bias"] = jnp.zeros((), jnp.float32)
    return params


def _init_params_mla(cfg: ModelConfig, key: jax.Array, w) -> Params:
    """init_params for a latent-attention model with a layer plan
    (GLM-4.7-Flash): ``dense_layers`` holds the leading
    first_dense_layers layers (attention + a dense MLP), ``layers`` the
    expert layers that the scan runs, each group stacked on its own
    leading axis. Every leaf is drawn from the seed, the router's
    selection bias too and NOT zero (normal, sd 0.1: a zero bias would
    make selecting with and without it the same, and sd 0.02 moves no
    selection); it stays float32, as the publication keeps it. Every
    weight at sd 0.02, but the routed experts' output projection where
    the configuration states another (cfg.routed_down_init_std)."""
    h, v, nh = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    E, mi = cfg.num_experts, cfg.moe_intermediate_size
    Ld = cfg.first_dense_layers
    Le = cfg.num_layers - Ld
    # (a model without the indexer draws from the 32 it always did)
    keys = iter(jax.random.split(key, 48 if cfg.index_topk else 32))

    def indexer(L, group):
        # learned sparse attention (ops/dsa.py): index queries from the
        # query bottleneck, one index key a token from the layer's
        # input through a LayerNorm, a weight a head from the input
        if not cfg.index_topk:
            return {}
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        return {
            "idx_q": w(next(keys), (L, qr, hi * di), group, "idx_q"),
            "idx_k": w(next(keys), (L, h, di), group, "idx_k"),
            "idx_k_norm": jnp.ones((L, di), cfg.dtype),
            "idx_k_norm_bias": jnp.zeros((L, di), cfg.dtype),
            "idx_w": w(next(keys), (L, h, hi), group, "idx_w"),
        }

    def attention(L, group):
        return {
            **indexer(L, group),
            "attn_norm": jnp.ones((L, h), cfg.dtype),
            "q_a": w(next(keys), (L, h, qr), group, "q_a"),
            "q_a_norm": jnp.ones((L, qr), cfg.dtype),
            "q_b": w(next(keys), (L, qr, nh * cfg.head_dim_), group, "q_b"),
            "kv_a": w(next(keys), (L, h, cfg.latent_dim), group, "kv_a"),
            "kv_a_norm": jnp.ones((L, kr), cfg.dtype),
            "kv_b": w(next(keys), (L, kr, nh * (cfg.qk_nope_head_dim
                                                + cfg.v_head_dim)),
                      group, "kv_b"),
            "o": w(next(keys), (L, nh * cfg.v_head_dim, h), group, "o"),
            "mlp_norm": jnp.ones((L, h), cfg.dtype),
        }

    params: Params = {
        "embed": w(next(keys), (v, h), "embed"),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": w(next(keys), (h, v), "lm_head"),
    }
    if cfg.tie_word_embeddings:
        del params["lm_head"]
    if Ld:
        i = cfg.intermediate_size
        params["dense_layers"] = {
            **attention(Ld, "dense_layers"),
            "gate": w(next(keys), (Ld, h, i), "dense_layers", "gate"),
            "up": w(next(keys), (Ld, h, i), "dense_layers", "up"),
            "down": w(next(keys), (Ld, i, h), "dense_layers", "down"),
        }
    layers = {
        **attention(Le, "layers"),
        "gate": w(next(keys), (Le, E, h, mi), "layers", "gate"),
        "up": w(next(keys), (Le, E, h, mi), "layers", "up"),
        "down": w(next(keys), (Le, E, mi, h), "layers", "down",
                  std=cfg.routed_down_init_std or 0.02),
        # the router scores every expert of the layer, whichever are
        # held here (cfg.router_experts_)
        "router": w(next(keys), (Le, h, cfg.router_experts_), "layers",
                    "router"),
    }
    if cfg.router_bias:
        layers["router_bias"] = 0.1 * jax.random.normal(
            next(keys), (Le, cfg.router_experts_), jnp.float32)
    if cfg.shared_expert_size:
        si = cfg.shared_expert_size
        layers.update({
            "s_gate": w(next(keys), (Le, h, si), "layers", "s_gate"),
            "s_up": w(next(keys), (Le, h, si), "layers", "s_up"),
            "s_down": w(next(keys), (Le, si, h), "layers", "s_down"),
        })
        if cfg.shared_expert_gate:
            layers["s_gate_w"] = w(next(keys), (Le, h, 1), "layers",
                                   "s_gate_w")
    params["layers"] = layers
    return params


def _init_params_hybrid(cfg: ModelConfig, key: jax.Array, w) -> Params:
    """init_params for a model whose period holds two kinds of mixer
    (Qwen3-Next: cfg.layer_pattern). Three groups, each stacked on its
    own leading axis: ``layers`` holds what every layer has (its two
    norms, the router, the experts, the gated shared expert) for all
    num_layers, ``gdn_layers`` the Gated DeltaNet mixers and
    ``attn_layers`` the gated attention mixers, each in the model's
    order; the scan takes a period of each (``forward``). ``qkvz`` is
    [q | k | v | z] (the checkpoint groups the columns by key head, a
    permutation that is a loader's business), ``ba`` [b | a], ``conv``
    [taps, channels] with the last tap on the token itself. A_log =
    log(U(0, 16)), dt_bias one and the norms at their published
    initialisation (zero-centred norms zero, the gated norm one)."""
    h, v = cfg.hidden_size, cfg.vocab_size
    L, Lg, La = cfg.num_layers, cfg.gdn_layers, cfg.attn_layers
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    hv, dv, ch = cfg.gdn_value_heads, cfg.gdn_value_dim, cfg.gdn_channels
    E, mi, si = cfg.num_experts, cfg.moe_intermediate_size, \
        cfg.shared_expert_size
    keys = iter(jax.random.split(key, 32))
    params: Params = {
        "embed": w(next(keys), (v, h), "embed"),
        "final_norm": jnp.zeros((h,), cfg.dtype),
        "lm_head": w(next(keys), (h, v), "lm_head"),
        "gdn_layers": {
            "qkvz": w(next(keys), (Lg, h, ch + hv * dv), "gdn_layers",
                      "qkvz"),
            "ba": w(next(keys), (Lg, h, 2 * hv), "gdn_layers", "ba"),
            "conv": w(next(keys), (Lg, cfg.gdn_conv, ch), "gdn_layers",
                      "conv"),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (Lg, hv), jnp.float32, 1e-3, 16.0)),
            "dt_bias": jnp.ones((Lg, hv), jnp.float32),
            "gdn_norm": jnp.ones((Lg, dv), cfg.dtype),
            "out": w(next(keys), (Lg, hv * dv, h), "gdn_layers", "out"),
        },
        "attn_layers": {
            "q": w(next(keys), (La, h, 2 * nh * hd), "attn_layers", "q"),
            "k": w(next(keys), (La, h, nkv * hd), "attn_layers", "k"),
            "v": w(next(keys), (La, h, nkv * hd), "attn_layers", "v"),
            "o": w(next(keys), (La, nh * hd, h), "attn_layers", "o"),
            "q_norm": jnp.zeros((La, hd), cfg.dtype),
            "k_norm": jnp.zeros((La, hd), cfg.dtype),
        },
        "layers": {
            "attn_norm": jnp.zeros((L, h), cfg.dtype),
            "mlp_norm": jnp.zeros((L, h), cfg.dtype),
            "gate": w(next(keys), (L, E, h, mi), "layers", "gate"),
            "up": w(next(keys), (L, E, h, mi), "layers", "up"),
            "down": w(next(keys), (L, E, mi, h), "layers", "down",
                      std=cfg.routed_down_init_std or 0.02),
            "router": w(next(keys), (L, h, cfg.router_experts_), "layers",
                        "router"),
            "s_gate": w(next(keys), (L, h, si), "layers", "s_gate"),
            "s_up": w(next(keys), (L, h, si), "layers", "s_up"),
            "s_down": w(next(keys), (L, si, h), "layers", "s_down"),
            "s_gate_w": w(next(keys), (L, h, 1), "layers", "s_gate_w"),
        },
    }
    return params


# the group of stacked parameters each mixer of a layer plan reads its
# own from (cfg.layer_plan), beside ``layers`` (what every block has)
PLAN_GROUPS = {"mamba": "mamba_layers", "mamba_mem": "mamba_layers",
               "swa": "diff_layers", "full": "diff_layers",
               "gmu": "gmu_layers", "cross": "cross_layers",
               # blocks that are ONE sublayer (Nemotron-H)
               "mamba2": "mamba2_layers", "attn": "gqa_layers",
               "moe": "moe_layers"}


def _init_params_plan(cfg: ModelConfig, key: jax.Array, w) -> Params:
    """init_params for a decoder-hybrid-decoder (Phi-4-mini-flash:
    cfg.layer_plan). ``layers`` holds what every block has (two
    LayerNorms with biases, the fused gate-up ``fc1`` and ``fc2``) for
    all num_layers; ``mamba_layers``, ``diff_layers`` (differential
    attention with its own K/V: the window layers and the full one),
    ``gmu_layers`` and ``cross_layers`` the mixers, each in the model's
    order. ``conv`` is [taps, channels] with the last tap on the token
    itself; ``A_log`` [state, channels] = log(1..state) a channel and
    ``D`` one (Mamba's S4D-real initialisation); ``dt_bias`` the
    inverse softplus of dt log-uniform in [0.001, 0.1]; the lambda
    vectors normal(0, 0.1); norms one, biases zero; the head is the
    embedding."""
    h, v, i, L = (cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size,
                  cfg.num_layers)
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    di, ds, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    Lm, La = cfg.mamba_layers, cfg.attn_layers
    Lg, Lc = cfg.kind_layers("gmu"), cfg.kind_layers("cross")
    keys = iter(jax.random.split(key, 32))
    f32 = jnp.float32

    def lambdas(n):
        return {name: 0.1 * jax.random.normal(next(keys), (n, hd), f32)
                for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2")}

    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, di), f32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {
        "embed": w(next(keys), (v, h), "embed"),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "final_norm_bias": jnp.zeros((h,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, h), cfg.dtype),
            "attn_norm_bias": jnp.zeros((L, h), cfg.dtype),
            "mlp_norm": jnp.ones((L, h), cfg.dtype),
            "mlp_norm_bias": jnp.zeros((L, h), cfg.dtype),
            "fc1": w(next(keys), (L, h, 2 * i), "layers", "fc1"),
            "fc2": w(next(keys), (L, i, h), "layers", "fc2"),
        },
        "mamba_layers": {
            "in_proj": w(next(keys), (Lm, h, 2 * di), "mamba_layers",
                         "in_proj"),
            "conv": w(next(keys), (Lm, cfg.mamba_d_conv, di),
                      "mamba_layers", "conv"),
            "conv_bias": jnp.zeros((Lm, di), cfg.dtype),
            "x_proj": w(next(keys), (Lm, di, r + 2 * ds), "mamba_layers",
                        "x_proj"),
            "dt_proj": w(next(keys), (Lm, r, di), "mamba_layers",
                         "dt_proj"),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, ds + 1, dtype=f32))[None, :, None], (Lm, ds, di)),
            "D": jnp.ones((Lm, di), f32),
            "out_proj": w(next(keys), (Lm, di, h), "mamba_layers",
                          "out_proj"),
        },
        "diff_layers": {
            "qkv": w(next(keys), (La, h, (nh + 2 * nkv) * hd),
                     "diff_layers", "qkv"),
            "qkv_bias": jnp.zeros((La, (nh + 2 * nkv) * hd), cfg.dtype),
            "o": w(next(keys), (La, nh * hd, h), "diff_layers", "o"),
            "o_bias": jnp.zeros((La, h), cfg.dtype),
            "subln": jnp.ones((La, 2 * hd), cfg.dtype),
            **lambdas(La),
        },
        "gmu_layers": {
            "in_proj": w(next(keys), (Lg, h, di), "gmu_layers", "in_proj"),
            "out_proj": w(next(keys), (Lg, di, h), "gmu_layers",
                          "out_proj"),
        },
        "cross_layers": {
            "q": w(next(keys), (Lc, h, nh * hd), "cross_layers", "q"),
            "q_bias": jnp.zeros((Lc, nh * hd), cfg.dtype),
            "o": w(next(keys), (Lc, nh * hd, h), "cross_layers", "o"),
            "o_bias": jnp.zeros((Lc, h), cfg.dtype),
            "subln": jnp.ones((Lc, 2 * hd), cfg.dtype),
            **lambdas(Lc),
        },
    }


def _stack_by_layer(make, layers: int):
    """A stack [layers, ...] whose every layer ``make(l)`` gives (an
    array, or its {"w8", "scale"} leaf), written a layer at a time into
    one buffer that is donated from write to write: the transient is
    ONE layer's, where the stack made whole holds its float copy beside
    its int8 one (3.7 G parameters of experts: 11 GB beside the
    finished leaves)."""
    def put(buf, leaf, at):
        return jax.tree.map(
            lambda b, a: jax.lax.dynamic_update_index_in_dim(b, a, at, 0),
            buf, leaf)
    put = jax.jit(put, donate_argnums=0)
    buf = jax.tree.map(lambda a: jnp.zeros((layers,) + a.shape, a.dtype),
                       jax.eval_shape(make, 0))
    for at in range(layers):
        buf = put(buf, make(at), jnp.int32(at))
    return buf


def _init_params_sublayers(cfg: ModelConfig, key: jax.Array, w) -> Params:
    """init_params for a model whose blocks are ONE sublayer each
    (Nemotron-H: cfg.sublayer_plan). ``layers`` holds every block's norm;
    ``mamba2_layers``, ``gqa_layers`` and ``moe_layers`` the sublayers,
    each stacked in the model's order. A Mamba-2 mixer as published
    initialises it: ``A_log`` = log(1..heads), ``D`` one, ``dt_bias``
    the inverse softplus of dt log-uniform in [0.001, 0.1] floored at
    1e-4, the depthwise convolution and its bias uniform in +-
    taps ** -0.5 (PyTorch's Conv1d); ``conv`` [taps, channels] with the
    last tap on the token itself, the channels x, then every group's B,
    then every group's C; ``in_proj``'s columns z, x B C, dt. The
    experts ``up`` [Le, E, h, s] and ``down`` [Le, E, s, h] are STORED
    s = cfg.moe_stored_size wide, zero beyond the published
    moe_intermediate_size, and made a layer at a time."""
    h, v, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    di, H, ch = cfg.mamba_d_inner, cfg.mamba_heads, cfg.mamba_conv_channels
    taps, E = cfg.mamba_d_conv, cfg.num_experts
    mi, ms, si = (cfg.moe_intermediate_size, cfg.moe_stored_size,
                  cfg.shared_expert_size)
    Lm, La, Le = (cfg.kind_layers("mamba2"), cfg.kind_layers("attn"),
                  cfg.kind_layers("moe"))
    keys = iter(jax.random.split(key, 24))
    f32 = jnp.float32
    dt = jnp.maximum(jnp.exp(
        jax.random.uniform(next(keys), (Lm, H), f32)
        * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)), 1e-4)
    bound = taps ** -0.5

    def widen_up(a):    # the last axis: w8 [E, h, mi], scale [E, mi]
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, ms - mi)])

    def widen_down(a):  # the rows of [E, mi, h]; a scale [E, h] as it is
        return jnp.pad(a, [(0, 0), (0, ms - mi), (0, 0)]) \
            if a.ndim == 3 else a

    def experts(k, name, shape, widen, std):
        """An expert stack a layer at a time, zero beyond ``mi``."""
        layer_keys = jax.random.split(k, Le)

        return _stack_by_layer(
            lambda at: jax.tree.map(widen, w(
                layer_keys[at], shape, "moe_layers", name, std=std)), Le)

    return {
        "embed": w(next(keys), (v, h), "embed"),
        "lm_head": w(next(keys), (h, v), "lm_head"),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": {"norm": jnp.ones((L, h), cfg.dtype)},
        "mamba2_layers": {
            "in_proj": w(next(keys), (Lm, h, di + ch + H), "mamba2_layers",
                         "in_proj"),
            "conv": jax.random.uniform(next(keys), (Lm, taps, ch), f32,
                                       -bound, bound).astype(cfg.dtype),
            "conv_bias": jax.random.uniform(
                next(keys), (Lm, ch), f32, -bound, bound).astype(cfg.dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, H + 1, dtype=f32)), (Lm, H)),
            "D": jnp.ones((Lm, H), f32),
            "gate_norm": jnp.ones((Lm, di), cfg.dtype),
            "out_proj": w(next(keys), (Lm, di, h), "mamba2_layers",
                          "out_proj"),
        },
        "gqa_layers": {
            "q": w(next(keys), (La, h, nh * hd), "gqa_layers", "q"),
            "k": w(next(keys), (La, h, nkv * hd), "gqa_layers", "k"),
            "v": w(next(keys), (La, h, nkv * hd), "gqa_layers", "v"),
            "o": w(next(keys), (La, nh * hd, h), "gqa_layers", "o"),
        },
        "moe_layers": {
            "router": w(next(keys), (Le, h, cfg.router_experts_),
                        "moe_layers", "router"),
            # a trained selection bias is not zero; sd 0.1 moves the
            # selection (sigmoid scores lie around 0.5)
            "router_bias": 0.1 * jax.random.normal(
                next(keys), (Le, cfg.router_experts_), f32),
            "up": experts(next(keys), "up", (E, h, mi), widen_up, 0.02),
            "down": experts(next(keys), "down", (E, mi, h), widen_down,
                            cfg.routed_down_init_std or 0.02),
            "s_up": w(next(keys), (Le, h, si), "moe_layers", "s_up"),
            "s_down": w(next(keys), (Le, si, h), "moe_layers", "s_down"),
        },
    }


def _mamba_mixer(cfg: ModelConfig, hidden, lp: Params, state, state_ids,
                 starts, token_valid, state_layer):
    """A Mamba mixer (ops/mamba.py) on the normed input ``hidden``
    [B,T,H] -> (the mixer's output [B,T,H], its pre-gate ``y`` [B,T,Di]
    with the skip term, the state pools). state = the WHOLE pools (the
    float32 states [Lm,P,N,Di], the convolutions' inputs
    [Lm,P,taps-1,Di]), of which the rows' pages ``state_ids`` [B] of
    layer ``state_layer`` are read and written in place, as
    _gdn_layer's: a row none of whose positions is real names the trash
    page 0; positions that are not real trail the chunk and advance
    nothing (dt = 0; the convolution keeps its last REAL inputs); a row
    whose first position is 0 starts from a zero state. Scopes
    mamba_proj, mamba_conv, mamba_chunk_scan / mamba_recurrent_step
    (ops/mamba.selective_scan), mamba_gate, mamba_out_proj."""
    B, T, _ = hidden.shape
    di, ds, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    hs, conv = state
    ids = jnp.where(jnp.any(token_valid, axis=1), state_ids, 0)
    fresh = starts == 0
    f32 = jnp.float32
    with jax.named_scope("mamba_proj"):
        xz = quant.dequant_matmul(hidden, lp["in_proj"], dtype=f32,
                                  exact_scale=True)
        xs, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("mamba_conv"):
        prev = jnp.where(fresh[:, None, None], 0, conv[state_layer, ids])
        xs, new_conv = gdn.causal_conv(
            xs, lp["conv"], prev,
            jnp.sum(token_valid, axis=1, dtype=jnp.int32),
            bias=lp["conv_bias"])
        conv = conv.at[state_layer, ids].set(new_conv)
    with jax.named_scope("mamba_proj"):
        dbc = jnp.einsum("btd,dj->btj", xs.astype(hidden.dtype),
                         lp["x_proj"], preferred_element_type=f32)
        dt = jax.nn.softplus(jnp.einsum(
            "btr,rd->btd", dbc[..., :r].astype(hidden.dtype), lp["dt_proj"],
            preferred_element_type=f32) + lp["dt_bias"])
        dt = jnp.where(token_valid[..., None], dt, 0.0)
    y, hs = mamba.selective_scan(
        xs, dt, dbc[..., r:r + ds], dbc[..., r + ds:],
        -jnp.exp(lp["A_log"]), hs, ids, state_layer, fresh)
    with jax.named_scope("mamba_gate"):
        y = y + lp["D"] * xs
        gated = (y * jax.nn.silu(z)).astype(hidden.dtype)
    with jax.named_scope("mamba_out_proj"):
        out = quant.dequant_matmul(gated, lp["out_proj"], exact_scale=True)
    return out, y.astype(hidden.dtype), (hs, conv)


def _mamba2_mixer(cfg: ModelConfig, hidden, lp: Params, state, state_ids,
                  starts, token_valid, state_layer):
    """A Mamba-2 mixer (ops/mamba2.py) on the normed input ``hidden``
    [B,T,H] -> (the mixer's output [B,T,H], the state pools). state =
    the WHOLE pools (the float32 states [Lm,P,N,Di], the convolutions'
    inputs [Lm,P,taps-1,Ch]), of which the rows' pages ``state_ids``
    [B] of layer ``state_layer`` are read and written in place, by
    _mamba_mixer's conventions (the trash page for a row that is not
    real, dt = 0 where a position is not, a zero state at position 0).
    ``in_proj``'s columns are z, (x, B, C), dt; the convolution runs
    over x, B and C together; the output is the GROUP RMSNorm of y
    silu(z) (cfg.mamba_groups groups, one weight a channel). Scopes
    mamba2_proj, mamba2_conv, mamba2_chunk_scan / mamba2_recurrent_step
    (ops/mamba2.ssd_scan), mamba2_gate_norm, mamba2_out_proj; a prefill
    chunk on the kernels (ops/mamba2.chunk_mix) has the convolution,
    the scan, the gate and the norm inside mamba2_chunk_scan, and
    mamba2_conv holds only the convolution's new state."""
    B, T, _ = hidden.shape
    di, H, G, N = (cfg.mamba_d_inner, cfg.mamba_heads, cfg.mamba_groups,
                   cfg.mamba_d_state)
    ch = cfg.mamba_conv_channels
    hs, conv = state
    ids = jnp.where(jnp.any(token_valid, axis=1), state_ids, 0)
    fresh = starts == 0
    f32 = jnp.float32
    with jax.named_scope("mamba2_proj"):
        zxd = quant.dequant_matmul(hidden, lp["in_proj"], dtype=f32,
                                   exact_scale=True)
        z, xbc, dt = zxd[..., :di], zxd[..., di:di + ch], zxd[..., di + ch:]
        dt = jnp.where(token_valid[..., None],
                       jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
    if mamba2.mamba2_path(T, di, H, G, N) == mamba2.CHUNKED:
        # a prefill chunk on the kernels: everything between the two
        # projections is ONE kernel on in_proj's output where it lies
        prev = jnp.where(fresh[:, None, None], 0, conv[state_layer, ids])
        y, hs = mamba2.chunk_mix(
            zxd, dt, prev, lp["conv"], lp["conv_bias"],
            -jnp.exp(lp["A_log"]), lp["D"], lp["gate_norm"],
            cfg.rms_norm_eps, hs, ids, state_layer, fresh, hidden.dtype)
        with jax.named_scope("mamba2_conv"):
            conv = conv.at[state_layer, ids].set(mamba2.conv_tail(
                zxd, di, prev,
                jnp.sum(token_valid, axis=1, dtype=jnp.int32)))
        with jax.named_scope("mamba2_out_proj"):
            out = quant.dequant_matmul(y, lp["out_proj"], exact_scale=True)
        return out, (hs, conv)
    with jax.named_scope("mamba2_conv"):
        prev = jnp.where(fresh[:, None, None], 0, conv[state_layer, ids])
        xbc, new_conv = gdn.causal_conv(
            xbc, lp["conv"], prev,
            jnp.sum(token_valid, axis=1, dtype=jnp.int32),
            bias=lp["conv_bias"])
        conv = conv.at[state_layer, ids].set(new_conv)
        xs = xbc[..., :di]
        Bm, Cm = (xbc[..., di + j * G * N:di + (j + 1) * G * N].reshape(
            B, T, G, N).astype(hidden.dtype) for j in (0, 1))
    y, hs = mamba2.ssd_scan(xs.astype(hidden.dtype), dt, Bm, Cm,
                            -jnp.exp(lp["A_log"]), hs, ids, state_layer,
                            fresh)
    with jax.named_scope("mamba2_gate_norm"):
        y = (y + jnp.repeat(lp["D"], di // H) * xs) * jax.nn.silu(z)
        y = rms_norm(y.reshape(B, T, G, di // G),
                     lp["gate_norm"].reshape(G, di // G), cfg.rms_norm_eps)
        y = y.reshape(B, T, di).astype(hidden.dtype)
    with jax.named_scope("mamba2_out_proj"):
        out = quant.dequant_matmul(y, lp["out_proj"], exact_scale=True)
    return out, (hs, conv)


def _diff_attention(cfg: ModelConfig, hidden, lp: Params, kv, kv_len,
                    token_valid, block_tables, starts, positions, mesh,
                    layer, kv_layer, window, appends: bool):
    """Differential attention (Ye et al., arXiv 2410.05258) on the
    normed input ``hidden`` [B,T,H] -> (the heads' outputs [B,T,nh*hd],
    the pool). Query heads (2a, 2a+1) are q1_a, q2_a; key heads (2c,
    2c+1) are k1_c, k2_c and value heads (2c, 2c+1) ONE value V_c of
    twice the width; pair a reads c = a // 2:

        o_a = attn(q1_a, k1_c, V_c) - lambda attn(q2_a, k2_c, V_c)

    then RMSNorm over the pair's 2 hd values times (1 - lambda_init).
    The pool holds a token's K as [k1_c | k2_c] and its V as V_c, heads
    of 2 hd, exactly as the projection's columns lie (cfg.
    pool_kv_heads, cfg.pool_head_dim): with q1 padded to [q1 | 0] and
    q2 to [0 | q2] both softmaxes are PLAIN grouped-query calls of one
    paged kernel (four query heads a pool head), and the difference is
    taken after the call. ``appends``: the layer has K/V of its own
    (``qkv``) and writes them to pool layer ``kv_layer`` before it
    reads; else (a cross layer, ``q`` alone) it reads that pool layer,
    another layer's, and appends nothing. No rotary embedding. Scope
    diff_attention (with qkv_proj, kv_write, attention inside)."""
    B, T, _ = hidden.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    f32 = jnp.float32

    def proj(h, name):
        return quant.dequant_matmul(h, lp[name], exact_scale=True) \
            + lp[name + "_bias"]
    with jax.named_scope("qkv_proj"):
        if appends:
            qkv = proj(hidden, "qkv")
            q = qkv[..., :nh * hd]
            k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(
                B, T, nkv // 2, 2 * hd)
            v = qkv[..., (nh + nkv) * hd:].reshape(B, T, nkv // 2, 2 * hd)
        else:
            q = proj(hidden, "q")
        q = q.reshape(B, T, nh // 2, 2, hd)
        zero = jnp.zeros_like(q[..., 0, :])
        q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                       jnp.concatenate([zero, q[..., 1, :]], -1)],
                      axis=3).reshape(B, T, nh, 2 * hd)
    if appends:
        with jax.named_scope("kv_write"):
            kv = kv_pool.append(kv, k, v, block_tables, starts,
                                token_valid, kv_layer, mesh=mesh)
    with jax.named_scope("attention"):
        tables, first, at = block_tables, starts, positions
        if window:
            # a window layer reads the blocks its window and its chunk
            # touch and no other: with no rotary embedding only the
            # DISTANCE of a key matters, so the row's table is cut to
            # those blocks and its positions counted from the first of
            # them. The kernels skip a block outside the window anyway
            # (ops/pallas_paged.py), but walk a grid step for it: 256
            # blocks of a 16k bucket against 41
            Bs, MB = kv[0].shape[-2], block_tables.shape[1]
            few = -(-(window - 1 + T) // Bs) + 1
            # whole panels of the prefill kernel (512 keys a grid step:
            # ops/pallas_paged.prefill_tiles takes the widest panel that
            # DIVIDES the bucket, and 41 blocks divide by nothing: a
            # block a step, 5.5 ms a layer where 0.7 would do; PERF.md,
            # PR 50)
            panel = max(1, 512 // Bs)
            few = -(-few // panel) * panel
            if few < (MB if kv_len is None else min(-(-kv_len // Bs), MB)):
                lo = jnp.maximum(starts - (window - 1), 0) // Bs
                tables = jnp.take_along_axis(
                    block_tables,
                    jnp.clip(lo[:, None] + jnp.arange(few), 0, MB - 1),
                    axis=1)
                first, at = starts - lo * Bs, positions - (lo * Bs)[:, None]
                kv_len = few * Bs
        o = kv_pool.attend(q, kv, tables, first, at, kv_len, kv_layer,
                           window=window, scale=hd ** -0.5, softcap=None,
                           mesh=mesh)
    with jax.named_scope("diff_lambda"):
        init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, f32))
        lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
               - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init)
        o = o.astype(f32).reshape(B, T, nh // 2, 2, 2 * hd)
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = rms_norm(o, lp["subln"], cfg.rms_norm_eps) * (1.0 - init)
    return o.astype(hidden.dtype).reshape(B, T, nh * hd), kv


def _mamba2_sublayer(cfg: ModelConfig, hidden, lp: Params, pool, spool, at):
    out, spool = _mamba2_mixer(cfg, hidden, lp, spool, at["state_ids"],
                               at["starts"], at["token_valid"],
                               at["group_layer"])
    return out, pool, spool, None


def _gqa_sublayer(cfg: ModelConfig, hidden, lp: Params, pool, spool, at):
    """Grouped-query attention with NO rotary embedding (Nemotron-H:
    the Mamba blocks carry position) through the ordinary paged path:
    pool layer ``group_layer``. Scopes qkv_proj, kv_write, attention,
    o_proj."""
    B, T, _ = hidden.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    mm = functools.partial(quant.dequant_matmul, exact_scale=True)
    with jax.named_scope("qkv_proj"):
        q = mm(hidden, lp["q"]).reshape(B, T, nh, hd)
        k = mm(hidden, lp["k"]).reshape(B, T, nkv, hd)
        v = mm(hidden, lp["v"]).reshape(B, T, nkv, hd)
    with jax.named_scope("kv_write"):
        pool = kv_pool.append(pool, k, v, at["block_tables"], at["starts"],
                              at["token_valid"], at["group_layer"],
                              mesh=at["mesh"])
    with jax.named_scope("attention"):
        attn = kv_pool.attend(q, pool, at["block_tables"], at["starts"],
                              at["positions"], at["kv_len"],
                              at["group_layer"], window=None,
                              scale=hd ** -0.5, softcap=None,
                              mesh=at["mesh"])
    with jax.named_scope("o_proj"):
        return mm(attn.reshape(B, T, nh * hd), lp["o"]), pool, spool, None


def _expert_sublayer(cfg: ModelConfig, hidden, lp: Params, pool, spool, at):
    """The expert layer as a block's ONLY part: the held experts
    without a gate (ops/moe.moe_mlp ``gate`` None; their stacks read in
    place from ``expert_stacks`` at ``group_layer`` where the list or
    the grouped path runs) and the shared expert of the same form,
    added with no gate in front. Scopes moe_router / moe_experts /
    moe_combine (ops/moe.py), shared_expert."""
    B, T, H = hidden.shape
    stacks, valid = at["expert_stacks"], at["token_valid"]
    up, down = (lp[n] if stacks is None else stacks[n]
                for n in ("up", "down"))
    act = _activation(cfg)
    y, work = moe.moe_mlp(
        hidden.reshape(B * T, H), lp["router"], None, up, down,
        top_k=cfg.num_experts_per_tok,
        capacity_factor=cfg.moe_capacity_factor,
        capacity_tokens=at["moe_capacity_tokens"], act=act,
        valid=valid.reshape(B * T), renormalize=cfg.norm_topk_prob,
        exact=True if T == 1 else None,     # a decode step drops nothing
        layer=None if stacks is None else at["group_layer"], positions=T,
        router_score=cfg.router_score, router_bias=lp["router_bias"],
        routed_scale=cfg.routed_scaling_factor,
        expert_offset=cfg.expert_offset)
    with jax.named_scope("shared_expert"):
        inner = act(quant.dequant_matmul(hidden, lp["s_up"],
                                         dtype=jnp.float32,
                                         exact_scale=True))
        shared = quant.dequant_matmul(inner.astype(hidden.dtype),
                                      lp["s_down"], exact_scale=True)
    return y.reshape(B, T, H) + shared, pool, spool, work


# a one-sublayer block's part by kind: (cfg, the normed input, the
# block's parameters, the K/V pool, the state pools, the step's
# arguments) -> (its output, the pools, the experts' work or None)
SUBLAYERS = dict(zip(SUBLAYER_KINDS, (_mamba2_sublayer, _gqa_sublayer,
                                      _expert_sublayer)))


def _sublayer_block(cfg: ModelConfig, kind: str, x, lp: Params, pool,
                    spool, mem, at):
    """One block that is ONE sublayer (Nemotron-H): x += f(RMSNorm(x)),
    f by ``kind`` (SUBLAYERS). The memory (a decoder-hybrid-decoder's,
    part of the layer loop's carry) passes through. -> _plan_layer's
    results."""
    with jax.named_scope("norm"):
        hidden = rms_norm(x, lp["norm"], cfg.rms_norm_eps)
    out, pool, spool, work = SUBLAYERS[kind](cfg, hidden, lp, pool, spool,
                                             at)
    return x + out, pool, spool, mem, work


def _plan_layer(cfg: ModelConfig, kind: str, x, lp: Params, pool, spool,
                mem, at):
    """One block of a decoder-hybrid-decoder (cfg.layer_plan): x +=
    mixer(LN1(x)); x += fc2(up * silu(gate)), gate, up = split(fc1(
    LN2(x))). ``kind`` (static) names the mixer, ``lp`` the block's own
    parameters and its mixer's; ``at`` holds the step's arguments
    (_run_plan) and the block's place: ``layer`` its index in the model
    and ``group_layer`` among its kind's: a Mamba layer's in the state
    pool, an attention layer's in the K/V pool; a cross layer reads
    pool layer ``shared_kv_layer`` (static: the last "full" layer's).
    ``mem`` is the memory a "mamba_mem" layer leaves and a "gmu" layer
    reads. -> (x', pool, spool, mem, the experts' work: None)."""
    starts, token_valid, group_layer = (at["starts"], at["token_valid"],
                                        at["group_layer"])
    eps = cfg.rms_norm_eps
    with jax.named_scope("attn_norm"):
        hidden = _layer_norm(x, lp["attn_norm"], lp["attn_norm_bias"], eps)
    if kind in ("mamba", "mamba_mem"):
        out, y, spool = _mamba_mixer(cfg, hidden, lp, spool,
                                     at["state_ids"], starts, token_valid,
                                     group_layer)
        if kind == "mamba_mem":
            mem = y
    elif kind == "gmu":
        with jax.named_scope("gmu"):
            gate = quant.dequant_matmul(hidden, lp["in_proj"],
                                        exact_scale=True)
            out = quant.dequant_matmul(
                (jax.nn.silu(gate.astype(jnp.float32))
                 * mem.astype(jnp.float32)).astype(x.dtype),
                lp["out_proj"], exact_scale=True)
    else:
        own = kind != "cross"
        with jax.named_scope("diff_attention"):
            attn, pool = _diff_attention(
                cfg, hidden, lp, pool, at["kv_len"], token_valid,
                at["block_tables"], starts, at["positions"], at["mesh"],
                at["layer"],
                group_layer if own else at["shared_kv_layer"],
                cfg.sliding_window if kind == "swa" else None, own)
            with jax.named_scope("o_proj"):
                out = quant.dequant_matmul(
                    attn, lp["o"], exact_scale=True) + lp["o_bias"]
    x = x + out
    with jax.named_scope("mlp_norm"):
        hidden = _layer_norm(x, lp["mlp_norm"], lp["mlp_norm_bias"], eps)
    with jax.named_scope("mlp"):
        gu = quant.dequant_matmul(hidden, lp["fc1"], exact_scale=True)
        half = gu.shape[-1] // 2
        x = x + quant.dequant_matmul(
            gu[..., half:] * jax.nn.silu(gu[..., :half]), lp["fc2"],
            exact_scale=True)
    return x, pool, spool, mem, None


# the block each kind of a layer plan is: a decoder-hybrid-decoder's
# two-part block, or ONE sublayer. Both are called (cfg, kind, x, the
# block's parameters, the K/V pool, the state pools, the memory, the
# step's arguments and the block's place) -> (x', the pools, the
# memory, the experts' work or None)
PLAN_BLOCKS = {**{k: _plan_layer for k in ("mamba", "mamba_mem", "swa",
                                           "full", "gmu", "cross")},
               **{k: _sublayer_block for k in SUBLAYERS}}


def _sum_work(works):
    """The sum of the experts' work over blocks (None: no experts)."""
    works = [w for w in works if w is not None]
    return moe.Work(*map(sum, zip(*works))) if works else None


def _run_plan(params: Params, cfg: ModelConfig, x, positions, cache,
              block_tables, state_ids, token_valid, kv_len, mesh, last,
              finishing, moe_capacity_tokens=None):
    """The layer loop of a model with a layer plan: cfg.layer_plan's
    runs in order, each a scan over its periods (a run of one period is
    called as it stands), the pools in the carry as in ``forward``; a
    block is what PLAN_BLOCKS says of its kind. Where the list or the
    grouped path reads the experts in place (ops/moe.py) their stacks
    are closed over whole and a block is handed its index among the
    expert layers, as ``forward``'s one-run loop does.
    ``last`` [B] (a prefill chunk): the runs from the first that reads
    the memory or another layer's K/V on (cfg.self_layers) see ONE
    position a row, ``last[b]``, of the residual stream and the memory,
    and, with ``finishing`` (a traced bool: some row's prompt ends in
    this chunk), do not run at all where it is False (they write no
    cache: what they would have returned is read by nobody); None:
    every run sees every position. -> (x [B,T or 1,H], cache', the
    experts' work summed over the blocks, ops/moe.Work, or None)."""
    B, T, _ = x.shape
    if token_valid is None:
        token_valid = jnp.ones((B, T), bool)
    # a cross layer reads the pool layer of the LAST layer that sees
    # every key, among the layers that own their K/V
    own = [k for period, reps in cfg.plan_ for _ in range(reps)
           for k in period if k in ("swa", "full")]
    shared = len(own) - 1 - own[::-1].index("full") if "full" in own else 0
    groups = {g: params[g] for g in set(PLAN_GROUPS.values()) if g in params}
    expert_stacks = None
    if cfg.kind_layers("moe") and any(in_place(
            B, T, cfg.hidden_size, cfg.moe_stored_size,
            moe.stored_dtype(groups["moe_layers"]["up"]), x.dtype, mesh,
            cfg.expert_gate) for in_place in (moe.list_path,
                                              moe.grouped_path)):
        # read in place, whole: ``forward``'s note on the expert stacks
        expert_stacks = {n: groups["moe_layers"][n] for n in ("up", "down")}
        groups["moe_layers"] = {n: a for n, a in groups["moe_layers"].items()
                                if n not in expert_stacks}

    def go(runs, at, x, pool, spool, mem, positions, token_valid):
        base, seen = at
        # what every block of these runs is handed, beside its place
        step = dict(positions=positions, starts=positions[:, 0],
                    state_ids=state_ids, token_valid=token_valid,
                    block_tables=block_tables, kv_len=kv_len, mesh=mesh,
                    shared_kv_layer=shared,
                    moe_capacity_tokens=moe_capacity_tokens,
                    expert_stacks=expert_stacks)
        works = []
        for period, repeats in runs:
            if "mamba_mem" in period and mem is None and repeats > 1:
                mem = jnp.zeros(x.shape[:2] + (cfg.mamba_d_inner,),
                                x.dtype)
            per = {g: sum(PLAN_GROUPS[k] == g for k in period)
                   for g in seen}

            def body(carry, p, period=period, base=base, seen=seen,
                     per=per):
                h, pool, spool, mem = carry
                did = []
                for j, kind in enumerate(period):
                    group = PLAN_GROUPS[kind]
                    layer = base + p * len(period) + j
                    among = (seen[group] + p * per[group] + sum(
                        PLAN_GROUPS[k] == group for k in period[:j]))
                    # each reads its own row of the stacks in place
                    # (closed over: ``forward``'s note on the hybrid)
                    lp = jax.tree.map(lambda a: a[layer],
                                      params["layers"])
                    lp.update(jax.tree.map(lambda a: a[among],
                                           groups[group]))
                    h, pool, spool, mem, work = PLAN_BLOCKS[kind](
                        cfg, kind, h, lp, pool, spool, mem,
                        dict(step, layer=layer, group_layer=among))
                    did.append(work)
                return (h, pool, spool, mem), _sum_work(did)

            carry = (x, pool, spool, mem)
            if repeats == 1:
                carry, work = body(carry, 0)
            else:
                carry, work = jax.lax.scan(body, carry, jnp.arange(repeats))
                if work is not None:
                    work = moe.Work(*map(jnp.sum, work))
            works.append(work)
            x, pool, spool, mem = carry
            base += len(period) * repeats
            seen = {g: seen[g] + per[g] * repeats for g in seen}
        return x, pool, spool, mem, (base, seen), _sum_work(works)

    plan = cfg.plan_
    cut = next((n for n, (period, _) in enumerate(plan)
                if "gmu" in period or "cross" in period), len(plan))
    if last is None:
        cut = len(plan)
    x, pool, spool, mem, at, work = go(
        plan[:cut], (0, {g: 0 for g in PLAN_GROUPS.values()}), x,
        cache.carried(), cache.state_carried(), None, positions,
        token_valid)
    if cut < len(plan):
        # the second depth: the row's last prompt position alone
        def at_last(a):
            return jnp.take_along_axis(
                a, last.reshape((B,) + (1,) * (a.ndim - 1)), axis=1)
        x, positions = at_last(x), at_last(positions)
        mem = None if mem is None else at_last(mem)
        one = jnp.ones((B, 1), bool)
        reads_only = all(k in ("gmu", "cross") for period, _ in plan[cut:]
                         for k in period)
        if finishing is not None and reads_only:
            x = jax.lax.cond(
                finishing,
                lambda x, mem: go(plan[cut:], at, x, pool, spool, mem,
                                  positions, one)[0],
                lambda x, mem: x, x, mem)
        else:
            x, pool, spool, mem, _, _ = go(plan[cut:], at, x, pool, spool,
                                           mem, positions, one)
    return x, cache.carried_back(pool, spool), work


def _gdn_layer(cfg: ModelConfig, x, lp: Params, state, state_ids, starts,
               token_valid, layer, state_layer, moe_capacity_tokens,
               expert_stacks):
    """One Gated DeltaNet block (ops/gdn.py). x [B,T,H]; state = the
    WHOLE state pool (matrices [Lg,P,Hv,Dk,Dv] float32, the
    convolutions' inputs [Lg,P,taps-1,Ch]), of which this block reads
    and writes the rows' pages ``state_ids`` [B] of layer
    ``state_layer``, in place. A row none of whose positions is real
    (token_valid [B,T]; a parked decode row, a spare prefill row)
    names the trash page 0 whatever its table says; positions that are
    not real trail the chunk and advance nothing (exp(g) = 1, beta = 0,
    the convolution keeps its last REAL inputs); a row whose first
    position is 0 starts from a zero state. Returns (x', the state
    pool, the experts' work); scopes gdn_proj, gdn_conv, gdn_scan /
    gdn_step (ops/gdn.mix), gdn_gate_norm, gdn_out_proj."""
    B, T, _ = x.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv, ch = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_channels
    mats, conv = state
    if token_valid is None:
        token_valid = jnp.ones((B, T), bool)
    real = jnp.any(token_valid, axis=1)
    ids = jnp.where(real, state_ids, 0)
    fresh = starts == 0
    with jax.named_scope("attn_norm"):
        hidden = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, offset=1.0)
    # between the two projections the mixer keeps float32: what enters
    # the rule's products is rounded to the activations' dtype once
    f32 = jnp.float32
    with jax.named_scope("gdn_proj"):
        qkvz = quant.dequant_matmul(hidden, lp["qkvz"], dtype=f32,
                                    exact_scale=True)
        mixed, z = qkvz[..., :ch], qkvz[..., ch:]
        ba = jnp.einsum("bth,hj->btj", hidden, lp["ba"],
                        preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
            ba[..., hv:] + lp["dt_bias"])
        beta = jnp.where(token_valid[..., None], beta, 0.0)
        g = jnp.where(token_valid[..., None], g, 0.0)
    with jax.named_scope("gdn_conv"):
        prev = jnp.where(fresh[:, None, None], 0,
                         conv[state_layer, ids])
        mixed, new_conv = gdn.causal_conv(
            mixed, lp["conv"], prev,
            jnp.sum(token_valid, axis=1, dtype=jnp.int32))
        conv = conv.at[state_layer, ids].set(new_conv)
        q = mixed[..., :hk * dk].reshape(B, T, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(B, T, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(B, T, hv, dv)

        def l2norm(a, scale=1.0):
            return (a * (jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6) * scale)
            ).astype(x.dtype)
        q, k, v = l2norm(q, dk ** -0.5), l2norm(k), v.astype(x.dtype)
    o, mats = gdn.mix(q, k, v, g, beta, mats, ids, state_layer, fresh)
    with jax.named_scope("gdn_gate_norm"):
        o = rms_norm(o, lp["gdn_norm"], cfg.rms_norm_eps)
        o = (o * jax.nn.silu(z.reshape(B, T, hv, dv))).astype(x.dtype)
    with jax.named_scope("gdn_out_proj"):
        x = x + quant.dequant_matmul(o.reshape(B, T, hv * dv), lp["out"],
                                     exact_scale=True)
    x, _, work = _mlp_block(cfg, x, lp, None, token_valid,
                            moe_capacity_tokens, expert_stacks, layer,
                            None)
    return x, (mats, conv), work


def _ret_layer(cfg: ModelConfig, rope, positions, starts, x, lp: Params,
               state, state_ids, token_valid, layer, window=None,
               taken=None):
    """One power retention block (ops/retention.py; Brumby). x [B,T,H];
    state = the WHOLE state pools (``S`` [L,P,Hkv,F,D] and the
    normalisers [L,P,F Hkv/D,D], float32), of which this block reads
    and writes the rows' pages ``state_ids`` [B] of layer ``layer``, in
    place: the model's only cache. q = RoPE(RMSNorm_head(x W_q)) times
    head_dim ** -0.5, k = RoPE(RMSNorm_head(x W_k)), v = x W_v, log g =
    logsigmoid(x W_g + b_g) a key-value head; then W_o and the dense
    MLP. As _gdn_layer: a row none of whose positions is real names the
    trash page 0 whatever its table says; positions that are not real
    trail the chunk and advance nothing (log g = 0, k = 0); a row whose
    first position is 0 starts from a zero state. Inside a decode
    window that takes the window form (``window``: ops/retention.Window;
    ``taken``: this layer's slices of its keys, values and gates) the
    pages are only read, the rows' pages are the window's, and the
    step's k, v and summed log-gate join the slices. Returns (x', the
    pools, the slices or None); scopes ret_proj, rope, ret_scan /
    ret_step (ops/retention.retain), ret_out_proj."""
    B, T, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    cos, sin = rope
    if token_valid is None:
        token_valid = jnp.ones((B, T), bool)
    ids = jnp.where(jnp.any(token_valid, axis=1), state_ids, 0)
    with jax.named_scope("attn_norm"):
        hidden = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("ret_proj"):
        q = quant.dequant_matmul(hidden, lp["q"]).reshape(B, T, nh, hd)
        k = quant.dequant_matmul(hidden, lp["k"]).reshape(B, T, nkv, hd)
        v = quant.dequant_matmul(hidden, lp["v"]).reshape(B, T, nkv, hd)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        logg = jax.nn.log_sigmoid(
            jnp.einsum("bth,hj->btj", hidden, lp["ret_gate"],
                       preferred_element_type=jnp.float32)
            + lp["ret_gate_bias"])
        logg = jnp.where(token_valid[..., None], logg, 0.0)
    with jax.named_scope("rope"):
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        q = (q.astype(jnp.float32) * hd ** -0.5).astype(x.dtype)
        k = jnp.where(token_valid[..., None, None], k, 0)
    if window is None:
        y, *state = retention.retain(q, k, v, logg, *state, ids, layer,
                                     starts == 0)
    else:
        with jax.named_scope("ret_step"):
            y, *taken = retention.retain_in_window(
                q, k, v, logg, *taken, window, *state, layer)
    with jax.named_scope("ret_out_proj"):
        x = x + quant.dequant_matmul(
            y.astype(x.dtype).reshape(B, T, nh * hd), lp["o"])
    x, _, _ = _mlp_block(cfg, x, lp, None, token_valid, None, None, layer,
                         lambda h, name: quant.dequant_matmul(h, lp[name]))
    return x, tuple(state), None if window is None else tuple(taken)


def _mla_attention(cfg: ModelConfig, rope, positions, starts, hidden,
                   lp: Params, kv, kv_len, token_valid, block_tables,
                   mesh, layer):
    """Latent attention (MLA) on the normed input ``hidden`` [B,T,H] ->
    (heads' outputs [B,T,nh*v_head_dim], the pool).

    c_q = RMSNorm(x W_qa), q = c_q W_qb -> per head [q_nope | q_rope];
    [c_kv | k_rope] = x W_kva, c = RMSNorm(c_kv); the rope parts rotated
    at the token's position. With a pool (serving) the layer caches
    ``[c | k_rope]`` — ONE vector a token — and attends ABSORBED: W_kvb
    split per head into W_uk [nope, r] and W_uv [r, v], q_lat = q_nope
    W_uk^T, scores of [q_lat | q_rope] against the cached vectors, the
    weighted sum of the cached c, then W_uv: no key or value of a head
    is ever made. W_kvb's int8 scales are per output channel, which in
    those two products are q_nope's channels and o's: they multiply
    there and nothing is requantised. Without a pool (encode) it is
    the EXPANDED form, [k_nope | v] = c W_kvb per head: the two forms
    are what tests/test_mla.py compares. A prefill chunk of enough
    positions a row that expanded multiplies less (models/kv.expands)
    attends expanded over the pool too: the prefill kernel makes each
    key panel's k_nope and v per head from the cached c (ops/
    pallas_paged.paged_attention ``expand``); what is cached does not
    change.

    With an indexer (cfg.index_topk, GLM-5; ops/dsa.py) the pool is two
    arrays, the latents and the index keys: the layer also makes the
    index queries qI = c_q W_qI (per index head, the leading rope part
    rotated), ONE index key kI = LayerNorm(x W_kI) a token (rotated
    alike) and the heads' weights x W_w, appends kI to the index pool
    at the token's place, and attends only the index_topk positions
    the index scores rank best (models/kv.attend_selected) wherever
    the kv bucket holds more than that (models/kv.selects)."""
    B, T, _ = hidden.shape
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cos, sin = rope
    eps = cfg.rms_norm_eps
    with jax.named_scope("mla_q_proj"):
        c_q = rms_norm(quant.dequant_matmul(hidden, lp["q_a"]),
                       lp["q_a_norm"], eps)
        q = quant.dequant_matmul(c_q, lp["q_b"]).reshape(B, T, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
    with jax.named_scope("mla_kv_proj"):
        ckv = quant.dequant_matmul(hidden, lp["kv_a"])
        c = rms_norm(ckv[..., :r], lp["kv_a_norm"], eps)
        k_rope = ckv[..., None, r:]                       # [B,T,1,dr]
    with jax.named_scope("rope"):
        q_rope = apply_rope(q_rope, positions, cos, sin)
        k_rope = apply_rope(k_rope, positions, cos, sin)
    scale = (dn + dr) ** -0.5
    indexed = bool(cfg.index_topk)
    if indexed and kv is None and T > cfg.index_topk:
        raise ValueError(
            f"{cfg.name}: a forward without a KV pool (encode, "
            f"forward_train) over {T} positions, more than index_topk "
            f"{cfg.index_topk}, is not supported: the selection is "
            f"built on the index pool")
    # the index key is cached at every kv bucket; the index queries and
    # the heads' weights are made only where the bucket selects
    selecting = indexed and kv is not None and kv_pool.selects(
        kv_len, block_tables.shape[1], kv[0].shape[-2], cfg.index_topk)
    if indexed and kv is not None:
        with jax.named_scope("dsa_indexer"):
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            ik = _layer_norm(quant.dequant_matmul(hidden, lp["idx_k"]),
                             lp["idx_k_norm"], lp["idx_k_norm_bias"])
            ik = jnp.concatenate(
                [apply_rope(ik[:, :, None, :dr], positions, cos, sin),
                 ik[:, :, None, dr:]], axis=-1)           # [B,T,1,di]
            if selecting:
                iq = quant.dequant_matmul(c_q, lp["idx_q"]).reshape(
                    B, T, hi, di)
                iq = jnp.concatenate(
                    [apply_rope(iq[..., :dr], positions, cos, sin),
                     iq[..., dr:]], axis=-1)
                iw = (jnp.einsum("bth,hj->btj", hidden, lp["idx_w"],
                                 preferred_element_type=jnp.float32)
                      * (hi ** -0.5 * di ** -0.5))
    kv_b = lp["kv_b"]
    quantized = quant.is_quantized(kv_b)
    w_kvb = (kv_b["w8"] if quantized else kv_b).reshape(r, nh, dn + dv)
    ch_scale = (kv_b["scale"].reshape(nh, dn + dv) if quantized else None)
    if kv is None:
        with jax.named_scope("attention"):
            kvh = jnp.einsum("btr,rhd->bthd", c, w_kvb.astype(c.dtype))
            if quantized:
                kvh = kvh * ch_scale.astype(c.dtype)
            k = jnp.concatenate(
                [kvh[..., :dn], jnp.broadcast_to(k_rope, (B, T, nh, dr))],
                axis=-1)
            attn = causal_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k,
                kvh[..., dn:], scale=scale)
        return attn.reshape(B, T, nh * dv), None
    # a chunk long enough a row attends expanded, inside the kernel
    # (ops/pallas_paged.expanded_cheaper): the pool is the same
    expand = None
    if kv_pool.expands(T, nh, kv[0], r, (dn, dr, dv), mesh):
        expand = (w_kvb[..., :dn], w_kvb[..., dn:]) + (
            (ch_scale[:, :dn], ch_scale[:, dn:]) if quantized
            else (None, None))
    else:
        with jax.named_scope("mla_absorb_q"):
            if quantized:
                q_nope = (q_nope.astype(jnp.float32)
                          * ch_scale[:, :dn]).astype(q_nope.dtype)
            q_lat = jnp.einsum("bthd,rhd->bthr", q_nope,
                               w_kvb[..., :dn].astype(q_nope.dtype))
    # the pool's vectors are padded to whole lanes (kv.latent_pool_width):
    # zeros in the cache and in the queries, which add nothing to a score
    pad = kv[0].shape[-1] - (r + dr)

    def padded(parts):
        if pad:
            parts.append(jnp.zeros(parts[0].shape[:-1] + (pad,),
                                   parts[0].dtype))
        return jnp.concatenate(parts, axis=-1)
    with jax.named_scope("kv_write"):
        # (the index pool's keys ride the same call: models/kv.append)
        kv = kv_pool.append(
            kv, padded([c[:, :, None, :], k_rope]),
            ik if indexed else None, block_tables, starts, token_valid,
            layer, mesh=mesh)
        latents = kv[:1]
    q = (padded([q_lat, q_rope]) if expand is None
         else jnp.concatenate([q_nope, q_rope], axis=-1))
    if selecting:
        ctx = kv_pool.attend_selected(
            q, kv[0], kv[1], iq, iw, block_tables, starts, positions,
            kv_len, layer, topk=cfg.index_topk, scale=scale, value_dim=r,
            mesh=mesh, expand=expand)
    else:
        with jax.named_scope("attention"):
            ctx = kv_pool.attend(
                q, latents, block_tables, starts, positions, kv_len,
                layer, window=None, scale=scale, softcap=None, mesh=mesh,
                value_dim=r, expand=expand)               # [B,T,nh,r]
    if expand is not None:      # the heads' outputs already [B,T,nh,dv]
        return ctx.reshape(B, T, nh * dv), kv
    with jax.named_scope("mla_absorb_o"):
        attn = jnp.einsum("bthr,rhd->bthd", ctx,
                          w_kvb[..., dn:].astype(ctx.dtype))
        if quantized:
            attn = attn * ch_scale[:, dn:].astype(attn.dtype)
    return attn.reshape(B, T, nh * dv), kv


def _layer_body(cfg: ModelConfig, rope: Tuple[jnp.ndarray, jnp.ndarray],
                positions: jnp.ndarray, starts: Optional[jnp.ndarray],
                x: jnp.ndarray, lp: Params,
                kv: Optional[Tuple[jnp.ndarray, ...]],
                kv_len: Optional[int] = None, lora_layer=None,
                adapter_ids: Optional[jnp.ndarray] = None,
                lora_scaling: float = 1.0,
                token_valid: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None,
                mesh=None, layer_local=None, layer=None,
                moe_capacity_tokens: Optional[int] = None,
                expert_stacks: Optional[Params] = None, kv_layer=None):
    """One transformer block. x [B,T,H]; kv = the WHOLE paged pool
    (k, v) [L,N,Hkv,Bs,D] — with (ks, vs) [L,N,Hkv,Bs] behind them for
    the int8 pool — of which this block appends to and reads
    ``layer`` (its index, traced; ``kv_layer`` where the pool holds
    the attention layers alone and the experts' stacks every layer:
    a hybrid model), addressed through block_tables
    [B,MB]. The pool comes back as the second result, the same buffer
    with this layer's chunk written (models/kv.py: carried, never
    stacked); what is done to it is done behind models/kv.py
    (``append``, ``attend``). kv None (encode): no cache. Returns (x',
    the pool, the experts' work in the block, ops/moe.Work: None on a
    dense model).

    kv_len (static) bounds attention to the first ceil(kv_len/Bs) blocks
    of every slot: K/V writes target the pool via the tables, and
    score/value matmuls scale with the live context instead of
    max_model_len. Caller guarantees every real query position is
    < kv_len.
    token_valid [B,T] marks real tokens: invalid tokens' K/V writes are
    routed to the trash block and (on MoE models) they are kept out of
    expert-capacity competition. moe_capacity_tokens (static): the
    token count the experts' capacity is reckoned on, where that is not
    B*T (ops/moe.moe_mlp ``capacity_tokens``). expert_stacks: the
    experts' gate/up/down of ALL layers, where the block reads its own
    in place (ops/moe.py, the list and grouped paths); ``lp`` then
    lacks them.
    lora_layer: this layer's stacked adapters {proj: {a, b}} + per-row
    adapter_ids [B] (models/lora.py) — batched multi-LoRA.

    Each stage runs under a ``jax.named_scope`` (attn_norm, qkv_proj,
    rope, kv_write, attention, o_proj, mlp_norm, then mlp or
    moe_router / moe_experts / moe_combine / shared_expert): the names
    are metadata on the compiled operations, where a profiler capture
    finds them (docs/observability.md "Names on the device"); they cost
    nothing at run time.
    """
    B, T, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    cos, sin = rope
    if kv_layer is None:
        kv_layer = layer

    def proj(h, name):
        out = quant.dequant_matmul(h, lp[name],
                                   exact_scale=cfg.exact_dequant_scale)
        bias = lp.get(f"{name}_bias")
        if bias is not None:
            out = out + bias
        if lora_layer is not None and name in lora_layer:
            out = lora.apply(h, out, lora_layer[name], adapter_ids,
                             lora_scaling)
        return out

    offset = 1.0 if cfg.rms_norm_offset else 0.0
    with jax.named_scope("attn_norm"):
        hidden = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                          offset=offset)
    if cfg.mla:
        attn, kv = _mla_attention(cfg, rope, positions, starts, hidden,
                                  lp, kv, kv_len, token_valid,
                                  block_tables, mesh, layer)
        with jax.named_scope("o_proj"):
            x = x + proj(attn, "o")
        return _mlp_block(cfg, x, lp, kv, token_valid,
                          moe_capacity_tokens, expert_stacks, layer, proj)
    with jax.named_scope("qkv_proj"):
        if cfg.attn_gate:
            # a head's query and its output gate, side by side
            qg = proj(hidden, "q").reshape(B, T, nh, 2 * hd)
            q, out_gate = qg[..., :hd], qg[..., hd:]
        else:
            q = proj(hidden, "q").reshape(B, T, nh, hd)
        k = proj(hidden, "k").reshape(B, T, nkv, hd)
        v = proj(hidden, "v").reshape(B, T, nkv, hd)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, offset=offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, offset=offset)
    with jax.named_scope("rope"):
        if cfg.rotary_dim:
            # the leading rotary_dim columns turn, the rest pass
            rd = cfg.rotary_dim
            q = jnp.concatenate([apply_rope(q[..., :rd], positions, cos,
                                            sin), q[..., rd:]], axis=-1)
            k = jnp.concatenate([apply_rope(k[..., :rd], positions, cos,
                                            sin), k[..., rd:]], axis=-1)
        else:
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)

    # Gemma-2 deviations from the Llama baseline: attention scale from
    # query_pre_attn_scalar, tanh score softcap, and (alternating)
    # sliding windows. layer_local (traced bool, from the scan's
    # per-layer flags) picks between two STATICALLY-windowed branches
    # via lax.cond — kernels stay static-shaped.
    scale_val = ((float(cfg.query_pre_attn_scalar) ** -0.5)
                 if cfg.query_pre_attn_scalar else hd ** -0.5)
    cap = cfg.attn_logit_softcap
    sw = cfg.sliding_window

    def _windowed(attn_fn_w):
        if cfg.alternating_sliding:
            return jax.lax.cond(layer_local,
                                lambda: attn_fn_w(sw),
                                lambda: attn_fn_w(None))
        return attn_fn_w(sw)

    if kv is None:
        with jax.named_scope("attention"):
            attn = _windowed(lambda w: causal_attention(
                q, k, v, scale=scale_val, sliding_window=w,
                logit_softcap=cap))
    else:
        with jax.named_scope("kv_write"):
            kv = kv_pool.append(kv, k, v, block_tables, starts,
                                token_valid, kv_layer, mesh=mesh)
        with jax.named_scope("attention"):
            attn = _windowed(lambda w: kv_pool.attend(
                q, kv, block_tables, starts, positions, kv_len, kv_layer,
                window=w, scale=scale_val, softcap=cap, mesh=mesh))
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                out_gate.astype(jnp.float32))).astype(x.dtype)
    with jax.named_scope("o_proj"):
        o_out = proj(attn.reshape(B, T, nh * hd), "o")
        if cfg.sandwich_norms:
            # Gemma-2: normalize the attention OUTPUT before the residual
            o_out = rms_norm(o_out, lp["post_attn_norm"],
                             cfg.rms_norm_eps, offset=offset)
        x = x + o_out
    return _mlp_block(cfg, x, lp, kv, token_valid, moe_capacity_tokens,
                      expert_stacks, layer, proj)


def _mlp_block(cfg: ModelConfig, x, lp: Params, kv, token_valid,
               moe_capacity_tokens, expert_stacks, layer, proj):
    """The block's second half: mlp_norm, then the dense MLP or, where
    the layer's parameters hold a router, the experts (a model with
    leading dense layers has both kinds of layer). Returns
    _layer_body's triple."""
    B, T, _ = x.shape
    offset = 1.0 if cfg.rms_norm_offset else 0.0
    with jax.named_scope("mlp_norm"):
        hidden = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps,
                          offset=offset)
    act = _activation(cfg)
    work = None
    if "router" in lp:
        H = hidden.shape[-1]
        # the list and grouped paths read their experts in place in
        # the whole stacks (ops/moe.list_path and grouped_path, asked
        # by ``forward``); else this layer's
        gate, up, down = (
            lp[n] if expert_stacks is None else expert_stacks[n]
            for n in ("gate", "up", "down"))
        # the stacks hold the expert layers alone: the pool's layer
        # index less the leading dense layers
        moe_layer = None if expert_stacks is None else layer
        if moe_layer is not None and cfg.first_dense_layers:
            moe_layer = layer - cfg.first_dense_layers
        # scopes moe_router / moe_experts / moe_combine: ops/moe.py
        y, work = moe.moe_mlp(
            hidden.reshape(B * T, H), lp["router"], gate, up, down,
            top_k=cfg.num_experts_per_tok,
            capacity_factor=cfg.moe_capacity_factor,
            capacity_tokens=moe_capacity_tokens, act=act,
            valid=None if token_valid is None
            else token_valid.reshape(B * T),
            renormalize=cfg.norm_topk_prob,
            # decode (T == 1) must be exact: a dropped token would
            # corrupt a live sequence's residual stream mid-generation
            exact=True if T == 1 else None,
            layer=moe_layer, positions=T,
            router_score=cfg.router_score,
            router_bias=lp.get("router_bias"),
            routed_scale=cfg.routed_scaling_factor,
            expert_offset=cfg.expert_offset)
        if cfg.shared_expert_size:
            # an always-on shared expert: Qwen2-MoE's behind a
            # per-token sigmoid gate, GLM-4.7-Flash's with none
            with jax.named_scope("shared_expert"):
                mm = functools.partial(
                    quant.dequant_matmul,
                    exact_scale=cfg.exact_dequant_scale)
                shared = mm(act(mm(hidden, lp["s_gate"]))
                            * mm(hidden, lp["s_up"]), lp["s_down"])
                if cfg.shared_expert_gate:
                    shared = jax.nn.sigmoid(
                        hidden @ lp["s_gate_w"]) * shared
                y = y.reshape(B, T, H) + shared
            x = x + y
        else:
            x = x + y.reshape(B, T, H)
    else:
        with jax.named_scope("mlp"):
            gated = act(proj(hidden, "gate")) * proj(hidden, "up")
            mlp_out = proj(gated, "down")
            if cfg.sandwich_norms:
                mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"],
                                   cfg.rms_norm_eps, offset=offset)
            x = x + mlp_out
    return x, kv, work


def _layer_norm(x: jnp.ndarray, weight, bias,
                eps: float = 1e-6) -> jnp.ndarray:
    """LayerNorm over the last axis, in float32 (the indexer's key)."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """Gemma's gelu_pytorch_tanh (jax.nn.gelu's approximate form)."""
    return jax.nn.gelu(x, approximate=True)


def _activation(cfg: ModelConfig):
    return {"silu": jax.nn.silu, "relu2": moe.relu2}.get(
        cfg.activation, _gelu_tanh)


def open_window(cfg: ModelConfig, block_tables: jnp.ndarray,
                positions: jnp.ndarray, steps: int, valid: jnp.ndarray):
    """What a decode window of ``steps`` steps (one position a row and
    step, the rows' first at ``positions`` [B]) carries beside the
    cache from its first ``forward_in_window`` to ``close_window``: an
    ops/retention.Window where the model's power retention layers take
    the window form at that step count (ops/retention.windowed: the
    pages are read a step and written once, by the fold), else None,
    and the steps run as ``forward`` runs them. valid [B]: the row is
    real at the first step (else it names the trash page)."""
    if not (cfg.ret_layers and retention.windowed(1, steps)):
        return None
    _, ids = kv_pool.split_tables(block_tables, True)
    return retention.open_window(cfg.num_layers, steps, cfg.num_kv_heads,
                                 cfg.head_dim_, jnp.where(valid, ids, 0),
                                 positions == 0)


def close_window(cache: KVCache, window) -> KVCache:
    """The window's end: its keys folded into the rows' pages (``cache``
    as the last step returned it; None: nothing was deferred)."""
    if window is None:
        return cache
    return cache.carried_back(cache.carried(), retention.fold_window(
        window, *cache.state_carried()))


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, cache: KVCache,
            block_tables: Optional[jnp.ndarray] = None,
            rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
            kv_len: Optional[int] = None,
            lora_params=None, adapter_ids: Optional[jnp.ndarray] = None,
            lora_scaling: float = 1.0,
            token_valid: Optional[jnp.ndarray] = None,
            mesh=None, moe_capacity_tokens: Optional[int] = None,
            last: Optional[jnp.ndarray] = None,
            finishing: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, KVCache, Optional[jnp.ndarray]]:
    """Incremental forward. tokens/positions [B,T] -> (logits fp32
    [B,T,V], cache', what it counted: ``Work``).

    cache is the paged block pool (models/kv.py); block_tables [B, MB]
    map each row's virtual positions to pool blocks (None = identity
    tables for a pool built by make_slot_cache, i.e. the contiguous
    per-slot layout). positions[b] must be contiguous starting at the
    sequence's current length; the new K/V chunk is written at that
    offset through the tables.
    kv_len (static) bounds attention to the first ceil(kv_len/Bs)
    blocks — see _layer_body.
    lora_params: layer-leading stacked adapters (models/lora.layer_slice)
    + adapter_ids [B] selecting each row's adapter (0 = base).
    token_valid [B,T] bool marks real (non-padding) tokens — their K/V
    writes are routed to the trash block, and MoE models keep them out
    of expert-capacity competition (ops/moe.py).
    moe_capacity_tokens (static): reckon the experts' capacity on this
    many tokens instead of B*T — a prefill of fewer rows than the full
    batch passes the full batch's count, so that it never holds less
    per expert (ops/moe.moe_mlp ``capacity_tokens``).
    the experts' work (``Work``'s first four members, ops/moe.Work
    summed over the layers; the whole None on a dense model that is not
    looped): ``experts_read``, the experts whose weights the
    forward read (layers x experts, or the experts its valid rows were
    routed to where the expert matmuls walk that list: ops/moe
    list_path, decode steps, and grouped_path, prefill chunks), and
    ``expert_rows``, the rows those experts multiplied.
    last [B] (a prefill chunk of a model whose plan has two depths,
    cfg.self_layers < num_layers: a decoder-hybrid-decoder): the index
    in T of each row's last real position. The layers from
    cfg.self_layers on, the final norm and the head then run on that
    position alone and the logits are [B,1,V]; with ``finishing`` (a
    traced bool: some row's prompt ends in this chunk) those layers do
    not run at all where it is False, and the logits mean nothing.
    None: every layer on every position.
    """
    return forward_in_window(
        params, cfg, tokens, positions, cache, None,
        block_tables=block_tables, rope=rope, kv_len=kv_len,
        lora_params=lora_params, adapter_ids=adapter_ids,
        lora_scaling=lora_scaling, token_valid=token_valid, mesh=mesh,
        moe_capacity_tokens=moe_capacity_tokens, last=last,
        finishing=finishing)[:3]


def forward_in_window(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                      positions: jnp.ndarray, cache: KVCache, window,
                      block_tables: Optional[jnp.ndarray] = None,
                      rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                      kv_len: Optional[int] = None,
                      lora_params=None,
                      adapter_ids: Optional[jnp.ndarray] = None,
                      lora_scaling: float = 1.0,
                      token_valid: Optional[jnp.ndarray] = None,
                      mesh=None, moe_capacity_tokens: Optional[int] = None,
                      last: Optional[jnp.ndarray] = None,
                      finishing: Optional[jnp.ndarray] = None):
    """``forward`` as one step of a decode window: ``window`` is what
    ``open_window`` gave or the step before returned. -> (logits,
    cache', what it counted, window'). With a window the power
    retention layers read their pages and write nothing (the cache's
    pools come back as they went in) and the step's keys join the
    window; with None this IS ``forward``."""
    if rope is None:
        rope = rope_table(cfg.max_position_embeddings, cfg.rope_dim_,
                          cfg.rope_theta, scaling=cfg.rope_scaling)
    if lora_params is not None and (cfg.mla or cfg.first_dense_layers
                                    or cfg.layer_pattern
                                    or cfg.layer_plan):
        raise ValueError(
            "LoRA adapters are not supported on a latent-attention "
            "model, one with leading dense layers or one with two kinds "
            "of mixer")
    if block_tables is None:
        B = tokens.shape[0]
        pages = 1 + jnp.arange(B, dtype=jnp.int32)[:, None]
        if cache.k is None:     # state pages alone: row b's page, 1 + b
            block_tables = pages
        else:
            Bs = cache.block_size
            n_per = (cache.k.shape[1] - 1) // B
            block_tables = kv_pool.linear_tables(B, n_per * Bs, Bs)
            if cfg.state_layers:
                block_tables = jnp.concatenate([block_tables, pages],
                                               axis=1)
    starts = positions[:, 0]
    # the last column of a table row is the sequence's state page,
    # where the model has such pages (models/kv.split_tables)
    block_tables, state_ids = kv_pool.split_tables(
        block_tables, bool(cfg.state_layers))
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)
    if cfg.layer_plan:
        # several runs of periods, no rotary embedding
        with jax.named_scope("layers"):
            x, cache, work = _run_plan(
                params, cfg, x, positions, cache, block_tables, state_ids,
                token_valid, kv_len, mesh, last, finishing,
                moe_capacity_tokens)
        with jax.named_scope("final_norm"):
            if "final_norm_bias" in params:
                x = _layer_norm(x, params["final_norm"],
                                params["final_norm_bias"], cfg.rms_norm_eps)
            else:
                x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        with jax.named_scope("lm_head"):
            return _lm_head(params, cfg, x), cache, _counted(work), window
    # the scan's unit is one PERIOD of the layer pattern (cfg.pattern_):
    # one attention layer for every model but the hybrid, whose period
    # runs its Gated DeltaNet layers and then its attention layer
    pattern = cfg.pattern_
    period = len(pattern)

    def scan_body(carry, xs, kv_base=None):
        # the pools ride in the CARRY, one buffer each from the
        # executable's donated argument to its result: as the scan's
        # xs -> ys the K/V pool was sliced, rewritten and stacked, a
        # layer's pool per layer and the whole pool per step
        # (models/kv.py); the state pages beside it alike. kv_base (a
        # looped model's pass): the first pool layer of the pass
        h, pool, spool = carry
        lp, layer, ll, local, taken = xs
        if pattern == ("ret",):
            h, spool, taken = _ret_layer(
                cfg, rope, positions, starts, h, lp, spool, state_ids,
                token_valid, layer, window, taken)
            return (h, pool, spool), (None, taken)
        if period == 1:
            h, pool, work = _layer_body(
                cfg, rope, positions, starts, h, lp, pool,
                kv_len=kv_len, lora_layer=ll, adapter_ids=adapter_ids,
                lora_scaling=lora_scaling, token_valid=token_valid,
                block_tables=block_tables, mesh=mesh,
                layer_local=local, layer=layer,
                moe_capacity_tokens=moe_capacity_tokens,
                expert_stacks=expert_stacks,
                kv_layer=None if kv_base is None else kv_base + layer)
            return (h, pool, spool), (work, None)
        # ``layer`` is the period's index; its sub-layers in a static
        # loop, each reading its own row of the groups' stacks in place
        # (closed over, like the experts' stacks: as the scan's xs a
        # period's slice [layers a period, ...] was copied out whole
        # before a sub-layer's row was taken, 75 MB of the fused input
        # projections a period and step on the chip: PERF.md, PR 42)
        works = []
        for j, kind in enumerate(pattern):
            # the sub-layer's place among its kind's, in the period
            i, per = pattern[:j].count(kind), pattern.count(kind)
            sub = jax.tree.map(lambda a: a[layer * period + j],
                               layer_params)
            sub.update(jax.tree.map(lambda a: a[layer * per + i],
                                    params[kind + "_layers"]))
            if kind == "gdn":
                h, spool, work = _gdn_layer(
                    cfg, h, sub, spool, state_ids, starts, token_valid,
                    layer * period + j, layer * per + i,
                    moe_capacity_tokens, expert_stacks)
            else:
                h, pool, work = _layer_body(
                    cfg, rope, positions, starts, h, sub, pool,
                    kv_len=kv_len, token_valid=token_valid,
                    block_tables=block_tables, mesh=mesh,
                    layer=layer * period + j,
                    moe_capacity_tokens=moe_capacity_tokens,
                    expert_stacks=expert_stacks,
                    kv_layer=layer * per + i)
            works.append(work)
        return (h, pool, spool), (moe.Work(*map(sum, zip(*works))), None)

    layer_params = params["layers"]
    expert_stacks = None
    if cfg.num_experts and any(in_place(
            *tokens.shape, cfg.hidden_size,
            cfg.moe_intermediate_size or cfg.intermediate_size,
            moe.stored_dtype(layer_params["gate"]), x.dtype, mesh)
            for in_place in (moe.list_path, moe.grouped_path)):
        # the list path's kernel and the grouped path's read a layer of
        # the expert stacks in place: closed over whole, like the pool,
        # where the scan's xs would hand them a copy of the layer (a
        # custom call cannot fuse its operand's slice)
        expert_stacks = {n: layer_params[n] for n in ("gate", "up", "down")}
        layer_params = {n: w for n, w in layer_params.items()
                        if n not in expert_stacks}
    # the layer plan: leading dense layers run before the scan with
    # parameters of their own, on the first layers of the pool; the
    # scan runs the rest (the expert layers) at the pool's layer index,
    # so the pool stays the one carried buffer
    Ld = cfg.first_dense_layers
    if period == 1:
        layers = jnp.arange(Ld, cfg.num_layers)
    else:       # the scan counts periods; the body reads the stacks
        layers = jnp.arange(cfg.num_periods)
    xs = (layer_params if period == 1 else None, layers, lora_params,
          # Gemma-2 layer pattern: even layers sliding, odd global
          layers % 2 == 0 if cfg.alternating_sliding else None,
          # a decode window's keys, values and gates, a layer's slices
          None if window is None else (window.k, window.v, window.G))
    pool, spool = cache.carried(), cache.state_carried()
    with jax.named_scope("dense_layers"):
        for i in range(Ld):
            lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
            (x, pool, spool), _ = scan_body(
                (x, pool, spool), (lp, jnp.int32(i), None, None, None))
    if cfg.loop_steps > 1:
        x, pool, loop = _run_passes(params, cfg, scan_body, x, pool, spool,
                                    xs, token_valid)
        with jax.named_scope("lm_head"):
            return (_lm_head(params, cfg, x), cache.carried_back(pool, spool),
                    _counted(None, loop), window)
    with jax.named_scope("layers"):
        (x, pool, spool), (work, taken) = jax.lax.scan(
            scan_body, (x, pool, spool), xs)
    if window is not None:
        k, v, G = taken
        window = window._replace(k=k, v=v, G=G, step=window.step + 1)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     offset=1.0 if cfg.rms_norm_offset else 0.0)
    with jax.named_scope("lm_head"):
        logits = _lm_head(params, cfg, x)
    return (logits, cache.carried_back(pool, spool), _counted(
        None if work is None else moe.Work(*map(jnp.sum, work))), window)


def _run_passes(params: Params, cfg: ModelConfig, scan_body, x, pool,
                spool, xs, token_valid):
    """A looped model's layers (cfg.loop_steps > 1): the layer scan
    inside a scan over PASSES, the pools in the carry as in one pass.
    Pass t runs the same layers over the pass before's normed stream,
    appending to and attending over pool layers t * num_layers + l; the
    final norm closes every pass and the exit gate reads its output.
    One traced layer body and one traced pass, whatever the count.
    -> (the last pass's normed stream, the pool, LoopWork)."""
    gate_w, gate_b = params["exit_gate"], params["exit_gate_bias"]

    def pass_body(carry, t):
        h, pool, spool = carry
        with jax.named_scope("loop_pass"):
            with jax.named_scope("layers"):
                (h, pool, spool), _ = jax.lax.scan(
                    functools.partial(scan_body,
                                      kv_base=t * cfg.num_layers),
                    (h, pool, spool), xs)
            with jax.named_scope("final_norm"):
                h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
            with jax.named_scope("exit_gate"):
                lam = jax.nn.sigmoid(
                    jnp.einsum("bth,h->bt", h.astype(jnp.float32), gate_w)
                    + gate_b)
        return (h, pool, spool), lam

    (x, pool, spool), lam = jax.lax.scan(
        pass_body, (x, pool, spool), jnp.arange(cfg.loop_steps))
    with jax.named_scope("exit_gate"):
        # p_t = lambda_t prod_{j<t} (1 - lambda_j); the last pass takes
        # what is left, so the shares of a token add up to one
        stay = jnp.cumprod(1.0 - lam, axis=0)                # [P, B, T]
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        mass = jnp.concatenate([(lam * before)[:-1], before[-1:]])
        valid = (jnp.ones(lam.shape[1:], bool) if token_valid is None
                 else token_valid)
        rows = jnp.sum(valid, dtype=jnp.int32)
        work = LoopWork(passes_run=cfg.loop_steps * rows, row_steps=rows,
                        exit_mass=jnp.sum(jnp.where(valid, mass, 0.0),
                                          axis=(1, 2)))
    return x, pool, work


def encode(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
           rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
           token_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence causal forward WITHOUT the LM head: final-normed
    hidden states [B,T,H]. The embeddings/rerank/score endpoints pool
    these (engine/server.py); forward_train puts the head on top.
    token_valid [B,T] marks real tokens in right-padded batches — on
    MoE models padding must not compete for expert capacity.
    """
    if cfg.layer_pattern or cfg.layer_plan:
        raise ValueError(
            f"{cfg.name}: a forward without caches (encode, "
            f"forward_train: embeddings, echoed prompt log-"
            f"probabilities) is not built for a model with state pages; "
            f"its reference is under chipbench/references/")
    if rope is None:
        rope = rope_table(cfg.max_position_embeddings, cfg.rope_dim_,
                          cfg.rope_theta, scaling=cfg.rope_scaling)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _embed(params, cfg, tokens)

    def scan_body(carry, xs):
        lp, local = xs
        out, _, _ = _layer_body(cfg, rope, positions, None, carry, lp,
                                None, token_valid=token_valid,
                                layer_local=local)
        return out, None

    Ld = cfg.first_dense_layers
    for i in range(Ld):
        x, _ = scan_body(x, (jax.tree.map(lambda a: a[i],
                                          params["dense_layers"]), None))
    local_flags = (jnp.arange(cfg.num_layers) % 2 == 0
                   if cfg.alternating_sliding
                   else jnp.zeros((cfg.num_layers - Ld,), bool))
    if cfg.loop_steps > 1:
        # a looped model: the same layers a pass, the final norm after
        # every pass (forward_in_window's loop without the caches)
        def pass_body(h, _):
            h, _ = jax.lax.scan(scan_body, h,
                                (params["layers"], local_flags))
            return rms_norm(h, params["final_norm"], cfg.rms_norm_eps), None
        return jax.lax.scan(pass_body, x, None, length=cfg.loop_steps)[0]
    x, _ = jax.lax.scan(scan_body, x, (params["layers"], local_flags))
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                    offset=1.0 if cfg.rms_norm_offset else 0.0)


def forward_train(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                  ) -> jnp.ndarray:
    """Full-sequence causal forward without cache. tokens [B,T] ->
    logits fp32: the reference the cached ``forward`` is compared
    against (no serving path calls it)."""
    return _lm_head(params, cfg, encode(params, cfg, tokens, rope=rope))


def _embed(params: Params, cfg: ModelConfig,
           tokens: jnp.ndarray) -> jnp.ndarray:
    x = quant.dequant_rows(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale:
        # Gemma scales embeddings by sqrt(hidden)
        x = x.astype(jnp.float32) * jnp.sqrt(float(cfg.hidden_size))
    return x.astype(cfg.dtype)


def _lm_head(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    from production_stack_tpu.ops.attention import _softcap

    def cap(logits):
        return _softcap(logits, cfg.final_logit_softcap)
    if cfg.tie_word_embeddings:
        emb = params["embed"]
        if quant.is_quantized(emb):
            # per-row scale (quantize_embed) lands on the vocab axis of
            # embed.T — apply it per logit after the int8 matmul
            logits = jnp.einsum("bth,vh->btv", x,
                                emb["w8"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            return cap(logits * emb["scale"][None, None, :])
        return cap(jnp.einsum("bth,hv->btv", x, emb.T,
                              preferred_element_type=jnp.float32))
    head = params["lm_head"]
    if quant.is_quantized(head):
        logits = jnp.einsum("bth,hv->btv", x, head["w8"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return cap(logits * head["scale"][None, None, :])
    return cap(jnp.einsum("bth,hv->btv", x, head,
                          preferred_element_type=jnp.float32))
