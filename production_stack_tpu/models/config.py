"""Model configuration for the Llama decoder family.

One config dataclass covers Llama-2/3, TinyLlama, Mistral and friends —
they differ only in dimensions, GQA ratio, rope theta and vocab. The
reference stack treats models as opaque strings passed to `vllm serve`
(reference: helm/templates/deployment-vllm-multi.yaml:57-64); here model
architecture is first-class so the engine can build/shard/jit it.
"""

import dataclasses
import json
import os
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp


def _rope_scaling_spec(rs: Optional[dict]) -> Optional[tuple]:
    """HF config.json rope_scaling dict -> the hashable spec
    ops/rope.rope_table takes. Unsupported kinds raise (serving with
    the wrong frequencies would be silently wrong logits)."""
    if not rs:
        return None
    kind = rs.get("rope_type") or rs.get("type")
    if kind in ("default", None):
        return None
    if kind == "linear":
        return ("linear", float(rs["factor"]))
    if kind == "llama3":
        return ("llama3", float(rs["factor"]),
                float(rs.get("low_freq_factor", 1.0)),
                float(rs.get("high_freq_factor", 4.0)),
                float(rs.get("original_max_position_embeddings", 8192)))
    raise ValueError(
        f"unsupported rope_scaling type {kind!r} (supported: linear, "
        f"llama3)")


# the kinds of a layer plan whose block is ONE sublayer, x +=
# f(RMSNorm(x)) (Nemotron-H; models/llama.SUBLAYERS has what each
# runs); every other kind's block is a mixer and a feed-forward part
SUBLAYER_KINDS = ("mamba2", "attn", "moe")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "debug-llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    # family variations beyond the Llama/Mistral baseline:
    # sliding-window attention (Mistral v0.1/0.2, Gemma-2 local
    # layers): each query attends only the last `sliding_window`
    # positions. None = full causal.
    sliding_window: Optional[int] = None
    # Gemma-2: every second layer (even indices) uses the sliding
    # window, odd layers are global. False = sliding_window (if any)
    # applies to every layer (Mistral).
    alternating_sliding: bool = False
    # Gemma-2 softcaps: s -> cap * tanh(s / cap) on attention scores
    # and final logits (None = off)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # Gemma-2 attention scale: 1/sqrt(query_pre_attn_scalar) instead
    # of 1/sqrt(head_dim) (None = head_dim)
    query_pre_attn_scalar: Optional[float] = None
    # sandwich norms (Gemma-2, Ouro): post-attention and
    # post-feedforward RMSNorms in ADDITION to the usual pre-norms, on
    # each sublayer's OUTPUT before it joins the residual stream
    sandwich_norms: bool = False
    # RoPE frequency scaling as a hashable spec (ops/rope.py):
    # ("linear", factor) or ("llama3", factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings). None = none.
    # Llama-3.1/3.2 checkpoints REQUIRE the llama3 warp.
    rope_scaling: Optional[tuple] = None
    attention_bias: bool = False    # Qwen2: biases on q/k/v projections
    # "silu" | "gelu_tanh" (Gemma GeGLU) | "relu2" (Nemotron-H)
    activation: str = "silu"
    rms_norm_offset: bool = False   # Gemma: y *= (1 + w), not w
    embed_scale: bool = False       # Gemma: embeddings *= sqrt(hidden)
    # MoE (Mixtral / Qwen2-MoE): 0 experts = dense MLP. capacity_factor
    # tunes the prefill dispatch's drop tradeoff (ops/moe.py); decode is
    # exact. Mixtral renormalizes the top-k weights (norm_topk_prob) and
    # has no shared expert; Qwen2-MoE keeps raw softmax weights, uses a
    # narrower per-expert FFN (moe_intermediate_size), and adds an
    # always-on shared expert with a sigmoid gate.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 2.0
    norm_topk_prob: bool = True
    moe_intermediate_size: Optional[int] = None   # default: intermediate
    shared_expert_size: int = 0                   # 0 = no shared expert
    moe_naming: str = "mixtral"   # HF weight naming: "mixtral" | "qwen2" 
    # GLM-4.7-Flash / DeepSeek-style routing (ops/moe.route): scores
    # from a sigmoid instead of a softmax, a per-expert bias that enters
    # the SELECTION only (e_score_correction_bias), a scale on the
    # renormalised weights, and a shared expert with no gate in front
    router_score: str = "softmax"            # "softmax" | "sigmoid"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = True
    # the layer plan: this many leading layers carry a dense MLP of
    # intermediate_size, the rest experts (first_k_dense_replace). They
    # run before the layer scan with parameters of their own
    # (params["dense_layers"], models/llama.py)
    first_dense_layers: int = 0
    # random weights only (llama.init_params): the sd the ROUTED
    # experts' output projection is drawn at where a configuration's
    # file states one (its ``assumed.routed_down_init_std``); None: the
    # 0.02 of every other leaf
    routed_down_init_std: Optional[float] = None
    # latent attention (MLA): kv_lora_rank > 0 turns it on. Queries go
    # through a q_lora_rank bottleneck; per token ONE latent of
    # kv_lora_rank values and one rotated key part of qk_rope_head_dim
    # are cached, shared by every head (models/kv.py, the latent pool);
    # each head's keys are qk_nope_head_dim + qk_rope_head_dim wide and
    # its values v_head_dim
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # learned sparse attention over the latent pool (GLM-5,
    # ``glm_moe_dsa``; DeepSeek-V3.2's indexer): index_topk > 0 turns it
    # on. Per token ONE index key of index_head_dim values is cached in
    # a pool of its own beside the latent pool (models/kv.py); a query
    # scores every earlier position with index_n_heads index queries
    # and attends the index_topk best (ops/dsa.py)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the chip's share of a deployment that divides each expert layer
    # over several chips: the router scores router_experts experts
    # (0: num_experts, nothing divided) and this chip holds num_experts
    # of them, from expert_offset on; it adds only what its own experts
    # give for the tokens routed to them (ops/moe.moe_mlp)
    router_experts: int = 0
    expert_offset: int = 0
    # the layer pattern: the token mixer of each layer of one PERIOD,
    # which the layer scan runs as its unit (models/llama.forward).
    # () is a period of one attention layer: every model but the
    # hybrid. "gdn" is a Gated DeltaNet layer (ops/gdn.py): per
    # sequence and layer gdn_value_heads matrices of gdn_key_dim x
    # gdn_value_dim and the last gdn_conv - 1 inputs of a depthwise
    # causal convolution over its q, k and v channels, whatever the
    # context: a state page (models/kv.py), not blocks. "ret" is a power
    # retention layer of degree 2 (ops/retention.py; Brumby): the
    # attention layer's own projections (q and k normed a head and
    # turned), a gate a key-value head, and per sequence, layer and
    # key-value head a float32 state over the ret_features monomials of
    # the key's symmetric square, the model's ONLY cache where every
    # layer is one
    layer_pattern: Tuple[str, ...] = ()
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    # gated attention (Qwen3-Next): q_proj twice as wide, a head's
    # query and an output gate; RMSNorm on each head's q and k; the
    # rotary embedding on the leading rotary_dim columns (0: all)
    attn_gate: bool = False
    qk_norm: bool = False
    rotary_dim: int = 0
    # int8 weights: a projection's float32 sums times its float32
    # scales, rounded once (models/quant.dequant_matmul exact_scale),
    # where every other model rounds the sums, the scales and their
    # product. The hybrid's 24 layers of two projections and a
    # three-matmul shared expert each read the logit probe at its
    # limit without it (0.10-0.35 of 0.3 on the chip: PERF.md, PR 42)
    exact_dequant_scale: bool = False
    # the layer plan in full: RUNS of (period, repeats), each run a scan
    # over its periods (models/llama.forward). () is the one run every
    # other model is: (pattern_, num_periods). A decoder-hybrid-decoder
    # (Phi-4-mini-flash, ``phi4flash``; SambaY) is three: ("mamba",
    # "swa") x 8, ("mamba_mem", "full") x 1, ("gmu", "cross") x 7.
    # "mamba" is a selective-scan layer (ops/mamba.py): per sequence and
    # layer a float32 state [mamba_d_state, mamba_d_inner] and the last
    # mamba_d_conv - 1 inputs of its convolution, a state page;
    # "mamba_mem" the same layer that also leaves its pre-gate output as
    # the MEMORY the later "gmu" layers (gated memory units: no cache,
    # no state) multiply into; "swa" / "full" differential attention
    # with K/V of its own (a window of sliding_window / every key);
    # "cross" differential attention whose K and V are the last "full"
    # layer's, read from that layer's pool layer, nothing appended. Such
    # a block is LayerNorm (weight and bias) -> mixer -> LayerNorm -> a
    # fused gate-up MLP, with no rotary embedding anywhere
    layer_plan: Tuple[Tuple[Tuple[str, ...], int], ...] = ()
    mamba_d_inner: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    # Nemotron-H (``nemotron_h``): a plan whose blocks are ONE sublayer
    # each, x += f(RMSNorm(x)): "mamba2" a Mamba-2 mixer (ops/mamba2.py:
    # mamba_heads heads of mamba_d_inner / mamba_heads channels, ONE
    # decay a head, B and C by mamba_groups groups of mamba_d_state, a
    # gated RMSNorm a group; the convolution runs over x, B and C:
    # mamba_conv_channels; per sequence and layer a float32 state
    # [mamba_d_state, mamba_d_inner] and the convolution's inputs), "moe"
    # the expert layer alone, "attn" grouped-query attention with no
    # rotary embedding through the ordinary paged path (SUBLAYER_KINDS).
    # mamba_heads and mamba_groups are the Mamba-2 mixers' geometry and
    # say nothing else of the model
    mamba_heads: int = 0
    mamba_groups: int = 1
    # experts WITHOUT a gate matrix: down(act(up(x))) (ops/moe.py
    # ``gate`` None), with activation "relu2" Nemotron-H's
    expert_gate: bool = True
    # a looped model (Ouro, ``ouro``; LoopLM): the WHOLE layer stack runs
    # loop_steps times on every token with the same weights, the final
    # norm closing each pass, and pass t appends to and attends over
    # K and V of its OWN: pool layer t * num_layers + l
    # (models/llama.forward_in_window; ``pool_layers``). 1: every other
    # model. exit_gate: a Linear(hidden, 1) on each pass's normed
    # stream, lambda_t = sigmoid(x . g + b), from which the share of a
    # token's exit mass a pass takes is made; every token runs every
    # pass (a threshold below 1 is refused by name: _ouro)
    loop_steps: int = 1
    exit_gate: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps} < 1")
        if self.loop_steps > 1 and (
                self.layer_pattern or self.layer_plan or self.mla
                or self.num_experts or self.sliding_window
                or self.rms_norm_offset or self.tie_word_embeddings):
            raise ValueError(
                f"{self.name}: a looped model (loop_steps "
                f"{self.loop_steps}) is built for dense full-attention "
                f"layers alone: no layer pattern or plan, no latent "
                f"attention, no experts, no sliding window, and with "
                f"plain norm weights (no rms_norm_offset) and a head of "
                f"its own (no tie_word_embeddings)")
        kinds = {k for period, _ in self.layer_plan for k in period}
        if kinds & set(SUBLAYER_KINDS) and kinds - set(SUBLAYER_KINDS):
            raise ValueError(
                f"layer_plan mixes blocks that are one sublayer "
                f"({sorted(kinds & set(SUBLAYER_KINDS))}) with two-part "
                f"blocks ({sorted(kinds - set(SUBLAYER_KINDS))}): the "
                f"parameter tree and num_params are built for one or the "
                f"other")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def rope_dim_(self) -> int:
        """Width the rotary embedding turns: the whole head, or the
        rope part of a latent-attention head."""
        if self.mla:
            return self.qk_rope_head_dim
        return self.rotary_dim or self.head_dim_

    @property
    def pattern_(self) -> Tuple[str, ...]:
        """The mixers of one period of layers, in order."""
        return self.layer_pattern or ("attn",)

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.pattern_)

    @property
    def plan_(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        """The runs of (period, repeats) the layer loop scans, in
        order: one run for every model but a decoder-hybrid-decoder."""
        return self.layer_plan or ((self.pattern_, self.num_periods),)

    def kind_layers(self, *kinds: str) -> int:
        """Layers of the plan whose mixer is one of ``kinds``."""
        return sum(period.count(k) * repeats
                   for period, repeats in self.plan_ for k in kinds)

    @property
    def attn_layers(self) -> int:
        """Layers that keep K and V (or latents) a token: the KV pool's
        leading axis."""
        return self.kind_layers("attn", "swa", "full")

    @property
    def pool_layers(self) -> int:
        """Layers of the K/V pool: one a layer that keeps K and V, and
        of a looped model one a layer and PASS (every pass keeps K and
        V of its own)."""
        return self.attn_layers * self.loop_steps

    @property
    def reader_layers(self) -> int:
        """Layers that READ a pool layer: those that keep K and V (a
        pass each, in a looped model) and the cross layers, which read
        another layer's."""
        return self.pool_layers + self.kind_layers("cross")

    @property
    def mamba_layers(self) -> int:
        """Selective-scan layers: a state page a sequence."""
        return self.kind_layers("mamba", "mamba_mem", "mamba2")

    @property
    def mamba_conv_channels(self) -> int:
        """Channels of a Mamba layer's convolution: x, and for Mamba-2
        every group's B and C beside it."""
        return self.mamba_d_inner + (
            2 * self.mamba_groups * self.mamba_d_state
            if self.kind_layers("mamba2") else 0)

    @property
    def expert_layers(self) -> int:
        """Layers that hold experts: a plan's "moe" blocks, else every
        layer after the leading dense ones."""
        if not self.num_experts:
            return 0
        return (self.kind_layers("moe") if self.layer_plan
                else self.num_layers - self.first_dense_layers)

    @property
    def sublayer_plan(self) -> bool:
        """Is every block of the layer plan ONE sublayer (SUBLAYER_KINDS;
        __post_init__ refuses a plan that mixes them with two-part
        blocks)?"""
        return any(k in SUBLAYER_KINDS for period, _ in self.layer_plan
                   for k in period)

    @property
    def moe_stored_size(self) -> int:
        """The width the expert stacks hold, which the kernels read:
        the published one, but in a one-sublayer plan the next multiple
        of the 128 lanes (models/llama._init_params_sublayers: zero
        columns of ``up`` and zero rows of ``down``, relu(0)^2 = 0, so
        the mathematics, and num_params, are those of the published
        width). The kernels that read experts in place copy whole
        vectors of lanes."""
        mi = self.moe_intermediate_size or self.intermediate_size
        return -(-mi // 128) * 128 if self.sublayer_plan else mi

    @property
    def differential(self) -> bool:
        """Does the plan hold differential-attention layers (a
        decoder-hybrid-decoder's)?"""
        return bool(self.kind_layers("swa", "full", "cross"))

    @property
    def self_layers(self) -> int:
        """Layers a prefill chunk runs on EVERY position: those before
        the first run that reads a memory or another layer's K/V; the
        rest run on a row's last prompt position alone
        (models/llama.forward ``last``). num_layers: no such run."""
        done = 0
        for period, repeats in self.plan_:
            if "gmu" in period or "cross" in period:
                return done
            done += len(period) * repeats
        return self.num_layers

    @property
    def window_everywhere(self) -> Optional[int]:
        """sliding_window where EVERY layer that attends is windowed
        (Mistral v0.1): a block wholly behind the window is of no use
        to any layer and the engine gives it back (engine.
        _roll_windows). None where some layer sees every key: Gemma-2's
        global layers, a decoder-hybrid-decoder's full and cross
        layers, which share the window layers' block table."""
        if (self.alternating_sliding
                or self.kind_layers("full", "cross")):
            return None
        return self.sliding_window or None

    @property
    def pool_kv_heads(self) -> int:
        """Heads of the K/V pool. Differential attention pairs its
        key heads ([k1 | k2], one value of twice the width): half as
        many heads, twice as wide, the same bytes a token."""
        return self.num_kv_heads // 2 if self.differential \
            else self.num_kv_heads

    @property
    def pool_head_dim(self) -> int:
        return 2 * self.head_dim_ if self.differential else self.head_dim_

    @property
    def gdn_layers(self) -> int:
        """Layers that keep a state a sequence: the state pool's."""
        return self.kind_layers("gdn")

    @property
    def ret_layers(self) -> int:
        """Power retention layers: state a sequence, no K or V."""
        return self.kind_layers("ret")

    @property
    def ret_features(self) -> int:
        """Monomials of the symmetric square of a head's key."""
        return self.head_dim_ * (self.head_dim_ + 1) // 2

    @property
    def state_layers(self) -> int:
        """Layers that keep state pages, of any kind."""
        return self.gdn_layers + self.ret_layers + self.mamba_layers

    @property
    def gdn_channels(self) -> int:
        """Channels of a Gated DeltaNet layer's convolution: q, k, v."""
        return (2 * self.gdn_key_heads * self.gdn_key_dim
                + self.gdn_value_heads * self.gdn_value_dim)

    @property
    def state_bytes_per_seq(self) -> int:
        """Bytes of one state page, all layers: the float32 matrices
        and the convolution's bfloat16 inputs of a Gated DeltaNet
        layer; a power retention layer's float32 ``S`` and ``z`` a
        key-value head; a selective-scan layer's float32 state and its
        convolution's inputs, by kind (a Mamba-2 layer's convolution
        holds B and C too) (0: no such layer)."""
        return (self.mamba_layers * (
            4 * self.mamba_d_state * self.mamba_d_inner
            + 2 * (self.mamba_d_conv - 1) * self.mamba_conv_channels)
            + self.gdn_layers * (
            4 * self.gdn_value_heads * self.gdn_key_dim
            * self.gdn_value_dim + 2 * (self.gdn_conv - 1)
            * self.gdn_channels)
            + self.ret_layers * 4 * self.num_kv_heads
            * self.ret_features * (self.head_dim_ + 1))

    @property
    def latent_dim(self) -> int:
        """Values the latent pool holds per token and layer: the
        normalised latent and the rotated key part (0: no MLA)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_experts_(self) -> int:
        """The router's outputs: every expert of the layer, of which
        num_experts are held here."""
        return self.router_experts or self.num_experts

    @property
    def num_params(self) -> int:
        """Parameters HELD here: the experts of this chip's share, the
        vocabulary's rows as sliced, the indexer."""
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd, nh = self.head_dim_, self.num_heads
        if self.sublayer_plan:
            # one sublayer a block: its norm and (Mamba-2) in_proj, the
            # convolution and its bias, A_log, dt_bias, D, the gated
            # norm, out_proj; (attention) q, k, v, o; (experts) router
            # and bias, the shared expert, the held experts at the
            # PUBLISHED width; embedding, head, final norm
            di, ch = self.mamba_d_inner, self.mamba_conv_channels
            mi = self.moe_intermediate_size
            mamba2 = (h * (di + ch + self.mamba_heads) + di * h
                      + ch * (self.mamba_d_conv + 1)
                      + 3 * self.mamba_heads + di)
            attn = 2 * h * nh * hd + 2 * h * self.num_kv_heads * hd
            moe = (h * self.router_experts_ + self.router_experts_
                   + 2 * h * self.shared_expert_size
                   + self.num_experts * 2 * h * mi)
            return (self.kind_layers("mamba2") * mamba2
                    + self.kind_layers("attn") * attn
                    + self.kind_layers("moe") * moe
                    + self.num_layers * h + 2 * v * h + h)
        if self.layer_plan:     # a decoder-hybrid-decoder's two-part blocks
            di, ds, r = (self.mamba_d_inner, self.mamba_d_state,
                         self.mamba_dt_rank)
            kv, lam = 2 * self.num_kv_heads * hd, 4 * hd + 2 * hd
            mamba = (h * 2 * di + di * h + di * (r + 2 * ds) + r * di
                     + di + (self.mamba_d_conv + 1) * di + di * ds + di)
            own = h * (nh * hd + kv) + nh * hd + kv + nh * hd * h + h + lam
            cross = 2 * (h * nh * hd) + nh * hd + h + lam
            block = 4 * h + 3 * h * i           # two LayerNorms, fc1, fc2
            return (self.mamba_layers * mamba + self.attn_layers * own
                    + self.kind_layers("gmu") * 2 * h * di
                    + self.kind_layers("cross") * cross
                    + self.num_layers * block + v * h + 2 * h)
        E = self.num_experts
        dense = 3 * h * i
        if E:
            mi = self.moe_intermediate_size or i
            mlp = 3 * h * mi * E + h * self.router_experts_
            if self.router_bias:
                mlp += self.router_experts_
            if self.shared_expert_size:
                mlp += (3 * h * self.shared_expert_size
                        + (h if self.shared_expert_gate else 0))
        else:
            mlp = dense
        if self.mla:
            qr, kr = self.q_lora_rank, self.kv_lora_rank
            attn = (h * qr + qr                              # q_a, norm
                    + qr * nh * (self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)    # q_b
                    + h * self.latent_dim + kr               # kv_a, norm
                    + kr * nh * (self.qk_nope_head_dim
                                 + self.v_head_dim)          # kv_b
                    + nh * self.v_head_dim * h)              # o
            if self.index_topk:
                hi, di = self.index_n_heads, self.index_head_dim
                attn += (qr * hi * di + h * di + 2 * di      # q, k, norm
                         + h * hi)                           # head weights
        else:
            attn = (h * (nh * hd) * (2 if self.attn_gate else 1)  # q(, gate)
                    + 2 * h * (self.num_kv_heads * hd)   # k, v
                    + (nh * hd) * h                      # o
                    + (2 * hd if self.qk_norm else 0))
        emb = v * h * (1 if self.tie_word_embeddings else 2)
        Ld = self.first_dense_layers if E else 0
        rest = Ld * dense + (self.num_layers - Ld) * mlp + emb + h
        if self.gdn_layers:
            hv, dv = self.gdn_value_heads, self.gdn_value_dim
            gdn = (h * (self.gdn_channels + hv * dv)     # q, k, v, z
                   + h * 2 * hv                          # b, a
                   + self.gdn_channels * self.gdn_conv   # convolution
                   + hv * dv * h                         # out
                   + 2 * hv + dv)                        # A_log, dt, norm
            return (self.gdn_layers * gdn + self.attn_layers * attn
                    + self.num_layers * 2 * h + rest)
        if self.ret_layers:      # the gate a key-value head, its bias
            attn += h * self.num_kv_heads + self.num_kv_heads
        per_layer = attn + 2 * h                         # + norms
        if self.sandwich_norms:
            per_layer += 2 * h
        # a looped model's weights count ONCE, whatever its passes; the
        # exit gate is a Linear(hidden, 1) with its bias
        return (self.num_layers * per_layer + rest
                + (h + 1 if self.exit_gate else 0))

    @staticmethod
    def from_hf_config(cfg: Dict[str, Any], name: str = "",
                       dtype: Any = jnp.bfloat16) -> "ModelConfig":
        """Map a HuggingFace config dict onto ModelConfig.

        Families: Llama-2/3, TinyLlama, Mistral (the baseline), Qwen2
        (adds q/k/v biases), Gemma (GeGLU via gelu, scaled embeddings,
        unit-offset RMSNorm, tied embeddings), Gemma-2, Mixtral,
        Qwen2-MoE, GLM-4.7-Flash (``glm4_moe_lite``) and GLM-5
        (``glm_moe_dsa``), both through _glm4_moe_lite, Qwen3-Next
        (``qwen3_next``, _qwen3_next), Brumby (``brumby``, _brumby),
        Phi-4-mini-flash (``phi4flash``, _phi4flash), Nemotron-H
        (``nemotron_h``, _nemotron_h) and Ouro (``ouro``, _ouro).
        Keys the mapping does not know are ignored.
        """
        archs = cfg.get("architectures") or []
        arch = archs[0] if archs else ""
        model_type = cfg.get("model_type", "")
        # EXACT family matching: substring checks would silently accept
        # e.g. Gemma2ForCausalLM (softcapping, extra norms) or
        # Qwen2MoeForCausalLM as their simpler cousins and serve garbage
        is_qwen2 = model_type == "qwen2" or arch == "Qwen2ForCausalLM"
        is_gemma = model_type == "gemma" or arch == "GemmaForCausalLM"
        is_gemma2 = (model_type == "gemma2"
                     or arch == "Gemma2ForCausalLM")
        is_mixtral = (model_type == "mixtral"
                      or arch == "MixtralForCausalLM")
        is_qwen2_moe = (model_type == "qwen2_moe"
                        or arch == "Qwen2MoeForCausalLM")
        is_glm_lite = (model_type in ("glm4_moe_lite", "glm_moe_dsa")
                       or arch in ("Glm4MoeLiteForCausalLM",
                                   "GlmMoeDsaForCausalLM"))
        is_llama_like = (model_type in ("llama", "mistral") or arch in
                         ("LlamaForCausalLM", "MistralForCausalLM"))
        if model_type == "qwen3_next" or arch == "Qwen3NextForCausalLM":
            return _qwen3_next(cfg, name, dtype)
        if model_type == "brumby" or arch == "BrumbyForCausalLM":
            return _brumby(cfg, name, dtype)
        if model_type == "phi4flash" or arch == "Phi4FlashForCausalLM":
            return _phi4flash(cfg, name, dtype)
        if model_type == "nemotron_h" or arch == "NemotronHForCausalLM":
            return _nemotron_h(cfg, name, dtype)
        if model_type == "ouro" or arch == "OuroForCausalLM":
            return _ouro(cfg, name, dtype)
        if not (is_qwen2 or is_gemma or is_gemma2 or is_mixtral
                or is_qwen2_moe or is_glm_lite
                or is_llama_like) and (model_type or arch):
            raise ValueError(
                f"unsupported model family (model_type={model_type!r}, "
                f"architecture={arch!r}); supported: llama, mistral, "
                f"qwen2, gemma, gemma2, mixtral, qwen2_moe, "
                f"glm4_moe_lite, glm_moe_dsa, qwen3_next, brumby, "
                f"phi4flash, nemotron_h, ouro")
        if is_glm_lite:
            return _glm4_moe_lite(cfg, name, dtype)
        if is_qwen2_moe:
            if (cfg.get("decoder_sparse_step", 1) != 1
                    or cfg.get("mlp_only_layers")):
                raise ValueError(
                    "qwen2_moe with dense interleaving "
                    "(decoder_sparse_step != 1 or mlp_only_layers) is "
                    "not supported: every layer must be sparse")
        gemmaish = is_gemma or is_gemma2
        hidden_act = cfg.get("hidden_act") or cfg.get(
            "hidden_activation") or ("gelu_tanh" if gemmaish else "silu")
        return ModelConfig(
            name=name or cfg.get("_name_or_path", "hf-model"),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            # Mistral v0.1/0.2 ship sliding_window in config.json; null
            # (v0.3+) and absent both mean full causal. Mixtral configs
            # carry the field but HF/vLLM ignore it for that family.
            sliding_window=(cfg.get("sliding_window")
                            if (is_llama_like or is_gemma2) else None),
            alternating_sliding=is_gemma2,
            attn_logit_softcap=(cfg.get("attn_logit_softcapping")
                                if is_gemma2 else None),
            final_logit_softcap=(cfg.get("final_logit_softcapping")
                                 if is_gemma2 else None),
            query_pre_attn_scalar=(cfg.get("query_pre_attn_scalar")
                                   if is_gemma2 else None),
            sandwich_norms=is_gemma2,
            rope_scaling=_rope_scaling_spec(cfg.get("rope_scaling")),
            tie_word_embeddings=cfg.get("tie_word_embeddings", gemmaish),
            attention_bias=cfg.get("attention_bias",
                                   is_qwen2 or is_qwen2_moe),
            activation="gelu_tanh" if "gelu" in hidden_act else "silu",
            rms_norm_offset=gemmaish,
            embed_scale=gemmaish,
            num_experts=(cfg.get("num_local_experts", 0) if is_mixtral
                         else cfg.get("num_experts", 0) if is_qwen2_moe
                         else 0),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            # HF Qwen2MoeConfig defaults norm_topk_prob to FALSE — a
            # missing key must not flip routing to Mixtral semantics
            norm_topk_prob=cfg.get("norm_topk_prob", False)
            if is_qwen2_moe else True,
            moe_intermediate_size=cfg.get("moe_intermediate_size")
            if is_qwen2_moe else None,
            shared_expert_size=cfg.get("shared_expert_intermediate_size",
                                       0) if is_qwen2_moe else 0,
            moe_naming="qwen2" if is_qwen2_moe else "mixtral",
            dtype=dtype,
        )

    @staticmethod
    def from_json(path: str, dtype: Any = jnp.bfloat16) -> "ModelConfig":
        with open(os.path.join(path, "config.json") if os.path.isdir(path) else path) as f:
            return ModelConfig.from_hf_config(json.load(f), name=path, dtype=dtype)


def _glm4_moe_lite(cfg: Dict[str, Any], name: str,
                   dtype: Any) -> ModelConfig:
    """GLM-4.7-Flash (``glm4_moe_lite``) and GLM-5 (``glm_moe_dsa``):
    latent attention, leading dense layers, a sigmoid router with a
    selection bias and a routing scale, ungated shared experts; GLM-5
    adds the sparse-attention indexer (``index_n_heads``,
    ``index_head_dim``, ``index_topk``: optional keys). What the tree
    does not build is refused by name; the multi-token-prediction block
    (``num_nextn_predict_layers``) is not built and not served, as HF's
    own model class drops those weights on load.

    A file may state the chip's share of a deployment that divides each
    expert layer over several chips (``deployment``: {"router_experts":
    the published n_routed_experts, "chips_per_layer", "chip_index"}):
    ``n_routed_experts`` is then the experts held here, which are those
    from chip_index x n_routed_experts on, and the router keeps its
    published width."""
    family = cfg.get("model_type", "glm4_moe_lite")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError(
            f"{family} with grouped routing (n_group / topk_group "
            f"!= 1) is not supported")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"{family} topk_method "
                         f"{cfg['topk_method']!r} is not supported "
                         f"(supported: noaux_tc)")
    if cfg.get("partial_rotary_factor", 1) != 1:
        raise ValueError(f"{family} with partial_rotary_factor != 1 "
                         f"is not supported")
    if not cfg.get("q_lora_rank"):
        raise ValueError(f"{family} without q_lora_rank (full-rank "
                         f"queries) is not supported")
    if cfg.get("attention_bias"):
        raise ValueError(f"{family} with attention_bias is not "
                         f"supported")
    held = cfg["n_routed_experts"]
    router_experts, expert_offset = _deployment(cfg, family, held)
    topk = cfg.get("index_topk", 0)
    if topk and not (cfg.get("index_n_heads") and cfg.get("index_head_dim")):
        raise ValueError(f"{family}: index_topk without index_n_heads "
                         f"and index_head_dim")
    if topk and cfg.get("index_head_dim") < cfg["qk_rope_head_dim"]:
        raise ValueError(f"{family}: index_head_dim below "
                         f"qk_rope_head_dim is not supported")
    # GLM-5 keeps the rotary base inside rope_parameters
    rope_params = cfg.get("rope_parameters") or {}
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        # one latent serves every head: the cache has one "kv head"
        num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        rope_theta=cfg.get("rope_theta",
                           rope_params.get("rope_theta", 10000.0)),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        rope_scaling=_rope_scaling_spec(
            cfg.get("rope_scaling")
            or (rope_params if "rope_type" in rope_params else None)),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        num_experts=held,
        router_experts=router_experts, expert_offset=expert_offset,
        index_n_heads=cfg.get("index_n_heads", 0) if topk else 0,
        index_head_dim=cfg.get("index_head_dim", 0) if topk else 0,
        index_topk=topk,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_size=(cfg.get("n_shared_experts", 0)
                            * cfg["moe_intermediate_size"]),
        moe_naming="glm4_moe_lite",
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_expert_gate=False,
        first_dense_layers=cfg.get("first_k_dense_replace", 0),
        routed_down_init_std=(cfg.get("assumed") or {}).get(
            "routed_down_init_std"),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        dtype=dtype,
    )


def _deployment(cfg: Dict[str, Any], family: str, held: int):
    """(router_experts, expert_offset) of a file's ``deployment``
    ({"router_experts", "chips_per_layer", "chip_index"}): the chip
    holds ``held`` experts of the router's, from chip_index x held
    on. No deployment: (0, 0), nothing divided."""
    deployment = cfg.get("deployment") or {}
    router_experts = deployment.get("router_experts", held)
    chips = deployment.get("chips_per_layer", 1)
    chip = deployment.get("chip_index", 0)
    if held * chips != router_experts or not 0 <= chip < chips:
        raise ValueError(
            f"{family}: a deployment of {chips} chips a layer, each "
            f"holding {held} experts, does not make the router's "
            f"{router_experts} (chip_index {chip})")
    return (router_experts if chips > 1 else 0), chip * held


def _qwen3_next(cfg: Dict[str, Any], name: str, dtype: Any) -> ModelConfig:
    """Qwen3-Next (``qwen3_next``): of every ``full_attention_interval``
    layers the last is gated softmax attention (a head's query and an
    output gate from one q_proj, zero-centred RMSNorm on each head's q
    and k, the rotary embedding on the leading ``partial_rotary_factor``
    of the head) and the others Gated DeltaNet (``linear_*``); every
    layer a softmax-routed mixture of experts with one gated shared
    expert; every RMSNorm zero-centred (``norm(x) * (1 + w)``). What
    the tree does not build is refused by name; the multi-token-
    prediction block is not built, as HF's class drops ``mtp.*``. A
    file may state the chip's share of the experts (``deployment``, as
    _glm4_moe_lite)."""
    family = "qwen3_next"
    interval = cfg.get("full_attention_interval", 4)
    layers = cfg["num_hidden_layers"]
    types = cfg.get("layer_types")
    pattern = ("gdn",) * (interval - 1) + ("attn",)
    if types is not None and list(types) != [
            {"gdn": "linear_attention", "attn": "full_attention"}[
                pattern[i % interval]] for i in range(layers)]:
        raise ValueError(f"{family}: layer_types that are not "
                         f"full_attention_interval's pattern are not "
                         f"supported")
    if interval < 2 or layers % interval:
        raise ValueError(
            f"{family}: num_hidden_layers {layers} is not whole periods "
            f"of full_attention_interval {interval} (at least 2)")
    if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
        raise ValueError(f"{family} with dense interleaving "
                         f"(decoder_sparse_step != 1 or mlp_only_layers) "
                         f"is not supported: every layer must be sparse")
    for key, refused in (("attention_bias", True),
                         ("use_sliding_window", True),
                         ("tie_word_embeddings", True)):
        if cfg.get(key, False) is refused:
            raise ValueError(f"{family} with {key} is not supported")
    if cfg.get("rope_scaling"):
        raise ValueError(f"{family} with rope_scaling is not supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{family} hidden_act "
                         f"{cfg['hidden_act']!r} is not supported")
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    if hv % hk:
        raise ValueError(f"{family}: linear_num_value_heads {hv} is "
                         f"not a multiple of linear_num_key_heads {hk}")
    head_dim = cfg.get("head_dim") or (cfg["hidden_size"]
                                       // cfg["num_attention_heads"])
    rotary = int(head_dim * cfg.get("partial_rotary_factor", 1.0))
    router_experts, offset = _deployment(cfg, family, cfg["num_experts"])
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg.get("intermediate_size",
                                  cfg["moe_intermediate_size"]),
        num_layers=layers,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim,
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        rms_norm_offset=True,
        num_experts=cfg["num_experts"],
        router_experts=router_experts, expert_offset=offset,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_size=cfg.get("shared_expert_intermediate_size", 0),
        moe_naming="qwen2",
        routed_down_init_std=(cfg.get("assumed") or {}).get(
            "routed_down_init_std"),
        layer_pattern=pattern,
        gdn_key_heads=hk, gdn_value_heads=hv,
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg.get("linear_conv_kernel_dim", 4),
        attn_gate=True, qk_norm=True,
        rotary_dim=0 if rotary == head_dim else rotary,
        exact_dequant_scale=True,
        dtype=dtype,
    )


def _brumby(cfg: Dict[str, Any], name: str, dtype: Any) -> ModelConfig:
    """Brumby (``brumby``; Manifest AI's retraining of Qwen3-14B):
    Qwen3's pre-norm block with SwiGLU, and in EVERY layer a power
    retention mixer of degree 2 in the attention's place
    (ops/retention.py): q, k, v, o as published, RMSNorm on each head's
    q and k and the rotary embedding on both (kept from Qwen3), a gate
    a key-value head. ``config.json`` has no key for the degree, the
    gate, the normaliser's eps or the scale of q . k: the release's and
    the ``retention`` package's as known here, listed under ``assumed``
    in the benchmark's file. What the tree does not build is refused by
    name."""
    family = "brumby"
    for key, refused in (("attention_bias", True),
                         ("use_sliding_window", True),
                         ("tie_word_embeddings", True)):
        if cfg.get(key, False) is refused:
            raise ValueError(f"{family} with {key} is not supported")
    if cfg.get("sliding_window"):
        raise ValueError(f"{family} with sliding_window is not supported")
    if cfg.get("rope_scaling"):
        raise ValueError(f"{family} with rope_scaling is not supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{family} hidden_act "
                         f"{cfg['hidden_act']!r} is not supported")
    heads = cfg["num_attention_heads"]
    kv_heads = cfg.get("num_key_value_heads", heads)
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    if heads % kv_heads or kv_heads % 2 or head_dim % 2:
        raise ValueError(
            f"{family}: {heads} query heads over {kv_heads} key-value "
            f"heads of {head_dim} is not supported (whole groups; the "
            f"state's layout wants both of the latter even)")
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        layer_pattern=("ret",), qk_norm=True,
        dtype=dtype,
    )


def _phi4flash(cfg: Dict[str, Any], name: str, dtype: Any) -> ModelConfig:
    """Phi-4-mini-flash (``phi4flash``): SambaY, a decoder-hybrid-
    decoder (Ren et al., arXiv 2507.06607) with differential attention.
    The first half of the layers is the SELF-decoder, periods of
    ``mb_per_layer`` layers of which the first is a Mamba layer and
    the last attention with its own K/V, windowed
    (``sliding_window``); the second half, the CROSS-decoder, opens
    with one more such period whose attention sees every key, and its
    other periods are a gated memory unit on that period's Mamba
    layer's pre-gate output and a cross-attention layer on that
    period's attention layer's K and V. Every
    block LayerNorm (weight and bias), a fused gate-up MLP, no rotary
    embedding. ``config.json`` has no key for the Mamba sizes
    (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` =
    ceil(hidden / 16): Mamba's defaults) nor for the differential
    attention's constants: a benchmark's file lists them under
    ``assumed``, and its ``assumed`` numbers (``mamba_d_state``,
    ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``) are read
    from there. What the tree does not build is refused by name."""
    family = "phi4flash"
    layers, per = cfg["num_hidden_layers"], cfg.get("mb_per_layer", 2)
    if per != 2 or layers % 4 or layers < 8:
        raise ValueError(
            f"{family}: mb_per_layer {per} with {layers} layers is not "
            f"supported (periods of 2: a Mamba layer, an attention "
            f"layer; two halves of whole periods, two periods at least "
            f"in the first)")
    for key, refused in (("tie_word_embeddings", False),
                         ("mlp_bias", True), ("lm_head_bias", True)):
        if cfg.get(key, not refused) is refused:
            raise ValueError(f"{family} with {key} = {refused} is not "
                             f"supported")
    if not cfg.get("sliding_window"):
        raise ValueError(f"{family} without sliding_window is not "
                         f"supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{family} hidden_act "
                         f"{cfg['hidden_act']!r} is not supported")
    heads = cfg["num_attention_heads"]
    kv_heads = cfg.get("num_key_value_heads", heads)
    hidden = cfg["hidden_size"]
    if heads % 2 or kv_heads % 2 or heads % kv_heads or hidden % heads:
        raise ValueError(
            f"{family}: {heads} query heads over {kv_heads} key-value "
            f"heads is not supported (differential attention pairs the "
            f"heads of both)")
    assumed = cfg.get("assumed") or {}
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"], num_layers=layers,
        num_heads=heads, num_kv_heads=kv_heads,
        head_dim=hidden // heads,
        rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        sliding_window=cfg["sliding_window"], tie_word_embeddings=True,
        attention_bias=True,
        layer_plan=((("mamba", "swa"), layers // 4),
                    (("mamba_mem", "full"), 1),
                    (("gmu", "cross"), layers // 4 - 1)),
        mamba_d_inner=assumed.get("mamba_expand", 2) * hidden,
        mamba_d_state=assumed.get("mamba_d_state", 16),
        mamba_d_conv=assumed.get("mamba_d_conv", 4),
        mamba_dt_rank=assumed.get("mamba_dt_rank", -(-hidden // 16)),
        exact_dequant_scale=True,
        dtype=dtype,
    )


def plan_runs(kinds) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """The runs of (period, repeats) that spell ``kinds`` (a block's
    kind each, in order) with the FEWEST traced sublayers (the periods'
    lengths summed; among equals the fewest runs): the layer loop
    traces a period once and scans its repeats. Nemotron-3-Nano's 52
    letters come out as (M E M E M * E) x 5, (M E) x 3, (M * E) x 1,
    (M E) x 4: 14 traced sublayers."""
    kinds = tuple(kinds)
    n = len(kinds)
    # best[i]: (traced sublayers, runs, the runs) of the blocks from i on
    best = [None] * n + [(0, 0, ())]
    for i in range(n - 1, -1, -1):
        for p in range(1, n - i + 1):
            period, most = kinds[i:i + p], 1
            while kinds[i + p * most:i + p * (most + 1)] == period:
                most += 1
            for reps in range(1, most + 1):
                cost, runs, tail = best[i + p * reps]
                cand = (cost + p, runs + 1, ((period, reps),) + tail)
                if best[i] is None or cand[:2] < best[i][:2]:
                    best[i] = cand
    return best[0][2]


def _nemotron_h(cfg: Dict[str, Any], name: str, dtype: Any) -> ModelConfig:
    """Nemotron-H (``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B,
    arXiv 2504.03624): ``hybrid_override_pattern`` spells the blocks, M
    a Mamba-2 mixer (arXiv 2405.21060), E the expert layer, * grouped-
    query attention; EACH BLOCK IS ONE SUBLAYER, x += f(RMSNorm(x)).
    The router is DeepSeek-V3's (sigmoid scores, a selection bias,
    renormalised, times ``routed_scaling_factor``); an expert is
    ``down(relu(up(x))^2)``, no gate, and one shared expert of the same
    form is added with no gate in front; the attention has no rotary
    embedding (``rope_theta`` stands in the published file unused); the
    head is untied. ``chunk_size`` is the chunked scan's own 128
    (ops/mamba2.CHUNK); the result does not depend on it. A file may
    state the chip's share of the experts (``deployment``, as
    _glm4_moe_lite). What the tree does not build is refused by
    name."""
    family = "nemotron_h"
    letters = cfg["hybrid_override_pattern"]
    layers = cfg["num_hidden_layers"]
    if len(letters) != layers or set(letters) - set("ME*"):
        raise ValueError(
            f"{family}: hybrid_override_pattern {letters!r} is not "
            f"{layers} blocks of M, E and * (a dense MLP block, '-', is "
            f"not supported)")
    for key in ("attention_bias", "mlp_bias", "use_bias",
                "mamba_proj_bias", "tie_word_embeddings"):
        if cfg.get(key, False):
            raise ValueError(f"{family} with {key} is not supported")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError(f"{family} with grouped routing (n_group / "
                         f"topk_group != 1) is not supported")
    if cfg.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"{family} mlp_hidden_act "
                         f"{cfg['mlp_hidden_act']!r} is not supported")
    if cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"{family} mamba_hidden_act "
                         f"{cfg['mamba_hidden_act']!r} is not supported")
    if cfg.get("sliding_window"):
        raise ValueError(f"{family} with sliding_window is not supported")
    if not cfg.get("use_conv_bias", True):
        raise ValueError(f"{family} without use_conv_bias is not "
                         f"supported")
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    if heads % groups:
        raise ValueError(f"{family}: mamba_num_heads {heads} is not a "
                         f"multiple of n_groups {groups}")
    held = cfg["n_routed_experts"]
    router_experts, offset = _deployment(cfg, family, held)
    assumed = cfg.get("assumed") or {}
    mi = cfg["moe_intermediate_size"]
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg.get("intermediate_size", mi),
        num_layers=layers, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        rms_norm_eps=cfg.get("layer_norm_epsilon",
                             cfg.get("norm_eps", 1e-5)),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        activation="relu2", expert_gate=False,
        num_experts=held, router_experts=router_experts,
        expert_offset=offset,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        moe_intermediate_size=mi,
        shared_expert_size=(cfg.get("n_shared_experts", 0) * cfg.get(
            "moe_shared_expert_intermediate_size", mi)),
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_expert_gate=False,
        routed_down_init_std=assumed.get("routed_down_init_std"),
        layer_plan=plan_runs(
            {"M": "mamba2", "E": "moe", "*": "attn"}[c] for c in letters),
        mamba_d_inner=heads * cfg["mamba_head_dim"],
        mamba_d_state=cfg["ssm_state_size"],
        mamba_d_conv=cfg.get("conv_kernel", 4),
        mamba_heads=heads, mamba_groups=groups,
        exact_dequant_scale=True,
        dtype=dtype,
    )


def _ouro(cfg: Dict[str, Any], name: str, dtype: Any) -> ModelConfig:
    """Ouro (``ouro``; ByteDance Ouro-1.4B / 2.6B, "Scaling Latent
    Reasoning via Looped Language Models", arXiv 2510.25741): a decoder
    of Llama-like blocks with sandwich norms (plain RMSNorm weights,
    ``y = w * x / rms``) whose WHOLE stack runs ``total_ut_steps`` times
    on every token with the same weights; the final norm closes every
    pass and its output is the next pass's input; each pass keeps K and
    V of its own; an exit gate (Linear(hidden, 1)) reads each pass's
    normed stream. The published ``early_exit_threshold`` is 1: a
    sigmoid is below 1, every token runs every pass and the last pass's
    logits are served. What the tree does not build is refused by
    name."""
    family = "ouro"
    threshold = cfg.get("early_exit_threshold", 1.0)
    if threshold < 1.0:
        raise ValueError(
            f"{family}: early_exit_threshold {threshold} below 1 is not "
            f"supported: rows of one batch would leave the layer loop at "
            f"different passes, which the scheduler and the step "
            f"programs do not build (every token runs all "
            f"total_ut_steps passes)")
    if cfg.get("sliding_window") and cfg.get("use_sliding_window", False):
        raise ValueError(f"{family} with a sliding window "
                         f"(use_sliding_window) is not supported")
    if set(cfg.get("layer_types") or ()) - {"full_attention"}:
        raise ValueError(f"{family}: layer_types other than "
                         f"full_attention are not supported")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError(f"{family} with tie_word_embeddings is not "
                         f"supported")
    if cfg.get("rope_scaling"):
        raise ValueError(f"{family} with rope_scaling "
                         f"{cfg['rope_scaling']!r} is not supported")
    for key in ("attention_bias", "mlp_bias"):
        if cfg.get(key, False):
            raise ValueError(f"{family} with {key} is not supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{family} hidden_act {cfg['hidden_act']!r} is "
                         f"not supported")
    heads = cfg["num_attention_heads"]
    kv_heads = cfg.get("num_key_value_heads", heads)
    if heads % kv_heads:
        raise ValueError(f"{family}: num_attention_heads {heads} is not "
                         f"a multiple of num_key_value_heads {kv_heads}")
    steps = int(cfg.get("total_ut_steps", 1))
    if steps < 1:
        raise ValueError(f"{family}: total_ut_steps {steps} < 1")
    return ModelConfig(
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=kv_heads, head_dim=cfg.get("head_dim"),
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        sandwich_norms=True, loop_steps=steps, exit_gate=True,
        dtype=dtype,
    )


# ---------------------------------------------------------------------------
# Presets. Dimensions are the publicly documented architecture shapes.
# ---------------------------------------------------------------------------

PRESETS: Dict[str, ModelConfig] = {
    # Tiny model for CPU tests — intentionally small, MXU-aligned dims.
    "debug-tiny": ModelConfig(
        name="debug-tiny", vocab_size=512, hidden_size=128,
        intermediate_size=384, num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=512,
    ),
    "tinyllama-1.1b": ModelConfig(
        name="tinyllama-1.1b", vocab_size=32000, hidden_size=2048,
        intermediate_size=5632, num_layers=22, num_heads=32, num_kv_heads=4,
        max_position_embeddings=2048,
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=500000.0, max_position_embeddings=8192,
    ),
    # Llama-3.1: same shapes as 3.0 but 128k context via the llama3
    # rope warp (ops/rope.py)
    "llama-3.1-8b": ModelConfig(
        name="llama-3.1-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32,
        num_kv_heads=8, rope_theta=500000.0,
        max_position_embeddings=131072,
        rope_scaling=("llama3", 8.0, 1.0, 4.0, 8192),
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        rope_theta=500000.0, max_position_embeddings=8192,
    ),
    # Llama-3.2 small models: 3.1-style rope warp (factor 32), tied
    # embeddings
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32,
        num_kv_heads=8, head_dim=64, rope_theta=500000.0,
        max_position_embeddings=131072, tie_word_embeddings=True,
        rope_scaling=("llama3", 32.0, 1.0, 4.0, 8192),
    ),
    "llama-3.2-3b": ModelConfig(
        name="llama-3.2-3b", vocab_size=128256, hidden_size=3072,
        intermediate_size=8192, num_layers=28, num_heads=24,
        num_kv_heads=8, head_dim=128, rope_theta=500000.0,
        max_position_embeddings=131072, tie_word_embeddings=True,
        rope_scaling=("llama3", 32.0, 1.0, 4.0, 8192),
    ),
    "llama-3.1-70b": ModelConfig(
        name="llama-3.1-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64,
        num_kv_heads=8, rope_theta=500000.0,
        max_position_embeddings=131072,
        rope_scaling=("llama3", 8.0, 1.0, 4.0, 8192),
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_position_embeddings=32768,
    ),
    # Mistral-7B v0.1: same shapes, 4096-token sliding-window attention
    "mistral-7b-v0.1": ModelConfig(
        name="mistral-7b-v0.1", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32,
        num_kv_heads=8, max_position_embeddings=32768,
        sliding_window=4096,
    ),
    # Tiny sliding-window model for CPU tests (window << context)
    "debug-sliding": ModelConfig(
        name="debug-sliding", vocab_size=512, hidden_size=128,
        intermediate_size=384, num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=512, sliding_window=64,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=28, num_heads=28,
        num_kv_heads=4, rope_theta=1000000.0,
        max_position_embeddings=32768, attention_bias=True,
    ),
    "gemma-2b": ModelConfig(
        name="gemma-2b", vocab_size=256000, hidden_size=2048,
        intermediate_size=16384, num_layers=18, num_heads=8,
        num_kv_heads=1, head_dim=256, max_position_embeddings=8192,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu_tanh",
        rms_norm_offset=True, embed_scale=True,
    ),
    # Tiny MoE for CPU tests: 4 experts, top-2, Mixtral semantics.
    "debug-moe": ModelConfig(
        name="debug-moe", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=512, num_experts=4, num_experts_per_tok=2,
    ),
    # Tiny GLM-4.7-Flash-style model for CPU tests: latent attention,
    # one leading dense layer, sigmoid router with bias and scale,
    # ungated shared expert. Widths are multiples of 128 where the
    # kernels (interpret mode) want them.
    "debug-mla": ModelConfig(
        name="debug-mla", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=3, num_heads=4, num_kv_heads=1,
        head_dim=48, max_position_embeddings=512, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=128,
        shared_expert_size=128, moe_naming="glm4_moe_lite",
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=1.8, shared_expert_gate=False,
        first_dense_layers=1, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    ),
    # Tiny GLM-5-style model for CPU tests (``glm_moe_dsa``): debug-mla
    # with the sparse-attention indexer (4 index heads of 32, the 16
    # best positions) and one of two chips' share of the experts: 4 of
    # the router's 8, from expert 4 on
    "debug-dsa": ModelConfig(
        name="debug-dsa", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=3, num_heads=4, num_kv_heads=1,
        head_dim=48, max_position_embeddings=512, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=128,
        shared_expert_size=128, moe_naming="glm4_moe_lite",
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, shared_expert_gate=False,
        first_dense_layers=1, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        index_n_heads=4, index_head_dim=32, index_topk=16,
        router_experts=8, expert_offset=4,
    ),
    # Tiny Qwen3-Next-style hybrid for CPU tests (``qwen3_next``): two
    # periods of three Gated DeltaNet layers and one gated attention
    # layer (a quarter of the head turned), 8 softmax-routed experts
    # top-2 with a gated shared expert, zero-centred norms. Heads of
    # 128 where the kernels (interpret mode) want whole lanes
    "debug-gdn": ModelConfig(
        name="debug-gdn", vocab_size=512, hidden_size=128,
        intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
        head_dim=128, max_position_embeddings=512, rms_norm_eps=1e-6,
        rms_norm_offset=True, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=128, shared_expert_size=128,
        moe_naming="qwen2", layer_pattern=("gdn", "gdn", "gdn", "attn"),
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=128,
        gdn_value_dim=128, gdn_conv=4, attn_gate=True, qk_norm=True,
        rotary_dim=32, exact_dequant_scale=True,
    ),
    # Tiny Brumby-style model for CPU tests (``brumby``): every mixer a
    # power retention layer (2 key-value heads of 16, so a head keeps
    # 136 monomials), no K/V pool at all
    "debug-brumby": ModelConfig(
        name="debug-brumby", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, rms_norm_eps=1e-6,
        layer_pattern=("ret",), qk_norm=True,
    ),
    # Tiny Phi-4-mini-flash-style decoder-hybrid-decoder for CPU tests
    # (``phi4flash``): 8 layers = (mamba, window 16) x 2, (mamba that
    # leaves the memory, full attention) x 1, (gated memory unit,
    # cross-attention) x 1; 8 / 4 heads of 8 in differential pairs,
    # a state of 4 a channel, every ratio of the published model
    "debug-yoco": ModelConfig(
        name="debug-yoco", vocab_size=512, hidden_size=64,
        intermediate_size=256, num_layers=8, num_heads=8, num_kv_heads=4,
        head_dim=8, max_position_embeddings=512, sliding_window=16,
        tie_word_embeddings=True, attention_bias=True,
        layer_plan=((("mamba", "swa"), 2), (("mamba_mem", "full"), 1),
                    (("gmu", "cross"), 1)),
        mamba_d_inner=128, mamba_d_state=4, mamba_d_conv=4,
        mamba_dt_rank=4, exact_dequant_scale=True,
    ),
    # Tiny Ouro-style looped model for CPU tests (``ouro``): 3 layers
    # run 4 times over 12 pool layers, sandwich norms, an exit gate
    "debug-ouro": ModelConfig(
        name="debug-ouro", vocab_size=512, hidden_size=128,
        intermediate_size=384, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=32, max_position_embeddings=512, rms_norm_eps=1e-6,
        rope_theta=1000000.0, sandwich_norms=True, loop_steps=4,
        exit_gate=True,
    ),
    # Tiny Nemotron-H-style model for CPU tests (``nemotron_h``): 12
    # blocks M E M * E M E M * E M E = (M E M * E) x 2, (M E) x 1, each
    # ONE sublayer; Mamba-2 of 8 heads of 32 in 2 groups, a state of 16;
    # 4 / 2 attention heads of 32, no rotary embedding; 8 sigmoid-routed
    # ungated relu^2 experts top-3 of width 48, STORED at 128, and a
    # shared one of 96
    "debug-nemotron": ModelConfig(
        name="debug-nemotron", vocab_size=512, hidden_size=128,
        intermediate_size=48, num_layers=12, num_heads=4, num_kv_heads=2,
        head_dim=32, max_position_embeddings=512, activation="relu2",
        expert_gate=False, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=48,
        shared_expert_size=96, router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, shared_expert_gate=False,
        moe_capacity_factor=8 / 3,
        layer_plan=((("mamba2", "moe", "mamba2", "attn", "moe"), 2),
                    (("mamba2", "moe"), 1)),
        mamba_d_inner=256, mamba_d_state=16, mamba_d_conv=4,
        mamba_heads=8, mamba_groups=2, exact_dequant_scale=True,
    ),
    # GLM-4.7-Flash (glm4_moe_lite, 30B-A3B): latent attention, one
    # leading dense layer of width 10240, 64 sigmoid-routed experts
    # top-4 of width 1536 with a selection bias and a routing scale of
    # 1.8, one ungated shared expert. 47 layers; the 48th, a
    # multi-token-prediction block, is not built
    "glm-4.7-flash": ModelConfig(
        name="glm-4.7-flash", vocab_size=154880, hidden_size=2048,
        intermediate_size=10240, num_layers=47, num_heads=20,
        num_kv_heads=1, head_dim=256, rope_theta=1000000.0,
        max_position_embeddings=202752, num_experts=64,
        num_experts_per_tok=4, moe_intermediate_size=1536,
        shared_expert_size=1536, moe_naming="glm4_moe_lite",
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=1.8, shared_expert_gate=False,
        first_dense_layers=1, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32,
        num_kv_heads=8, rope_theta=1000000.0,
        max_position_embeddings=32768, num_experts=8,
        num_experts_per_tok=2,
    ),
    # Qwen1.5-MoE-A2.7B: 60 experts top-4 (raw softmax weights) + an
    # always-on shared expert behind a sigmoid gate
    "qwen1.5-moe-a2.7b": ModelConfig(
        name="qwen1.5-moe-a2.7b", vocab_size=151936, hidden_size=2048,
        intermediate_size=5632, num_layers=24, num_heads=16,
        num_kv_heads=16, rope_theta=1000000.0,
        max_position_embeddings=8192, attention_bias=True,
        num_experts=60, num_experts_per_tok=4, norm_topk_prob=False,
        moe_intermediate_size=1408, shared_expert_size=5632,
        moe_naming="qwen2",
    ),
    # Gemma-2-2B: alternating 4096-window/global layers, softcaps,
    # sandwich norms, query_pre_attn_scalar = head_dim (256)
    "gemma-2-2b": ModelConfig(
        name="gemma-2-2b", vocab_size=256000, hidden_size=2304,
        intermediate_size=9216, num_layers=26, num_heads=8,
        num_kv_heads=4, head_dim=256, max_position_embeddings=8192,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu_tanh",
        rms_norm_offset=True, embed_scale=True,
        sliding_window=4096, alternating_sliding=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=256.0, sandwich_norms=True,
    ),
    "gemma-2-9b": ModelConfig(
        name="gemma-2-9b", vocab_size=256000, hidden_size=3584,
        intermediate_size=14336, num_layers=42, num_heads=16,
        num_kv_heads=8, head_dim=256, max_position_embeddings=8192,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu_tanh",
        rms_norm_offset=True, embed_scale=True,
        sliding_window=4096, alternating_sliding=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=256.0, sandwich_norms=True,
    ),
    # Tiny Gemma-2-style model for CPU tests (all deviations on)
    "debug-gemma2": ModelConfig(
        name="debug-gemma2", vocab_size=512, hidden_size=128,
        intermediate_size=384, num_layers=2, num_heads=4,
        num_kv_heads=2, max_position_embeddings=512,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu_tanh",
        rms_norm_offset=True, embed_scale=True,
        sliding_window=64, alternating_sliding=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=32.0, sandwich_norms=True,
    ),
    "gemma-7b": ModelConfig(
        name="gemma-7b", vocab_size=256000, hidden_size=3072,
        intermediate_size=24576, num_layers=28, num_heads=16,
        num_kv_heads=16, head_dim=256, max_position_embeddings=8192,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu_tanh",
        rms_norm_offset=True, embed_scale=True,
    ),
}

# Qwen2.5-7B shares Qwen2-7B's architecture shapes exactly
PRESETS["qwen2.5-7b"] = dataclasses.replace(PRESETS["qwen2-7b"],
                                            name="qwen2.5-7b")


# HF hub ids commonly passed as --model (e.g. from helm modelSpec
# entries) resolved to the preset with the same geometry; weights still
# come from --checkpoint (or are random-initialized).
HF_ALIASES: Dict[str, str] = {
    "meta-llama/Meta-Llama-3-8B": "llama-3-8b",
    "meta-llama/Meta-Llama-3-8B-Instruct": "llama-3-8b",
    "meta-llama/Llama-3.1-8B": "llama-3.1-8b",
    "meta-llama/Llama-3.1-8B-Instruct": "llama-3.1-8b",
    "meta-llama/Meta-Llama-3-70B": "llama-3-70b",
    "meta-llama/Meta-Llama-3-70B-Instruct": "llama-3-70b",
    "meta-llama/Llama-3.1-70B-Instruct": "llama-3.1-70b",
    "mistralai/Mistral-7B-v0.1": "mistral-7b-v0.1",
    "mistralai/Mistral-7B-Instruct-v0.2": "mistral-7b",
    "mistralai/Mistral-7B-Instruct-v0.3": "mistral-7b",
    "TinyLlama/TinyLlama-1.1B-Chat-v1.0": "tinyllama-1.1b",
    "Qwen/Qwen2-7B": "qwen2-7b",
    "Qwen/Qwen2-7B-Instruct": "qwen2-7b",
    "Qwen/Qwen2.5-7B": "qwen2.5-7b",
    "Qwen/Qwen2.5-7B-Instruct": "qwen2.5-7b",
    "mistralai/Mixtral-8x7B-v0.1": "mixtral-8x7b",
    "mistralai/Mixtral-8x7B-Instruct-v0.1": "mixtral-8x7b",
    "Qwen/Qwen1.5-MoE-A2.7B": "qwen1.5-moe-a2.7b",
    "Qwen/Qwen1.5-MoE-A2.7B-Chat": "qwen1.5-moe-a2.7b",
    "zai-org/GLM-4.7-Flash": "glm-4.7-flash",
    "google/gemma-2b": "gemma-2b",
    "google/gemma-2b-it": "gemma-2b",
    "google/gemma-7b": "gemma-7b",
    "google/gemma-7b-it": "gemma-7b",
    "meta-llama/Llama-3.2-1B": "llama-3.2-1b",
    "meta-llama/Llama-3.2-1B-Instruct": "llama-3.2-1b",
    "meta-llama/Llama-3.2-3B": "llama-3.2-3b",
    "meta-llama/Llama-3.2-3B-Instruct": "llama-3.2-3b",
    "google/gemma-2-2b": "gemma-2-2b",
    "google/gemma-2-2b-it": "gemma-2-2b",
    "google/gemma-2-9b": "gemma-2-9b",
    "google/gemma-2-9b-it": "gemma-2-9b",
}


def get_config(name: str) -> ModelConfig:
    if name in PRESETS:
        return PRESETS[name]
    if name in HF_ALIASES:
        cfg = PRESETS[HF_ALIASES[name]]
        return dataclasses.replace(cfg, name=name)
    if os.path.exists(name):
        return ModelConfig.from_json(name)
    raise KeyError(
        f"unknown model {name!r}; presets: {sorted(PRESETS)}, known HF ids: "
        f"{sorted(HF_ALIASES)}, or a path to an HF checkpoint directory"
    )
