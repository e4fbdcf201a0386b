"""Plain reference: the forward pass of Nemotron-H (``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B) in straightforward ``jax.numpy`` float32.

No kernel, no cache, no chunked (state-space-duality) form, no batching,
nothing imported from the program's ``ops/`` or ``models/``: all blocks
on every position of one prompt, the Mamba-2 recurrence a token at a
time. Written from the published descriptions (Nemotron-H, arXiv
2504.03624; Mamba-2, Dao and Gu, arXiv 2405.21060; DeepSeek-V3's
``noaux_tc`` router) and the published ``config.json`` keys; ``x`` is
the residual stream:

- the blocks are ``hybrid_override_pattern``'s letters in order, and
  EACH BLOCK IS ONE SUBLAYER: ``x += f(RMSNorm(x))``, ``f`` a Mamba-2
  mixer (``M``), the expert layer (``E``) or attention (``*``); a final
  RMSNorm; an untied head;
- **M**: ``z, xBC, dt = split(in_proj(u))`` (``d_inner``, ``d_inner + 2
  n_groups ssm_state_size``, ``mamba_num_heads`` columns); ``xBC =
  silu(conv1d(xBC) + b)`` depthwise, causal, ``conv_kernel`` taps; ``x``
  as heads of ``mamba_head_dim``, ``B, C`` as ``n_groups`` groups of
  ``ssm_state_size`` (head j reads group j // (heads / groups)); ``dt =
  softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)`` ONE scalar a
  head; with ``h`` ``[heads, head_dim, state]`` zero before the first
  token, for each token IN TURN (a ``lax.scan`` over tokens): ``h =
  exp(dt A) h + dt x (x) B``; ``y = h C + D x``; then ``y = y silu(z)``,
  RMSNorm over each of ``n_groups`` groups of ``d_inner / n_groups``
  channels with one weight a channel, and ``out_proj``;
- **E**: router logits in float32 over ALL the router's experts,
  ``sigmoid``; ``e_score_correction_bias`` added for the SELECTION
  alone; top ``num_experts_per_tok``; the chosen scores renormalised
  (``norm_topk_prob``, 1e-20 in the denominator) and times
  ``routed_scaling_factor``; an expert is ``down(relu(up(x))^2)``, no
  gate; one shared expert of the same form added with no gate in front;
- ``*``: grouped-query attention, no bias, softmax scale ``head_dim **
  -0.5``, NO rotary embedding (the Mamba blocks carry position;
  ``rope_theta`` stands in the published file unused).

The chip's share (``deployment``): the parameters hold
``n_routed_experts`` of the router's ``deployment.router_experts``
experts, those from ``chip_index x n_routed_experts`` on; the router
keeps its width, and an assignment to an expert held elsewhere
contributes nothing (what that chip would add is left out, here as in
the program). ``vocab_size`` is the slice the head holds.

Departures from the publication: the served engine's int8 leaves
dequantised (int8 x per-channel scale, so the comparison is of the
arithmetic, not of the quantisation); ``highest`` matmul precision; one
prompt at a time; attention a key-value head at a time in blocks of
queries, the experts an expert at a time, so that the reference fits
beside the engine. Expert stacks STORED wider than
``moe_intermediate_size`` (zero columns of ``up``, zero rows of
``down``) are read as they are: ``relu(0)^2 = 0``.

Controls (keys no published file holds, for chipbench/probe_seeds.py and
tools/mamba2_chip_check.py): ``round_to`` (a dtype's name) rounds the
residual stream and every block's input to that dtype; ``gate_control``
``"off"`` (the gated group norm without its gate: ``y`` in place of ``y
silu(z)``), ``skip_control`` ``"off"`` (no ``D x``), ``state_control``
``"bf16"`` (h rounded to bfloat16 after every token);
``routed_scaling_factor`` and ``num_experts_per_tok`` are read from the
dict handed in. The benchmark's probe uses none.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``_init_params_sublayers``): ``layers`` (every block's
norm), ``mamba2_layers``, ``gqa_layers`` and ``moe_layers`` stacked on
a leading axis each, in the model's order; ``{"w8", "scale"}`` leaves.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20
_ATTEND_BLOCK = 1024    # queries a key-value head's group attends at once
KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


def _deq(leaf) -> jnp.ndarray:
    """A weight leaf [..., in, out] in float32 (int8 x per-output-channel
    scale, or the plain array)."""
    if isinstance(leaf, dict):
        return (leaf["w8"].astype(jnp.float32)
                * leaf["scale"].astype(jnp.float32)[..., None, :])
    return leaf.astype(jnp.float32)


def _round(x, dtype):
    """float32 x rounded to ``dtype``'s exponent and mantissa bits."""
    info = jnp.finfo(dtype)
    return jnp.clip(jax.lax.reduce_precision(x, info.nexp, info.nmant),
                    float(info.min), float(info.max))


def _at(hf, x):
    """x as the precision of the control holds it; the reference
    itself (no ``round_to``) keeps float32."""
    dt = hf.get("round_to")
    return x if dt is None else _round(x, dt)


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def kinds(hf) -> List[str]:
    """The blocks' kinds in order: "mamba2", "moe", "attn"."""
    return [KINDS[c] for c in hf["hybrid_override_pattern"]]


def mamba2(hf, lp, u):
    """A Mamba-2 mixer. u [T, H] -> [T, H]."""
    f32 = jnp.float32
    T = u.shape[0]
    nh, hd = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N = hf["n_groups"], hf["ssm_state_size"]
    di = nh * hd
    zxd = u @ _deq(lp["in_proj"])
    z, xBC, dt = (zxd[:, :di], zxd[:, di:2 * di + 2 * G * N],
                  zxd[:, 2 * di + 2 * G * N:])
    w = lp["conv"].astype(f32)                       # [taps, channels]
    taps = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xBC.shape[1]), f32), xBC], axis=0)
    xBC = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(taps))
                      + lp["conv_bias"].astype(f32))
    x = xBC[:, :di].reshape(T, nh, hd)
    # head j reads group j // (heads / groups)
    Bm = jnp.repeat(xBC[:, di:di + G * N].reshape(T, G, N), nh // G, axis=1)
    Cm = jnp.repeat(xBC[:, di + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))        # [T, nh]
    A = -jnp.exp(lp["A_log"].astype(f32))                       # [nh]
    narrow = hf.get("state_control") == "bf16"

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs         # [nh, hd], [nh], [nh, N] x 2
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if narrow:
            h = _round(h, jnp.bfloat16)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((nh, hd, N), f32), (x, dt, Bm, Cm))
    if hf.get("skip_control") != "off":
        y = y + lp["D"].astype(f32)[:, None] * x
    y = y.reshape(T, di)
    if hf.get("gate_control") != "off":
        y = y * jax.nn.silu(z)
    y = y.reshape(T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + hf.get("layer_norm_epsilon", 1e-5))
    return (y.reshape(T, di) * lp["gate_norm"].astype(f32)) \
        @ _deq(lp["out_proj"])


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(hf, lp, u):
    """(weights [T, k] float32, expert ids [T, k] among ALL the
    router's experts)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(u @ lp["router"].astype(f32))
    _, top_i = jax.lax.top_k(scores + lp["router_bias"].astype(f32),
                             hf["num_experts_per_tok"])
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    if hf.get("norm_topk_prob", True):
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return top_p * hf.get("routed_scaling_factor", 1.0), top_i


def routed(hf, lp, u, offset: int = 0):
    """What the experts the parameters hold (those from ``offset`` on
    among the router's) give for the tokens routed to them: [T, H]."""
    top_p, top_i = route(hf, lp, u)
    up, down = lp["up"], lp["down"]
    held = (up["w8"] if isinstance(up, dict) else up).shape[0]

    def one(e, acc):
        pick = lambda leaf: jax.tree.map(       # noqa: E731
            lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False),
            leaf)
        w = jnp.sum(jnp.where(top_i == e + offset, top_p, 0.0), axis=-1)
        return acc + w[:, None] * (_relu2(u @ _deq(pick(up)))
                                   @ _deq(pick(down)))

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))


def shared(lp, u):
    return _relu2(u @ _deq(lp["s_up"])) @ _deq(lp["s_down"])


def expert_offset(hf) -> int:
    """Where the held experts start among the router's."""
    d = hf.get("deployment") or {}
    return d.get("chip_index", 0) * hf["n_routed_experts"] \
        if d.get("chips_per_layer", 1) > 1 else 0


def moe(hf, lp, u):
    """The expert layer as this chip computes it: its held experts'
    part and the shared expert."""
    return routed(hf, lp, u, expert_offset(hf)) + shared(lp, u)


def attention(hf, lp, u):
    """Grouped-query attention with no positional term. u [T, H]."""
    T = u.shape[0]
    nh, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    g = nh // nkv
    q = (u @ _deq(lp["q"])).reshape(T, nkv, g, hd)
    k = (u @ _deq(lp["k"])).reshape(T, nkv, hd)
    v = (u @ _deq(lp["v"])).reshape(T, nkv, hd)
    keys = jnp.arange(T)

    def one_head(c):
        qc = jax.lax.dynamic_index_in_dim(q, c, 1, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(k, c, 1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v, c, 1, keepdims=False)
        outs = []
        for t0 in range(0, T, _ATTEND_BLOCK):
            seen = keys[None, None, :] <= keys[t0:t0 + _ATTEND_BLOCK,
                                               None, None]
            s = jnp.einsum("tgd,sd->tgs", qc[t0:t0 + _ATTEND_BLOCK],
                           kc) * hd ** -0.5
            outs.append(jnp.einsum(
                "tgs,sd->tgd",
                jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vc))
        return jnp.concatenate(outs, axis=0)                # [T, g, hd]

    o = jnp.moveaxis(jax.lax.map(one_head, jnp.arange(nkv)), 0, 1)
    return o.reshape(T, nh * hd) @ _deq(lp["o"])


GROUPS = {"mamba2": "mamba2_layers", "moe": "moe_layers",
          "attn": "gqa_layers"}
MIXERS = {"mamba2": mamba2, "moe": moe, "attn": attention}


def _row(params, group, n):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, n, 0, keepdims=False),
        params[group])


def _block(hf, params, i, among, kind: str, x):
    """Block ``i`` (traced), the ``among``-th of kind ``kind``
    (static) -> x'."""
    u = _at(hf, _rms(x, _row(params, "layers", i)["norm"],
                     hf.get("layer_norm_epsilon", 1e-5)))
    return _at(hf, x + MIXERS[kind](hf, _row(params, GROUPS[kind], among), u))


def _numbers(hf):
    """The configuration's numbers as a hashable key."""
    deployment = tuple(sorted((hf.get("deployment") or {}).items()))
    return tuple(sorted(
        (k, v) for k, v in hf.items()
        if isinstance(v, (int, float, bool, str)) or v is None)) \
        + (("deployment", deployment),)


@functools.lru_cache(maxsize=None)
def _block_program(numbers):
    hf = dict(numbers)
    hf["deployment"] = dict(hf["deployment"])
    return jax.jit(lambda params, i, among, kind, x: _block(
        hf, params, i, among, kind, x), static_argnums=3)


def hidden_states(params, hf: Dict, tokens) -> jnp.ndarray:
    """The final-normed hidden states [T, H] of one prompt (token ids
    [T]); call under ``jax.default_matmul_precision("highest")``."""
    block = _block_program(_numbers(hf))
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    x = _at(hf, x)
    seen = {k: 0 for k in GROUPS}
    for i, kind in enumerate(kinds(hf)):
        x = block(params, jnp.int32(i), jnp.int32(seen[kind]), kind, x)
        seen[kind] += 1
    return _rms(x, params["final_norm"], hf.get("layer_norm_epsilon", 1e-5))


def _padded(tokens) -> jnp.ndarray:
    T = len(tokens)
    return jnp.zeros((-(-T // 128) * 128,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))


def logprobs(params, hf: Dict, tokens, at=None) -> jnp.ndarray:
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V], or after the positions ``at`` alone (right-padded to
    a multiple of 128: causal blocks, so what follows a position cannot
    reach it)."""
    rows = jnp.arange(len(tokens)) if at is None \
        else jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, hf, _padded(tokens))
        return jax.nn.log_softmax(x[rows] @ _deq(params["lm_head"]),
                                  axis=-1)


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20. A prompt at a
    time, right-padded to a multiple of 128."""

    @jax.jit
    def head(lm_head, x, want):
        lps = jax.nn.log_softmax(x @ _deq(lm_head), axis=-1)
        top_lp, top_id = jax.lax.top_k(lps, TOP)
        return lps[want], top_id, top_lp

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, want in zip(prompts, ids):
            x = hidden_states(params, hf, _padded(prompt))
            at, top_id, top_lp = jax.device_get(head(
                params["lm_head"], x[len(prompt) - 1],
                jnp.asarray(want, jnp.int32)))
            out.append({"prompt_tokens": len(prompt),
                        "logprobs": [float(v) for v in at],
                        "top_ids": [int(v) for v in top_id],
                        "top_logprobs": [float(v) for v in top_lp]})
    return out
