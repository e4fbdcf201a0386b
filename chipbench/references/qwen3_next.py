"""Plain reference: the forward pass of Qwen3-Next (``qwen3_next``) in
straightforward ``jax.numpy`` float32, for ONE CHIP'S SHARE of a
deployment that divides each expert layer over several chips.

No kernel, no cache, no chunked form, nothing imported from the
program's ``ops/`` or ``models/``. Written from the published
description (``modeling_qwen3_next.py``); x is a layer's input, every
norm float32, "RMSNorm0" the zero-centred one, ``norm(x) * (1 + w)``:

- layer i: ``h = x + Mixer_i(RMSNorm0(x))``, ``y = h + MoE(RMSNorm0(h))``;
  ``Mixer_i`` is gated attention where ``(i + 1) %
  full_attention_interval == 0``, else Gated DeltaNet; a final
  RMSNorm0, an untied head;
- **Gated DeltaNet**: ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x
  W_ba``; ``[q | k | v]`` through a depthwise CAUSAL convolution of
  ``linear_conv_kernel_dim`` taps (no bias) and SiLU; per value head h
  (q and k of key head ``h // (value heads / key heads)``): ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, ``q =
  l2norm(q) / sqrt(Dk)``, ``k = l2norm(k)``; with ``S`` a ``[Dk, Dv]``
  matrix, zero before the first token, for each token IN TURN (a
  ``lax.scan`` over tokens: the sequential recurrence, never a chunked
  form, because the two forms are what the comparison is for):
  ``S = exp(g) S``; ``m = k^T S``; ``d = beta (v - m)``; ``S = S + k
  d^T``; ``o = q^T S``; then per head ``RMSNorm(o; w) * SiLU(z)`` (a
  plain weight), the heads side by side, ``W_out``;
- **gated attention**: ``[q | gate] = x W_q`` a head; RMSNorm0 on each
  head's q and k; the LEADING ``partial_rotary_factor`` of the head's
  columns turned ("rotate half"); causal ``softmax(q k^T / sqrt(hd))
  v``, grouped query heads; ``(o * sigmoid(gate)) W_o``;
- **the experts**: ``p = softmax(x W_r)`` over ALL the router's experts
  in float32, the top ``num_experts_per_tok``, their weights divided
  by their sum (``norm_topk_prob``); every expert evaluated and
  weighted, ``down(silu(gate x) * up x)``; plus ``sigmoid(x w_sg) *
  shared(x)``. **The chip's share**: the router scores
  ``deployment.router_experts`` and picks as published; the experts
  held here are ``num_experts`` from ``deployment.chip_index`` x that
  on, and only they add to the result; the shared expert is every
  chip's alike. The vocabulary is the slice the file states.

Departures from the publication: no multi-token-prediction block (HF's
class drops ``mtp.*`` on load); the half-split rotary layout; the
served engine's int8 leaves dequantised (int8 x per-channel scale, so
the comparison is of the arithmetic, not of the quantisation);
``highest`` matmul precision; one prompt at a time; attention in
blocks of queries, a head at a time, at long prompts, so that the
reference fits beside the engine.

Controls (keys no published file holds, for tools/gdn_chip_check.py):
``round_to`` (a dtype's name) rounds the residual stream and every
block's input to that dtype; ``gdn_control`` one of ``"no_decay"`` (g
= 0), ``"beta_one"``, ``"no_conv_carry"`` (the convolution forgets its
inputs at every ``conv_chunk`` boundary), ``"state_bf16"`` (S rounded
to bfloat16 after every token); ``attn_control`` one of ``"no_gate"``,
``"rotary_all"``; ``num_experts_per_tok`` is read from the dict handed
in. The benchmark's probe uses none.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``_init_params_hybrid``): ``layers`` (every layer's
norms, router and experts), ``gdn_layers`` and ``attn_layers`` stacked
on a leading axis each, ``{"w8", "scale"}`` leaves.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20
_ATTEND_BLOCK = 1024    # queries a head attends at once


def _deq(leaf) -> jnp.ndarray:
    """A weight leaf [..., in, out] in float32 (int8 x per-output-channel
    scale, or the plain array)."""
    if isinstance(leaf, dict):
        return (leaf["w8"].astype(jnp.float32)
                * leaf["scale"].astype(jnp.float32)[..., None, :])
    return leaf.astype(jnp.float32)


def _at(hf, x):
    """x as the precision of the control holds it; the reference
    itself (no ``round_to``) keeps float32."""
    dt = hf.get("round_to")
    return x if dt is None else _round(x, dt)


def _round(x, dtype):
    """float32 x rounded to ``dtype``'s exponent and mantissa bits (a
    float32 -> bfloat16 -> float32 convert pair is simplified away by
    the TPU's compiler; reduce_precision is not)."""
    info = jnp.finfo(dtype)
    return jnp.clip(jax.lax.reduce_precision(x, info.nexp, info.nmant),
                    float(info.min), float(info.max))


def _rms0(x, w, eps):
    """The zero-centred RMSNorm: norm(x) * (1 + w)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [T, heads, D] at positions 0..T-1, all of D turned."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, S, round_state: bool = False):
    """The gated delta rule, token by token (a ``lax.scan``). q, k
    [T, Hv, Dk], v [T, Hv, Dv], g, beta [T, Hv], S [Hv, Dk, Dv] the
    state before the first token -> (o [T, Hv, Dv], the state after
    the last)."""
    def token(S, xs):
        q, k, v, g, beta = xs
        S = S * jnp.exp(g)[:, None, None]
        m = jnp.einsum("hk,hkv->hv", k, S)
        d = beta[:, None] * (v - m)
        S = S + k[:, :, None] * d[:, None, :]
        if round_state:
            S = _round(S, jnp.bfloat16)
        return S, jnp.einsum("hk,hkv->hv", q, S)

    S, o = jax.lax.scan(token, S, (q, k, v, g, beta))
    return o, S


def _delta_net(hf, lp, x):
    """Gated DeltaNet, the sequential recurrence. x [T, H] -> [T, H]."""
    T = x.shape[0]
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    taps, ch = hf["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv
    control = hf.get("gdn_control")
    qkvz = x @ _deq(lp["qkvz"])
    mixed, z = qkvz[:, :ch], qkvz[:, ch:]
    ba = x @ lp["ba"].astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = (-jnp.exp(lp["A_log"].astype(jnp.float32))
         * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"].astype(jnp.float32)))
    if control == "no_decay":
        g = jnp.zeros_like(g)
    if control == "beta_one":
        beta = jnp.ones_like(beta)
    # y_t = sum_j w[j] x_{t - (taps - 1) + j}: the last tap on the token
    w = lp["conv"].astype(jnp.float32)                      # [taps, ch]
    padded = jnp.concatenate([jnp.zeros((taps - 1, ch)), mixed])
    conv = jnp.zeros_like(mixed)
    for j in range(taps):
        tap = padded[j:j + T]
        if control == "no_conv_carry":
            # a token sees no input from before its chunk's first token
            first = (jnp.arange(T) // hf["conv_chunk"]) * hf["conv_chunk"]
            source = jnp.arange(T) - (taps - 1) + j
            tap = jnp.where((source >= first)[:, None], tap, 0.0)
        conv = conv + tap * w[j]
    mixed = jax.nn.silu(conv)
    rep = hv // hk
    q = jnp.repeat(_l2norm(mixed[:, :hk * dk].reshape(T, hk, dk))
                   / jnp.sqrt(float(dk)), rep, axis=1)
    k = jnp.repeat(_l2norm(mixed[:, hk * dk:2 * hk * dk].reshape(
        T, hk, dk)), rep, axis=1)
    v = mixed[:, 2 * hk * dk:].reshape(T, hv, dv)

    o, _ = delta_rule(q, k, v, g, beta, jnp.zeros((hv, dk, dv)),
                      round_state=control == "state_bf16")
    o = _rms(o, lp["gdn_norm"], hf["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(T, hv, dv))
    return o.reshape(T, hv * dv) @ _deq(lp["out"])


def _attention(hf, lp, x):
    """Gated softmax attention, full causal. x [T, H] -> [T, H]."""
    T = x.shape[0]
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps = hf["head_dim"], hf["rms_norm_eps"]
    control = hf.get("attn_control")
    qg = (x @ _deq(lp["q"])).reshape(T, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ _deq(lp["k"])).reshape(T, nkv, hd)
    v = (x @ _deq(lp["v"])).reshape(T, nkv, hd)
    q, k = _rms0(q, lp["q_norm"], eps), _rms0(k, lp["k_norm"], eps)
    rd = (hd if control == "rotary_all"
          else int(hd * hf.get("partial_rotary_factor", 1.0)))
    theta = hf["rope_theta"]
    q = jnp.concatenate([_rope(q[..., :rd], theta), q[..., rd:]], -1)
    k = jnp.concatenate([_rope(k[..., :rd], theta), k[..., rd:]], -1)
    groups = nh // nkv
    block = next((b for b in (_ATTEND_BLOCK, 512, 256, 128)
                  if T % b == 0), T)
    pos = jnp.arange(T)

    def head(h):
        kh, vh = k[:, h // groups], v[:, h // groups]

        def rows(t0):
            qb = jax.lax.dynamic_slice_in_dim(q[:, h], t0, block)
            s = (qb @ kh.T) / jnp.sqrt(float(hd))
            live = (t0 + jnp.arange(block))[:, None] >= pos[None, :]
            return jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1) @ vh
        return jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, hd)

    o = jnp.moveaxis(jax.lax.map(head, jnp.arange(nh)), 0, 1)   # [T,nh,hd]
    if control != "no_gate":
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(T, nh * hd) @ _deq(lp["o"])


def _ffn(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _moe_mlp(hf, lp, x):
    """x [T, H]. Softmax over all the router's experts, the top k
    renormalised, every HELD expert over every token and weighted, the
    gated shared expert added."""
    T = x.shape[0]
    held, k = hf["num_experts"], hf["num_experts_per_tok"]
    dep = hf.get("deployment") or {}
    offset = dep.get("chip_index", 0) * held
    p = jax.nn.softmax(x @ lp["router"].astype(jnp.float32), axis=-1)
    w, top_i = jax.lax.top_k(p, k)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[jnp.arange(T)[:, None], top_i].set(w)

    def one_expert(acc, e):
        def take(name):
            leaf = lp[name]
            return _deq({"w8": leaf["w8"][e], "scale": leaf["scale"][e]}
                        if isinstance(leaf, dict) else leaf[e])
        y = _ffn(x, take("gate"), take("up"), take("down"))
        return acc + y * jax.lax.dynamic_index_in_dim(
            weight, offset + e, 1), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(held))
    shared = _ffn(x, _deq(lp["s_gate"]), _deq(lp["s_up"]),
                  _deq(lp["s_down"]))
    return y + jax.nn.sigmoid(
        x @ lp["s_gate_w"].astype(jnp.float32)) * shared


def _layer(hf, params, i, kind: str, x):
    """Layer ``i`` (traced) of kind ``kind`` (static); its mixer's
    parameters are row i // interval x (mixers of the kind a period) +
    its place among them."""
    interval = hf["full_attention_interval"]

    def row(group, n):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, n, 0,
                                                   keepdims=False),
            params[group])
    lp = row("layers", i)
    eps = hf["rms_norm_eps"]
    h = _at(hf, _rms0(x, lp["attn_norm"], eps))
    if kind == "attn":
        x = _at(hf, x + _attention(hf, row("attn_layers", i // interval),
                                   h))
    else:
        x = _at(hf, x + _delta_net(
            hf, row("gdn_layers",
                    (i // interval) * (interval - 1) + i % interval), h))
    h = _at(hf, _rms0(x, lp["mlp_norm"], eps))
    return _at(hf, x + _moe_mlp(hf, lp, h))


def _numbers(hf):
    """The configuration's numbers as a hashable key (and the
    deployment's, one level down)."""
    def plain(v):
        return isinstance(v, (int, float, bool, str)) or v is None
    items = [(k, v) for k, v in hf.items() if plain(v)]
    dep = hf.get("deployment")
    if isinstance(dep, dict):
        items.append(("deployment", tuple(sorted(
            (k, v) for k, v in dep.items() if plain(v)))))
    return tuple(sorted(items))


@functools.lru_cache(maxsize=None)
def _layer_program(numbers):
    hf = dict(numbers)
    if "deployment" in hf:
        hf["deployment"] = dict(hf["deployment"])
    return jax.jit(lambda params, i, kind, x: _layer(hf, params, i, kind,
                                                     x), static_argnums=2)


def hidden_states(params, hf: Dict, tokens) -> jnp.ndarray:
    """The final-normed hidden states [T, H] of one prompt (token ids
    [T]); call under ``jax.default_matmul_precision("highest")``."""
    layer = _layer_program(_numbers(hf))
    interval = hf["full_attention_interval"]
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    for i in range(hf["num_hidden_layers"]):
        x = layer(params, jnp.int32(i),
                  "attn" if (i + 1) % interval == 0 else "gdn", x)
    return _rms0(x, params["final_norm"], hf["rms_norm_eps"])


def _padded(tokens) -> jnp.ndarray:
    T = len(tokens)
    return jnp.zeros((-(-T // 128) * 128,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))


def logprobs(params, hf: Dict, tokens) -> jnp.ndarray:
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V] (right-padded to a multiple of 128: causal layers, so
    what follows a position cannot reach it)."""
    T = len(tokens)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, hf, _padded(tokens))
        return jax.nn.log_softmax(x[:T] @ _deq(params["lm_head"]), axis=-1)


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
