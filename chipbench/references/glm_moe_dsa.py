"""Plain reference: the forward pass of GLM-5 (``glm_moe_dsa``) in
straightforward ``jax.numpy`` float32, for ONE CHIP'S SHARE of a
deployment that divides each expert layer over several chips.

No kernel, no cache, nothing imported from the program's ``ops/`` or
``models/``. The layer is GLM-4.7-Flash's (``glm4_moe_lite.py`` beside
this file, whose small helpers are used: dequantisation, RMSNorm, the
rotary turn, the feed-forward) with two differences, written out here
from the published description (the model's ``config.json``; the
indexer is DeepSeek-V3.2's, which ``glm_moe_dsa`` follows):

- **the learned sparse attention.** With ``h_t`` the layer's normed
  input and ``cq_t = RMSNorm(W_qa h_t)``: index queries ``qI_t,j =
  W_qI,j cq_t`` (``index_n_heads`` of ``index_head_dim``, the leading
  ``qk_rope_head_dim`` columns turned at position t), ONE index key a
  token ``kI_s = LayerNorm(W_kI h_s)`` (turned alike), head weights
  ``w_t = W_w h_t``;
  ``I[t, s] = sum_j w_t,j * heads^-0.5 * width^-0.5 * relu(qI_t,j . kI_s)``;
  ``S_t`` = the ``index_topk`` positions s <= t of largest ``I[t, s]``
  (ties to the lower position; all of them while t < index_topk); the
  softmax of the latent attention runs over ``S_t`` alone. Attention
  is EXPANDED (keys and values per head from the latent), a head and
  a block of queries at a time, so that 16k tokens fit;
- **the chip's share.** The router scores all of its experts
  (``deployment.router_experts``) and picks ``num_experts_per_tok`` of
  them as published; the experts held here are ``n_routed_experts``
  from ``deployment.chip_index`` x that on, and only they add to the
  result: what the absent experts would add is left out, as in the
  program, and that partial result goes on to the next layer. The
  vocabulary is the slice the file states.

Departures from the publication: those of ``glm4_moe_lite.py`` (no
multi-token-prediction layer, half-split rotary layout, the served
engine's int8 leaves dequantised, ``highest`` matmul precision), and of
the published indexer code the Hadamard turn of ``qI`` and ``kI`` (an
orthogonal transform of both: the scores are the same) and their
float8 storage (a storage format) are left out.

Controls (keys no published file holds, for tools/dsa_chip_check.py):
``round_to`` as in ``glm4_moe_lite.py``; ``select_control: "first"``
replaces ``S_t`` by the first ``index_topk`` positions;
``num_experts_per_tok`` and ``routed_scaling_factor`` are read from the
dict handed in, so a control changes them there. ``watch`` (positions)
makes ``logprobs`` also return, per layer, those queries' ``S_t``.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

from chipbench.references.glm4_moe_lite import (TOP, _at, _deq, _ffn, _rms,
                                                _rope)

_QUERY_BLOCK = 128      # queries whose index scores are held at once
_ATTEND_BLOCK = 1024    # queries a head attends at once


def _block(T: int, most: int) -> int:
    """Rows a blocked loop takes at once: the largest power-of-two
    multiple of 128 up to ``most`` that divides T, else all T."""
    return next((b for b in (1024, 512, 256, 128)
                 if b <= most and T % b == 0), T)


def _layer_norm(x, w, b, eps=1e-6):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32) + b.astype(jnp.float32)


def _rope_leading(x, width, theta):
    """x [T, heads, D] with its leading ``width`` columns turned."""
    return jnp.concatenate([_rope(x[..., :width], theta), x[..., width:]],
                           axis=-1)


def _selection(hf, lp, x, c_q):
    """[T, T] bool: which positions s each query t attends."""
    T = x.shape[0]
    heads, width = hf["index_n_heads"], hf["index_head_dim"]
    k = min(hf["index_topk"], T)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    if hf.get("select_control") == "first":
        return causal & (pos[None, :] < k)
    dr, theta = hf["qk_rope_head_dim"], hf["rope_theta"]
    q = _rope_leading((c_q @ _deq(lp["idx_q"])).reshape(T, heads, width),
                      dr, theta)
    key = _layer_norm(x @ _deq(lp["idx_k"]), lp["idx_k_norm"],
                      lp["idx_k_norm_bias"])
    key = _rope_leading(key[:, None, :], dr, theta)[:, 0]
    w = (x @ lp["idx_w"].astype(jnp.float32)) * (heads ** -0.5
                                                 * width ** -0.5)
    block = _block(T, _QUERY_BLOCK)

    def rows(t0):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        wb = jax.lax.dynamic_slice_in_dim(w, t0, block)
        live = (t0 + jnp.arange(block))[:, None] >= pos[None, :]
        score = jnp.sum(jax.nn.relu(jnp.einsum("qjd,sd->qjs", qb, key))
                        * wb[..., None], axis=1)
        # lax.top_k keeps the lower index of equal values
        _, best = jax.lax.top_k(jnp.where(live, score, -jnp.inf), k)
        return jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], best].set(True) & live

    return jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, T)


def _attention(hf, lp, x):
    """Expanded latent attention over the selected positions.
    x [T, H] -> ([T, H], the selection [T, T])."""
    T = x.shape[0]
    nh, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    r, dn = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    dr, dv = hf["qk_rope_head_dim"], hf["v_head_dim"]
    c_q = _rms(x @ _deq(lp["q_a"]), lp["q_a_norm"], eps)
    q = (c_q @ _deq(lp["q_b"])).reshape(T, nh, dn + dr)
    ckv = x @ _deq(lp["kv_a"])
    c = _rms(ckv[:, :r], lp["kv_a_norm"], eps)
    q_rope = _rope(q[..., dn:], hf["rope_theta"])
    k_rope = _rope(ckv[:, None, r:], hf["rope_theta"])[:, 0]   # [T, dr]
    chosen = _selection(hf, lp, x, c_q)
    w_kvb = _deq(lp["kv_b"]).reshape(r, nh, dn + dv)
    block = _block(T, _ATTEND_BLOCK)

    def head(h):
        kv = c @ w_kvb[:, h]                                   # [T, dn+dv]
        k_nope, v = kv[:, :dn], kv[:, dn:]

        def rows(t0):
            def cut(a):
                return jax.lax.dynamic_slice_in_dim(a, t0, block)
            s = (cut(q[:, h, :dn]) @ k_nope.T + cut(q_rope[:, h])
                 @ k_rope.T) / jnp.sqrt(float(dn + dr))
            return jax.nn.softmax(jnp.where(cut(chosen), s, -jnp.inf),
                                  axis=-1) @ v

        return jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, dv)

    o = jax.lax.map(head, jnp.arange(nh))                      # [nh,T,dv]
    return o.transpose(1, 0, 2).reshape(T, nh * dv) @ _deq(lp["o"]), chosen


def _moe_mlp(hf, lp, x):
    """x [T, H]: the router over ALL its experts, the held experts'
    part of the result, the shared expert as it is."""
    T = x.shape[0]
    k = hf["num_experts_per_tok"]
    held = hf["n_routed_experts"]
    first = (hf.get("deployment") or {}).get("chip_index", 0) * held
    sc = jax.nn.sigmoid(x @ lp["router"].astype(jnp.float32))
    _, top_i = jax.lax.top_k(
        sc + lp["router_bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(sc, top_i, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros(sc.shape, jnp.float32).at[
        jnp.arange(T)[:, None], top_i].set(w)

    def one_expert(acc, e):
        def take(name):
            leaf = lp[name]
            return _deq({"w8": leaf["w8"][e], "scale": leaf["scale"][e]}
                        if isinstance(leaf, dict) else leaf[e])
        y = _ffn(x, take("gate"), take("up"), take("down"))
        return acc + y * jax.lax.dynamic_index_in_dim(
            weight, first + e, 1), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(held))
    return y + _ffn(x, _deq(lp["s_gate"]), _deq(lp["s_up"]),
                    _deq(lp["s_down"]))


def _layer(hf, group, i, x, dense: bool, watch):
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        group)
    eps = hf["rms_norm_eps"]
    h = _at(hf, _rms(x, lp["attn_norm"], eps))
    attn, chosen = _attention(hf, lp, h)
    x = _at(hf, x + attn)
    h = _at(hf, _rms(x, lp["mlp_norm"], eps))
    y = (_ffn(h, _deq(lp["gate"]), _deq(lp["up"]), _deq(lp["down"]))
         if dense else _moe_mlp(hf, lp, h))
    return _at(hf, x + y), chosen[watch]


@functools.lru_cache(maxsize=None)
def _layer_program(hf_items):
    hf = {k: dict(v) if isinstance(v, tuple) else v for k, v in hf_items}
    return jax.jit(lambda group, i, x, dense, watch: _layer(
        hf, group, i, x, dense, watch), static_argnums=3)


def _numbers(hf: Dict):
    """The configuration's numbers as a hashable key (and the
    deployment's, one level down)."""
    def plain(v):
        return isinstance(v, (int, float, bool, str)) or v is None
    items = [(k, v) for k, v in hf.items() if plain(v)]
    dep = hf.get("deployment")
    if isinstance(dep, dict):
        items.append(("deployment", tuple(sorted(
            (k, v) for k, v in dep.items() if plain(v)))))
    if "rope_theta" not in hf:
        items.append(("rope_theta", float(
            (hf.get("rope_parameters") or {}).get("rope_theta", 10000.0))))
    return tuple(sorted(items))


def hidden_states(params, hf: Dict, tokens, watch=None):
    """(final-normed hidden states [T, H] of one prompt (ids [T]), the
    selections of the ``watch`` positions [layers, len(watch), T]);
    call under ``jax.default_matmul_precision("highest")``."""
    layer = _layer_program(_numbers(hf))
    watch = jnp.asarray([0] if watch is None else watch, jnp.int32)
    dense_n = hf.get("first_k_dense_replace", 0)
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    chosen = []
    for i in range(hf["num_hidden_layers"]):
        dense = i < dense_n
        x, rows = layer(params["dense_layers" if dense else "layers"],
                        jnp.int32(i if dense else i - dense_n), x, dense,
                        watch)
        chosen.append(rows)
    return (_rms(x, params["final_norm"], hf["rms_norm_eps"]),
            jnp.stack(chosen))


def _padded(tokens) -> jnp.ndarray:
    T = len(tokens)
    return jnp.zeros((-(-T // 128) * 128,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))


def logprobs(params, hf: Dict, tokens, watch=None):
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V]; with ``watch`` (positions) also those queries'
    selections per layer [layers, len(watch), T] bool."""
    T = len(tokens)
    with jax.default_matmul_precision("highest"):
        x, chosen = hidden_states(params, hf, _padded(tokens), watch)
        lps = jax.nn.log_softmax(x[:T] @ _deq(params["lm_head"]), axis=-1)
    return lps if watch is None else (lps, chosen[..., :T])


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
            x, _ = hidden_states(params, hf, _padded(prompt))
            at, top_id, top_lp = jax.device_get(head(
                params["lm_head"], x[len(prompt) - 1],
                jnp.asarray(want, jnp.int32)))
            out.append({"prompt_tokens": len(prompt),
                        "logprobs": [float(v) for v in at],
                        "top_ids": [int(v) for v in top_id],
                        "top_logprobs": [float(v) for v in top_lp]})
    return out
