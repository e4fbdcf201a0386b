"""Plain reference: the forward pass of GLM-4.7-Flash (``glm4_moe_lite``)
in straightforward ``jax.numpy`` float32.

No kernel, no cache, no batching tricks, nothing imported from the
program's ``ops/`` or ``models/``: the equations are written out here
from the published description (the model's ``config.json`` and HF's
``modeling_glm4_moe_lite.py`` / ``modeling_deepseek_v3.py``, whose
attention and router it shares):

- RMSNorm in float32 (eps ``rms_norm_eps``), SiLU, no biases;
- latent attention (MLA) in its EXPANDED form, never the absorbed one
  (the program decodes absorbed: the two forms are what the comparison
  is for). On ``x = RMSNorm(residual)``:
  ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> per head
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva`` (one per token,
  shared by the heads); ``c = RMSNorm(c_kv)``; ``[k_nope | v] = c W_kvb``
  per head; ``q_rope`` and ``k_rope`` rotated at the token's position;
  ``s = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``,
  causal softmax in float32, ``o = softmax(s) v``,
  ``out = concat_heads(o) W_o``;
- the first ``first_k_dense_replace`` layers: ``down(silu(gate x) * up x)``;
- the other layers: ``sc = sigmoid(x W_r)`` over the routed experts in
  float32; the top ``num_experts_per_tok`` of ``sc + b`` are chosen
  (``b`` = ``e_score_correction_bias``; ``n_group`` = ``topk_group`` =
  1, so no group step); their weights are ``sc`` WITHOUT ``b``, divided
  by their sum + 1e-20 (``norm_topk_prob``) and multiplied by
  ``routed_scaling_factor``; EVERY expert is evaluated for every token
  and weighted (zero where it was not chosen: exact, nothing dropped);
  plus the shared expert, which has no gate;
- final RMSNorm, untied output head.

Departures from the publication, each forced by what is compared:
the multi-token-prediction block (``num_nextn_predict_layers``) is
absent, as HF's own model class drops those weights on load; the rotary
embedding turns the rope part in the half-split ("rotate half") layout,
where the checkpoint's columns are interleaved (a permutation of
columns: a loader's business, and the weights here are random); the
weights are the served engine's own leaves (int8 with per-channel
scales, dequantised here to float32: the comparison is of the
arithmetic, not of the quantisation); matmuls run at
``jax.default_matmul_precision("highest")`` because a TPU otherwise
multiplies float32 in bfloat16 passes; prompts are computed one at a
time, so that the reference fits beside the engine.

Two keys that no published file holds turn the reference into a
CONTROL, for tools/mla_chip_check.py (what does the comparison read
when ...): ``round_to`` (a dtype's name) rounds the residual stream
and every block's input to that dtype, which is the reference with its
activations kept in a lower precision; and
``logprobs(..., chosen=)`` gives the expert layers another's top-k
choices, which tells a tie-break in a selection from arithmetic. The
benchmark's probe uses neither.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``_init_params_mla``): ``dense_layers`` and ``layers``
stacked on a leading axis each, ``{"w8", "scale"}`` leaves.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20


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
    return x if dt is None else x.astype(dt).astype(jnp.float32)


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


def _attention(hf, lp, x):
    """Expanded latent attention. x [T, H] -> [T, H]."""
    T = x.shape[0]
    nh, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    r, dn = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    dr, dv = hf["qk_rope_head_dim"], hf["v_head_dim"]
    c_q = _rms(x @ _deq(lp["q_a"]), lp["q_a_norm"], eps)
    q = (c_q @ _deq(lp["q_b"])).reshape(T, nh, dn + dr)
    ckv = x @ _deq(lp["kv_a"])
    c = _rms(ckv[:, :r], lp["kv_a_norm"], eps)
    kv = (c @ _deq(lp["kv_b"])).reshape(T, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = _rope(q[..., dn:], hf["rope_theta"])
    k_rope = _rope(ckv[:, None, r:], hf["rope_theta"])       # [T, 1, dr]
    s = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope[:, 0])
         ) / jnp.sqrt(float(dn + dr))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(T, nh * dv)
    return o @ _deq(lp["o"])


def _ffn(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _moe_mlp(hf, lp, x, chosen=None):
    """x [T, H]. Sigmoid scores, the bias in the selection alone,
    renormalised and scaled weights, every expert over every token, the
    shared expert added as it is. ``chosen`` [T, k] (a control) stands
    in for the selection; the weights are this function's own."""
    T = x.shape[0]
    E, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    sc = jax.nn.sigmoid(x @ lp["router"].astype(jnp.float32))
    _, top_i = jax.lax.top_k(
        sc + lp["router_bias"].astype(jnp.float32), k)
    if chosen is not None:
        top_i = chosen
    w = jnp.take_along_axis(sc, top_i, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], top_i].set(w)

    def one_expert(acc, e):
        def take(name):
            leaf = lp[name]
            return _deq({"w8": leaf["w8"][e], "scale": leaf["scale"][e]}
                        if isinstance(leaf, dict) else leaf[e])
        y = _ffn(x, take("gate"), take("up"), take("down"))
        return acc + y * weight[:, e][:, None], None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
    if "s_gate" in lp:
        y = y + _ffn(x, _deq(lp["s_gate"]), _deq(lp["s_up"]),
                     _deq(lp["s_down"]))
    return y


def _layer(hf, group, i, x, dense: bool, chosen=None):
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        group)
    eps = hf["rms_norm_eps"]
    h = _at(hf, _rms(x, lp["attn_norm"], eps))
    x = _at(hf, x + _attention(hf, lp, h))
    h = _at(hf, _rms(x, lp["mlp_norm"], eps))
    if dense:
        return _at(hf, x + _ffn(h, _deq(lp["gate"]), _deq(lp["up"]),
                                _deq(lp["down"])))
    return _at(hf, x + _moe_mlp(hf, lp, h, chosen))


@functools.lru_cache(maxsize=None)
def _layer_program(hf_items):
    """One jitted layer for a configuration's numbers (one program a
    kind of layer and prompt length, shared by every prompt)."""
    hf = dict(hf_items)
    return jax.jit(lambda group, i, x, dense, chosen=None: _layer(
        hf, group, i, x, dense, chosen), static_argnums=3)


def hidden_states(params, hf: Dict, tokens, chosen=None) -> jnp.ndarray:
    """The final-normed hidden states [T, H] of one prompt (token ids
    [T]); call under ``jax.default_matmul_precision("highest")``.
    ``chosen`` [expert layers, T, k]: a control's selections."""
    layer = _layer_program(tuple(sorted(
        (k, v) for k, v in hf.items()
        if isinstance(v, (int, float, bool, str)) or v is None)))
    dense_n = hf.get("first_k_dense_replace", 0)
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    for i in range(hf["num_hidden_layers"]):
        dense = i < dense_n
        x = layer(params["dense_layers" if dense else "layers"],
                  jnp.int32(i if dense else i - dense_n), x, dense,
                  None if dense or chosen is None else chosen[i - dense_n])
    return _rms(x, params["final_norm"], hf["rms_norm_eps"])


def logprobs(params, hf: Dict, tokens, chosen=None) -> jnp.ndarray:
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V] (the decode-step comparison reads them all; padded
    to a multiple of 128 like the probe's, so few programs serve all
    lengths)."""
    T = len(tokens)
    Tp = -(-T // 128) * 128
    padded = jnp.zeros((Tp,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))
    if chosen is not None:
        chosen = jnp.pad(jnp.asarray(chosen, jnp.int32),
                         ((0, 0), (0, Tp - T), (0, 0)))
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, hf, padded, chosen)[:T]
        return jax.nn.log_softmax(x @ _deq(params["lm_head"]), axis=-1)


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20. A prompt at a
    time, right-padded to a multiple of 128 (causal attention: what
    follows a position cannot reach it), so a few programs serve them
    all and the activations of one prompt are all that is held."""

    @jax.jit
    def head(lm_head, x, want):
        lps = jax.nn.log_softmax(x @ _deq(lm_head), axis=-1)
        top_lp, top_id = jax.lax.top_k(lps, TOP)
        return lps[want], top_id, top_lp

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, want in zip(prompts, ids):
            T = -(-len(prompt) // 128) * 128
            x = hidden_states(params, hf, jnp.asarray(
                prompt + [0] * (T - len(prompt)), jnp.int32))
            at, top_id, top_lp = jax.device_get(head(
                params["lm_head"], x[len(prompt) - 1],
                jnp.asarray(want, jnp.int32)))
            out.append({"prompt_tokens": len(prompt),
                        "logprobs": [float(v) for v in at],
                        "top_ids": [int(v) for v in top_id],
                        "top_logprobs": [float(v) for v in top_lp]})
    return out
