"""Plain reference: the forward pass of the Llama/Mistral decoder and
of the Qwen2-MoE decoder, in straightforward ``jax.numpy`` float32.

No kernel, no cache, no batching tricks, nothing imported from the
program's ``ops/`` or ``models/``: the equations are written out here
from the published descriptions (HF ``modeling_mistral.py`` and
``modeling_qwen2_moe.py``):

- RMSNorm in float32; rotary embedding in the half-split ("rotate
  half") layout with ``rope_theta``; grouped-query attention with a
  causal mask, scores scaled by 1/sqrt(head_dim), softmax in float32;
- dense block: ``down(silu(gate(x)) * up(x))``;
- Qwen2-MoE block: router softmax over all experts in float32, top-k,
  the raw probabilities kept when ``norm_topk_prob`` is false, EVERY
  expert evaluated for every token and weighted (zero where it was not
  chosen: exact, nothing dropped), plus the shared expert scaled by a
  sigmoid gate.

Departures from the publications, each forced by what is compared:
the weights are the served engine's own leaves (int8 with per-channel
scales, dequantised here to float32: the comparison is of the
arithmetic, not of the quantisation); matmuls run at
``jax.default_matmul_precision("highest")`` because a TPU otherwise
multiplies float32 in bfloat16 passes.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``init_params``): stacked layers, ``{"w8", "scale"}``
leaves.
"""

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


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, T, heads, D] at positions 0..T-1."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf, lp, x):
    B, T, _ = x.shape
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // nh

    def proj(name, heads):
        y = x @ _deq(lp[name])
        if name + "_bias" in lp:
            y = y + lp[name + "_bias"].astype(jnp.float32)
        return y.reshape(B, T, heads, hd)

    q = _rope(proj("q", nh), hf["rope_theta"])
    k = _rope(proj("k", nkv), hf["rope_theta"])
    v = proj("v", nkv)
    group = nh // nkv
    q = q.reshape(B, T, nkv, group, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k) / jnp.sqrt(float(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(B, T, nh * hd)
    return o @ _deq(lp["o"])


def _dense_mlp(lp, h):
    return (jax.nn.silu(h @ _deq(lp["gate"])) * (h @ _deq(lp["up"]))
            ) @ _deq(lp["down"])


def _moe_mlp(hf, lp, h):
    """Every expert over every token, weighted by the routing
    probability where the expert is among the token's top-k and by
    zero elsewhere; then the shared expert behind its sigmoid gate."""
    B, T, H = h.shape
    x = h.reshape(B * T, H)
    E, k = hf["num_experts"], hf["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ lp["router"].astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if hf.get("norm_topk_prob", False):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.zeros((B * T, E), jnp.float32).at[
        jnp.arange(B * T)[:, None], top_i].set(top_p)

    def one_expert(acc, e):
        def take(name):
            leaf = lp[name]
            return _deq({"w8": leaf["w8"][e], "scale": leaf["scale"][e]}
                        if isinstance(leaf, dict) else leaf[e])
        y = (jax.nn.silu(x @ take("gate")) * (x @ take("up"))
             ) @ take("down")
        return acc + y * weight[:, e][:, None], None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
    if hf.get("shared_expert_intermediate_size"):
        shared = (jax.nn.silu(x @ _deq(lp["s_gate"]))
                  * (x @ _deq(lp["s_up"]))) @ _deq(lp["s_down"])
        y = y + jax.nn.sigmoid(
            x @ lp["s_gate_w"].astype(jnp.float32)) * shared
    return y.reshape(B, T, H)


def _layer(hf, layers, i, x):
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        layers)
    eps = hf.get("rms_norm_eps", 1e-5)
    x = x + _attention(hf, lp, _rms(x, lp["attn_norm"], eps))
    h = _rms(x, lp["mlp_norm"], eps)
    return x + (_moe_mlp(hf, lp, h) if hf.get("num_experts")
                else _dense_mlp(lp, h))


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20. Prompts are
    right-padded to one length (causal attention: what follows a
    position cannot reach it), so one program serves them all."""
    hf = {k: v for k, v in hf.items()
          if isinstance(v, (int, float, bool)) or v is None}
    lens = [len(p) for p in prompts]
    T = -(-max(lens) // 128) * 128
    tokens = jnp.asarray([p + [0] * (T - len(p)) for p in prompts],
                         jnp.int32)
    layer = jax.jit(lambda layers, i, x: _layer(hf, layers, i, x))

    @jax.jit
    def embed(emb, tokens):
        if isinstance(emb, dict):
            return (emb["w8"][tokens].astype(jnp.float32)
                    * emb["scale"][tokens].astype(jnp.float32)[..., None])
        return emb[tokens].astype(jnp.float32)

    @jax.jit
    def head(params, x, last, want):
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x = _rms(x, params["final_norm"], hf.get("rms_norm_eps", 1e-5))
        if "lm_head" in params:
            logits = x @ _deq(params["lm_head"])
        else:   # tied: embed is [V, H] with a per-row scale
            emb = params["embed"]
            w = (emb["w8"].astype(jnp.float32)
                 * emb["scale"].astype(jnp.float32)[:, None]
                 if isinstance(emb, dict) else emb.astype(jnp.float32))
            logits = x @ w.T
        lps = jax.nn.log_softmax(logits, axis=-1)
        top_lp, top_id = jax.lax.top_k(lps, TOP)
        return jnp.take_along_axis(lps, want, axis=1), top_id, top_lp

    width = max(len(r) for r in ids)
    want = jnp.asarray([r + [0] * (width - len(r)) for r in ids],
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i in range(hf["num_hidden_layers"]):
            x = layer(params["layers"], jnp.int32(i), x)
        at, top_id, top_lp = head(
            {k: v for k, v in params.items() if k != "layers"}, x,
            jnp.asarray(lens, jnp.int32) - 1, want)
    at, top_id, top_lp = (jax.device_get(a) for a in (at, top_id, top_lp))
    return [{"prompt_tokens": lens[n],
             "logprobs": [float(v) for v in at[n][:len(ids[n])]],
             "top_ids": [int(v) for v in top_id[n]],
             "top_logprobs": [float(v) for v in top_lp[n]]}
            for n in range(len(prompts))]
