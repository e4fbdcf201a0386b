"""Plain reference: the forward pass of Ouro (``ouro``; ByteDance
Ouro-2.6B, "Scaling Latent Reasoning via Looped Language Models", arXiv
2510.25741), a looped decoder, in straightforward ``jax.numpy`` float32.

No kernel, no cache, no batching tricks, nothing imported from the
program's ``ops/`` or ``models/``. The equations, from the published
``config.json`` and ``modeling_ouro.py``:

    x = E[ids]                                      (no scale)
    for t in 0 .. total_ut_steps - 1:               (the SAME layers each pass)
      for l in 0 .. num_hidden_layers - 1:
        h = RMSNorm(x; w_in[l])
        q, k, v = h Wq[l], h Wk[l], h Wv[l]         (no bias)
        q, k = RoPE(q, pos), RoPE(k, pos)           (rope_theta, the whole head,
                                                     half-split, the token's
                                                     position in every pass)
        a = softmax(q k^T / sqrt(head_dim), causal) v   (pass t's OWN k, v)
        x = x + RMSNorm(a Wo[l]; w_in2[l])          (sandwich norm)
        u = RMSNorm(x; w_post[l])
        x = x + RMSNorm((silu(u Wg[l]) * (u Wu[l])) Wd[l]; w_post2[l])
      x = RMSNorm(x; w_final)                       (after EVERY pass)
      lambda_t = sigmoid(x . g + b)                 (the exit gate)
    logits = x W_head                               (of the LAST pass)

RMSNorm is ``w * x / rms(x)`` (plain weights, no ``1 + w``). The exit
rule: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the rest of the mass
on the last pass; a token leaves at the first pass whose cumulated p
reaches ``early_exit_threshold``. The published threshold is 1 and a
sigmoid is below 1: every token runs every pass and the last pass's
logits are served. ``exit_mass`` gives the p_t.

Departures from the publication, each forced by what is compared: the
weights are the served engine's own leaves (int8 with per-channel
scales, dequantised here to float32: the comparison is of the
arithmetic, not of the quantisation); matmuls run at
``jax.default_matmul_precision("highest")``; the layers are run one at
a time, each dequantised as it is used, so that 2.67 B parameters in
float32 (10.7 GB) never stand whole beside the engine, and the prompts
in groups of at most 2048 padded positions.

Controls (keys no published file holds; chipbench/probe_seeds.py,
tools/ouro_chip_check.py, tests/test_ouro.py): ``round_to`` (a dtype's
name) rounds the residual stream and every sublayer's input to that
dtype; ``total_ut_steps`` is read from the dict handed in (3: a pass
left out); ``kv_control`` ``"last_pass"``: every pass attends over the K
and V the LAST pass of the exact forward made (the paper's decode-time
cache sharing, a different result), ``"first_pass"``: over pass 0's
(what a pool with one layer a weight layer would hold);
``norm_control`` ``"off"``: no norm between passes (the final norm once,
before the head); ``sandwich_control`` ``"off"``: no norm on a
sublayer's output. The benchmark's probe uses none.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``init_params``): stacked layers, ``{"w8", "scale"}``
leaves, ``exit_gate`` / ``exit_gate_bias``.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20
_GROUP_TOKENS = 2048    # padded positions the passes run at once


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


def _rope(x, theta):
    """x [B, T, heads, D] at positions 0..T-1, half-split layout."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(hf, layers, i, x, use=None):
    """Layer ``i`` (traced) on x [B, T, H] -> (x', (k, v)): the pass's
    own rotated keys and values [B, T, heads, D]; ``use``: the keys and
    values the attention reads in their place (a ``kv_control``)."""
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        layers)
    B, T, _ = x.shape
    nh = hf["num_attention_heads"]
    nkv = hf.get("num_key_value_heads", nh)
    hd = hf.get("head_dim") or hf["hidden_size"] // nh
    eps = hf.get("rms_norm_eps", 1e-6)
    sandwich = hf.get("sandwich_control") != "off"

    h = _at(hf, _rms(x, lp["attn_norm"], eps))
    q = _rope((h @ _deq(lp["q"])).reshape(B, T, nh, hd), hf["rope_theta"])
    k = _rope((h @ _deq(lp["k"])).reshape(B, T, nkv, hd), hf["rope_theta"])
    v = (h @ _deq(lp["v"])).reshape(B, T, nkv, hd)
    ka, va = (k, v) if use is None else use
    q = q.reshape(B, T, nkv, nh // nkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, ka) / jnp.sqrt(float(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None, None, None], s, -jnp.inf),
                       axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", p, va).reshape(B, T, nh * hd)
    a = _at(hf, a) @ _deq(lp["o"])
    x = _at(hf, x + (_rms(a, lp["post_attn_norm"], eps) if sandwich else a))
    u = _at(hf, _rms(x, lp["mlp_norm"], eps))
    m = _at(hf, jax.nn.silu(u @ _deq(lp["gate"])) * (u @ _deq(lp["up"]))
            ) @ _deq(lp["down"])
    x = _at(hf, x + (_rms(m, lp["post_mlp_norm"], eps) if sandwich else m))
    return x, (k, v)


def _numbers(hf):
    """The configuration's numbers as a hashable key."""
    return tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


@functools.lru_cache(maxsize=None)
def _layer_program(numbers):
    hf = dict(numbers)
    return jax.jit(lambda layers, i, x, use: _layer(hf, layers, i, x, use))


def _embed(emb, tokens):
    if isinstance(emb, dict):
        return (emb["w8"][tokens].astype(jnp.float32)
                * emb["scale"][tokens].astype(jnp.float32)[..., None])
    return emb[tokens].astype(jnp.float32)


def _passes(params, hf, tokens, use_of=None, keep=None):
    """The passes over tokens [B, T] -> (the last pass's normed stream
    [B, T, H], lambda [passes, B, T], the (k, v) of every layer of pass
    ``keep`` or None). ``use_of``: by layer the (k, v) every pass attends
    over in place of its own, or "first": pass 0's, from pass 1 on."""
    layer = _layer_program(_numbers(hf))
    eps = hf.get("rms_norm_eps", 1e-6)
    between = hf.get("norm_control") != "off"
    L, P = hf["num_hidden_layers"], hf.get("total_ut_steps", 1)
    x = _at(hf, _embed(params["embed"], tokens))
    lams, kept = [], None
    for t in range(P):
        made = []
        for i in range(L):
            if use_of == "first":
                use = first[i] if t else None
            else:
                use = None if use_of is None else use_of[i]
            x, kv = layer(params["layers"], jnp.int32(i), x, use)
            made.append(kv)
        if not t:
            first = made
        if keep == t:
            kept = made
        if between or t == P - 1:
            x = _at(hf, _rms(x, params["final_norm"], eps))
        lams.append(jax.nn.sigmoid(
            x @ params["exit_gate"].astype(jnp.float32)
            + params["exit_gate_bias"].astype(jnp.float32)))
    return x, jnp.stack(lams), kept


def hidden_states(params, hf: Dict, tokens):
    """(the last pass's final-normed stream [B, T, H], lambda [passes,
    B, T]) of tokens [B, T] under the controls of ``hf``; call under
    ``jax.default_matmul_precision("highest")``."""
    control = hf.get("kv_control")
    if control == "last_pass":
        plain = {k: v for k, v in hf.items() if k != "kv_control"}
        _, _, last = _passes(params, plain, tokens,
                             keep=plain.get("total_ut_steps", 1) - 1)
        x, lam, _ = _passes(params, hf, tokens, use_of=last)
    elif control == "first_pass":
        x, lam, _ = _passes(params, hf, tokens, use_of="first")
    elif control is None:
        x, lam, _ = _passes(params, hf, tokens)
    else:
        raise ValueError(f"unknown kv_control {control!r}")
    return x, lam


def exit_mass(lam) -> jnp.ndarray:
    """lambda [passes, ...] -> p [passes, ...]: ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)``, the last pass taking what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def _padded(prompts):
    T = -(-max(len(p) for p in prompts) // 128) * 128
    return jnp.asarray([list(p) + [0] * (T - len(p)) for p in prompts],
                       jnp.int32)


def _groups(prompts):
    """The prompts' indices in groups of at most _GROUP_TOKENS padded
    positions, the longest first: a group is one batch of the passes,
    and its scores and activations stand beside the engine's pool."""
    order = sorted(range(len(prompts)), key=lambda n: -len(prompts[n]))
    groups, width = [], 0
    for n in order:
        T = -(-len(prompts[n]) // 128) * 128
        if groups and (len(groups[-1]) + 1) * width <= _GROUP_TOKENS:
            groups[-1].append(n)
        else:
            groups.append([n])
            width = T
    return groups


def logprobs(params, hf: Dict, prompts, at=None) -> List[jnp.ndarray]:
    """For each prompt the log-probabilities of the next token after
    EVERY position [T_n, V], or after the positions ``at[n]`` alone
    (right-padded together to a multiple of 128: causal attention, what
    follows a position cannot reach it)."""
    out = [None] * len(prompts)
    with jax.default_matmul_precision("highest"):
        head = _deq(params["lm_head"])
        for group in _groups(prompts):
            x, _ = hidden_states(params, hf,
                                 _padded([prompts[n] for n in group]))
            for j, n in enumerate(group):
                rows = jnp.arange(len(prompts[n])) if at is None \
                    else jnp.asarray(at[n], jnp.int32)
                out[n] = jax.nn.log_softmax(x[j, rows] @ head, axis=-1)
    return out


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20."""

    @jax.jit
    def head(lm_head, x, last, want):
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        lps = jax.nn.log_softmax(x @ _deq(lm_head), axis=-1)
        top_lp, top_id = jax.lax.top_k(lps, TOP)
        return jnp.take_along_axis(lps, want, axis=1), top_id, top_lp

    width = max(len(r) for r in ids)
    out = [None] * len(prompts)
    with jax.default_matmul_precision("highest"):
        for group in _groups(prompts):
            lens = [len(prompts[n]) for n in group]
            want = jnp.asarray([list(ids[n]) + [0] * (width - len(ids[n]))
                                for n in group], jnp.int32)
            x, _ = hidden_states(params, hf,
                                 _padded([prompts[n] for n in group]))
            at, top_id, top_lp = jax.device_get(head(
                params["lm_head"], x, jnp.asarray(lens, jnp.int32) - 1,
                want))
            for j, n in enumerate(group):
                out[n] = {"prompt_tokens": lens[j],
                          "logprobs": [float(v)
                                       for v in at[j][:len(ids[n])]],
                          "top_ids": [int(v) for v in top_id[j]],
                          "top_logprobs": [float(v) for v in top_lp[j]]}
    return out
