"""Plain reference: the forward pass of Brumby (``brumby``; Manifest
AI's Brumby-14B-Base, power retention, arXiv:2507.04239) in
straightforward ``jax.numpy`` float32.

No kernel, no cache, NO STATE, no chunks and no ``phi``: the ATTENTION
form of power retention, a ``[T, T]`` matrix a head, because the
program serves the recurrent and the chunked forms and the two sides
are what the comparison is for. Nothing imported from the program's
``ops/`` or ``models/``. x is a layer's input, every norm float32:

- layer i: ``h = x + Retention_i(RMSNorm(x))``, ``y = h + W_down(
  silu(W_gate n) * W_up n)`` with ``n = RMSNorm(h)``: Qwen3's pre-norm
  residual block with SwiGLU; a final RMSNorm, an untied head;
- **power retention of degree 2** (every layer): ``q = RoPE(
  RMSNorm_head(x W_q))``, ``k = RoPE(RMSNorm_head(x W_k))``, ``v = x
  W_v``, ``log g = logsigmoid(x W_g + b_g)``, one number a key-value
  head; for query head h of key-value group ``j = h // (heads /
  key-value heads)`` at position t,

      a_ts = exp(sum_{s < l <= t} log g_l) (q_t . k_s / sqrt(hd))^2 >= 0
      y_t = sum_{s <= t} a_ts v_s / (sum_{s <= t} a_ts + eps)

  (the gates summed in float32 and only their differences, which are
  ``<= 0``, exponentiated), the heads side by side, ``W_o``.

What ``config.json`` does not say, as the configuration's file lists
under ``assumed``: the degree (2), the gate (a key-value head, with a
bias, through logsigmoid), the per-head RMSNorm on q and k and the
rotary embedding on both (kept from Qwen3-14B, which the release
retrains), ``eps`` (1e-6), the scale of ``q . k`` (``1 / sqrt(hd)``: it
cancels in the quotient up to ``eps``).

Departures from the publication: the half-split rotary layout; the
served engine's int8 leaves dequantised (int8 x per-channel scale, so
the comparison is of the arithmetic, not of the quantisation);
``highest`` matmul precision; one prompt at a time; a head at a time
and the ``[T, T]`` matrix in blocks of queries at long prompts, the
head's product in blocks of the vocabulary, so that the reference fits
beside the engine.

Controls (keys no published file holds): ``round_to`` (a dtype's name)
rounds the residual stream and every block's input to that dtype
(``chipbench/probe_seeds.py --control``, the float8 control);
``ret_control`` one of ``"no_gate"`` (log g = 0), ``"no_norm"`` (the
quotient's denominator 1), ``"degree_one"`` (``q . k`` not squared)
for tools/retention_chip_check.py and tests. The benchmark's probe
uses none.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``init_params``): ``layers`` stacked on a leading
axis, ``{"w8", "scale"}`` leaves.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20
EPS = 1e-6
_ATTEND_BLOCK = 1024    # queries a head attends at once
_HEAD_BLOCKS = 8        # the vocabulary's columns, in this many blocks


def _deq(leaf) -> jnp.ndarray:
    """A weight leaf [..., in, out] in float32 (int8 x per-output-channel
    scale, or the plain array)."""
    if isinstance(leaf, dict):
        return (leaf["w8"].astype(jnp.float32)
                * leaf["scale"].astype(jnp.float32)[..., None, :])
    return leaf.astype(jnp.float32)


def _round(x, dtype):
    """float32 x rounded to ``dtype``'s exponent and mantissa bits (a
    float32 -> bfloat16 -> float32 convert pair is simplified away by
    the TPU's compiler; reduce_precision is not)."""
    info = jnp.finfo(dtype)
    return jnp.clip(jax.lax.reduce_precision(x, info.nexp, info.nmant),
                    float(info.min), float(info.max))


def _at(hf, x):
    """x as the precision of the control holds it; the reference
    itself (no ``round_to``) keeps float32."""
    dt = hf.get("round_to")
    return x if dt is None else _round(x, dt)


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


def power_attention(q, k, v, logg, control=None):
    """Gated power attention of degree 2, full causal. q [T, nh, D]
    (scaled), k, v [T, nkv, D], logg [T, nkv] -> [T, nh, D]."""
    T, nh, D = q.shape
    groups = nh // k.shape[1]
    block = next((b for b in (_ATTEND_BLOCK, 512, 256, 128)
                  if T % b == 0), T)
    pos = jnp.arange(T)
    cum = jnp.cumsum(logg, axis=0)                           # [T, nkv]

    def head(h):
        j = h // groups
        kh, vh, ch = k[:, j], v[:, j], cum[:, j]

        def rows(t0):
            at = t0 + jnp.arange(block)
            s = jax.lax.dynamic_slice_in_dim(q[:, h], t0, block) @ kh.T
            live = at[:, None] >= pos[None, :]
            gates = jnp.exp(jnp.where(
                live, ch[at][:, None] - ch[None, :], 0.0))
            a = jnp.where(live, (s if control == "degree_one" else s * s)
                          * gates, 0.0)
            den = (1.0 if control == "no_norm"
                   else jnp.sum(a, axis=-1, keepdims=True) + EPS)
            return (a @ vh) / den
        return jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, D)

    return jnp.moveaxis(jax.lax.map(head, jnp.arange(nh)), 0, 1)


def _retention(hf, lp, x):
    """The mixer. x [T, H] (normed) -> [T, H]."""
    T = x.shape[0]
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps = hf["head_dim"], hf["rms_norm_eps"]
    control = hf.get("ret_control")
    q = _rms((x @ _deq(lp["q"])).reshape(T, nh, hd), lp["q_norm"], eps)
    k = _rms((x @ _deq(lp["k"])).reshape(T, nkv, hd), lp["k_norm"], eps)
    v = (x @ _deq(lp["v"])).reshape(T, nkv, hd)
    logg = jax.nn.log_sigmoid(x @ lp["ret_gate"].astype(jnp.float32)
                              + lp["ret_gate_bias"].astype(jnp.float32))
    if control == "no_gate":
        logg = jnp.zeros_like(logg)
    theta = hf["rope_theta"]
    q, k = _rope(q, theta) / jnp.sqrt(float(hd)), _rope(k, theta)
    o = power_attention(q, k, v, logg, control)
    return o.reshape(T, nh * hd) @ _deq(lp["o"])


def _layer(hf, params, i, x):
    """Layer ``i`` (traced)."""
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        params["layers"])
    eps = hf["rms_norm_eps"]
    x = _at(hf, x + _retention(hf, lp, _at(hf, _rms(x, lp["attn_norm"],
                                                     eps))))
    h = _at(hf, _rms(x, lp["mlp_norm"], eps))
    return _at(hf, x + (jax.nn.silu(h @ _deq(lp["gate"]))
                        * (h @ _deq(lp["up"]))) @ _deq(lp["down"]))


def _numbers(hf):
    """The configuration's numbers as a hashable key."""
    return tuple(sorted(
        (k, v) for k, v in hf.items()
        if isinstance(v, (int, float, bool, str)) or v is None))


@functools.lru_cache(maxsize=None)
def _layer_program(numbers):
    hf = dict(numbers)
    return jax.jit(lambda params, i, x: _layer(hf, params, i, x))


def hidden_states(params, hf: Dict, tokens) -> jnp.ndarray:
    """The final-normed hidden states [T, H] of one prompt (token ids
    [T]); call under ``jax.default_matmul_precision("highest")``."""
    layer = _layer_program(_numbers(hf))
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    for i in range(hf["num_hidden_layers"]):
        x = layer(params, jnp.int32(i), x)
    return _rms(x, params["final_norm"], hf["rms_norm_eps"])


def _padded(tokens) -> jnp.ndarray:
    T = len(tokens)
    return jnp.zeros((-(-T // 128) * 128,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))


@jax.jit
def _logits(lm_head, x):
    """x [..., H] against the head, the vocabulary in blocks where it
    divides (the whole head in float32 is 3.1 GB at Brumby's sizes)."""
    if not isinstance(lm_head, dict):
        return x @ lm_head.astype(jnp.float32)
    w8, scale = lm_head["w8"], lm_head["scale"].astype(jnp.float32)
    V = w8.shape[-1]
    n = _HEAD_BLOCKS if V % _HEAD_BLOCKS == 0 else 1
    blocks = jnp.moveaxis(w8.reshape(w8.shape[0], n, V // n), 1, 0)
    out = jax.lax.map(lambda w: x @ w.astype(jnp.float32), blocks)
    return jnp.moveaxis(out, 0, -2).reshape(x.shape[:-1] + (V,)) * scale


def logprobs(params, hf: Dict, tokens, at=None) -> jnp.ndarray:
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V], or after the positions ``at`` alone (a long prompt's
    [T, V] would not fit beside the engine); right-padded to a multiple
    of 128: causal layers, so what follows a position cannot reach
    it."""
    rows = slice(0, len(tokens)) if at is None else jnp.asarray(at)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, hf, _padded(tokens))
        return jax.nn.log_softmax(_logits(params["lm_head"], x[rows]),
                                  axis=-1)


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20. A prompt at a
    time, right-padded to a multiple of 128."""
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, want in zip(prompts, ids):
            x = hidden_states(params, hf, _padded(prompt))
            lps = jax.nn.log_softmax(
                _logits(params["lm_head"], x[len(prompt) - 1]), axis=-1)
            top_lp, top_id = jax.lax.top_k(lps, TOP)
            at, top_id, top_lp = jax.device_get(
                (lps[jnp.asarray(want, jnp.int32)], top_id, top_lp))
            out.append({"prompt_tokens": len(prompt),
                        "logprobs": [float(v) for v in at],
                        "top_ids": [int(v) for v in top_id],
                        "top_logprobs": [float(v) for v in top_lp]})
    return out
