"""Plain reference: the forward pass of Phi-4-mini-flash (``phi4flash``)
in straightforward ``jax.numpy`` float32.

No kernel, no cache, no chunked form, no two depths, nothing imported
from the program's ``ops/`` or ``models/``: all layers on every
position of one prompt. Written from the published descriptions (SambaY,
Ren et al., arXiv 2507.06607; Mamba, Gu and Dao, arXiv 2312.00752;
Differential Transformer, Ye et al., arXiv 2410.05258); ``x`` is the
residual stream, LN a LayerNorm with weight and bias, L the number of
layers:

- every layer: ``x += mixer(LN1(x))``; ``gate, up = split(fc1(LN2(x)))``;
  ``x += fc2(up * silu(gate))``; a final LN; the head is the embedding
  transposed; NO positional embedding of any kind;
- **Mamba** (i even, i <= L/2): ``x, z = split(in_proj(u))``; ``x =
  silu(conv1d(x) + b)``, depthwise, causal, ``d_conv`` taps; ``dt_r, B,
  C = split(x_proj(x))``; ``dt = softplus(dt_proj(dt_r) + b_dt)``; ``A =
  -exp(A_log)``; with ``h`` ``[d_state, d_inner]`` zero before the first
  token, for each token IN TURN (a ``lax.scan`` over tokens): ``h =
  exp(dt (x) A) h + (dt x) (x) B``; ``y = C . h + D x``; out =
  ``out_proj(y * silu(z))``. Layer L/2 also leaves ``M = y`` (BEFORE the
  gate, ``D x`` included);
- **differential attention with its own K/V** (i odd, i <= L/2 + 1; a
  window of ``sliding_window`` keys for i < L/2, every key at L/2 + 1):
  ``q, k, v = split(Wqkv(u) + b)``; query heads (2a, 2a+1) are q1_a,
  q2_a; key heads (2c, 2c+1) k1_c, k2_c; value heads (2c, 2c+1)
  concatenate to V_c; pair a reads c = a // (pairs / value pairs);
  ``o_a = softmax(q1 k1^T / sqrt(hd)) V - lambda softmax(q2 k2^T /
  sqrt(hd)) V``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``o_a =
  RMSNorm(o_a; w) (1 - lambda_init)``; ``out_proj`` with bias;
- **gated memory unit** (i even, i > L/2): ``out_proj(silu(in_proj(u))
  * M)``, same token;
- **cross-attention** (i odd, i > L/2 + 1): ``q = Wq(u) + b``; K and V
  are layer L/2 + 1's; the same differential form with this layer's
  lambdas and norm; no window.

Departures from the publication: the served engine's int8 leaves
dequantised (int8 x per-channel scale, so the comparison is of the
arithmetic, not of the quantisation); ``highest`` matmul precision; one
prompt at a time; attention a head pair at a time in blocks of queries,
so that the reference fits beside the engine.

Controls (keys no published file holds, for chipbench/probe_seeds.py and
tools/mamba_chip_check.py): ``round_to`` (a dtype's name) rounds the
residual stream and every block's input to that dtype;
``gmu_control`` ``"off"`` (a gated memory unit adds nothing),
``lambda_control`` ``"zero"`` (lambda = 0), ``state_control`` ``"bf16"``
(h rounded to bfloat16 after every token); ``sliding_window`` is read
from the dict handed in. The benchmark's probe uses none.

Only the layout of the program's parameter tree is taken from it
(models/llama.py ``_init_params_plan``): ``layers`` (every block's
norms, ``fc1``, ``fc2``), ``mamba_layers``, ``diff_layers``,
``gmu_layers`` and ``cross_layers`` stacked on a leading axis each,
``{"w8", "scale"}`` leaves, ``A_log`` as ``[d_state, d_inner]``.
"""

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

TOP = 20
_ATTEND_BLOCK = 1024    # queries a head pair attends at once


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


def _ln(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32) + b.astype(jnp.float32)


def _mamba(hf, lp, u):
    """u [T, H] -> (out [T, H], y [T, Di] before the gate)."""
    f32 = jnp.float32
    T = u.shape[0]
    xz = u @ _deq(lp["in_proj"])
    di = xz.shape[-1] // 2
    x, z = xz[:, :di], xz[:, di:]
    w = lp["conv"].astype(f32)                       # [taps, Di]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), f32), x], axis=0)
    x = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(taps))
                    + lp["conv_bias"].astype(f32))
    A = -jnp.exp(lp["A_log"].astype(f32))            # [N, Di]
    n = A.shape[0]
    dbc = x @ lp["x_proj"].astype(f32)
    r = dbc.shape[-1] - 2 * n
    dt = jax.nn.softplus(dbc[:, :r] @ lp["dt_proj"].astype(f32)
                         + lp["dt_bias"].astype(f32))
    Bm, Cm = dbc[:, r:r + n], dbc[:, r + n:]
    narrow = hf.get("state_control") == "bf16"

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] \
            * b_t[:, None]
        if narrow:
            h = _round(h, jnp.bfloat16)
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((n, di), f32), (x, dt, Bm, Cm))
    y = y + lp["D"].astype(f32) * x
    return (y * jax.nn.silu(z)) @ _deq(lp["out_proj"]), y


def _differential(hf, lp, i, q, k, v, window):
    """q [T, nh, hd], k, v [S, nkv, hd] (S = T: the same prompt) ->
    [T, nh * hd]; ``window``: keys a query sees (0: every one before
    it and itself)."""
    f32 = jnp.float32
    T, nh, hd = q.shape
    pairs, vpairs = nh // 2, k.shape[1] // 2
    q = q.reshape(T, pairs, 2, hd)
    k = k.reshape(T, vpairs, 2, hd)
    v = v.reshape(T, vpairs, 2 * hd)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * i.astype(f32))
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                           * lp["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                             * lp["lambda_k2"].astype(f32))) + init)
    if hf.get("lambda_control") == "zero":
        lam = 0.0
    keys = jnp.arange(T)

    def one_pair(a):
        c = a // (pairs // vpairs)
        qa = jax.lax.dynamic_index_in_dim(q, a, 1, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(k, c, 1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v, c, 1, keepdims=False)
        outs = []
        for t0 in range(0, T, _ATTEND_BLOCK):
            rows = keys[t0:t0 + _ATTEND_BLOCK, None]
            seen = keys[None, :] <= rows
            if window:
                seen = seen & (keys[None, :] > rows - window)

            def attn(qq, kk):
                s = (qq[t0:t0 + _ATTEND_BLOCK] @ kk.T) * hd ** -0.5
                return jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                      axis=-1) @ vc
            outs.append(attn(qa[:, 0], kc[:, 0])
                        - lam * attn(qa[:, 1], kc[:, 1]))
        return jnp.concatenate(outs, axis=0)                 # [T, 2 hd]

    o = jnp.moveaxis(jax.lax.map(one_pair, jnp.arange(pairs)), 0, 1)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = (o * jax.lax.rsqrt(var + hf["layer_norm_eps"])
         * lp["subln"].astype(f32)) * (1.0 - init)
    return o.reshape(T, nh * hd) @ _deq(lp["o"]) \
        + lp["o_bias"].astype(f32)


def kind_of(hf, i: int) -> str:
    """The mixer of layer ``i``: "mamba", "swa", "mamba_mem", "full",
    "gmu", "cross"."""
    half = hf["num_hidden_layers"] // 2
    if i < half:
        return "swa" if i % 2 else "mamba"
    if i <= half + 1:
        return "full" if i % 2 else "mamba_mem"
    return "cross" if i % 2 else "gmu"


def _row(params, group, n):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, n, 0, keepdims=False),
        params[group])


def _layer(hf, params, i, kind: str, x, mem, k, v):
    """Layer ``i`` (traced) of kind ``kind`` (static) -> (x', mem, k,
    v): the memory and the K and V the later layers read."""
    eps = hf["layer_norm_eps"]
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["hidden_size"] // nh
    half = hf["num_hidden_layers"] // 2
    lp = _row(params, "layers", i)
    u = _at(hf, _ln(x, lp["attn_norm"], lp["attn_norm_bias"], eps))
    T = x.shape[0]
    if kind in ("mamba", "mamba_mem"):
        out, y = _mamba(hf, _row(params, "mamba_layers", i // 2), u)
        if kind == "mamba_mem":
            mem = y
    elif kind in ("swa", "full"):
        mp = _row(params, "diff_layers", i // 2)
        qkv = u @ _deq(mp["qkv"]) + mp["qkv_bias"].astype(jnp.float32)
        q = qkv[:, :nh * hd].reshape(T, nh, hd)
        k_own = qkv[:, nh * hd:(nh + nkv) * hd].reshape(T, nkv, hd)
        v_own = qkv[:, (nh + nkv) * hd:].reshape(T, nkv, hd)
        out = _differential(hf, mp, i, q, k_own, v_own,
                            hf["sliding_window"] if kind == "swa" else 0)
        if kind == "full":
            k, v = k_own, v_own
    elif kind == "gmu":
        mp = _row(params, "gmu_layers", (i - half - 2) // 2)
        out = (jax.nn.silu(u @ _deq(mp["in_proj"])) * mem) \
            @ _deq(mp["out_proj"])
        if hf.get("gmu_control") == "off":
            out = jnp.zeros_like(out)
    else:
        mp = _row(params, "cross_layers", (i - half - 2) // 2)
        q = (u @ _deq(mp["q"]) + mp["q_bias"].astype(jnp.float32)
             ).reshape(T, nh, hd)
        out = _differential(hf, mp, i, q, k, v, 0)
    x = _at(hf, x + out)
    u = _at(hf, _ln(x, lp["mlp_norm"], lp["mlp_norm_bias"], eps))
    gu = u @ _deq(lp["fc1"])
    n = gu.shape[-1] // 2
    x = x + (gu[:, n:] * jax.nn.silu(gu[:, :n])) @ _deq(lp["fc2"])
    return _at(hf, x), mem, k, v


def _numbers(hf):
    """The configuration's numbers as a hashable key."""
    return tuple(sorted(
        (k, v) for k, v in hf.items()
        if isinstance(v, (int, float, bool, str)) or v is None))


@functools.lru_cache(maxsize=None)
def _layer_program(numbers):
    hf = dict(numbers)
    return jax.jit(lambda params, i, kind, x, mem, k, v: _layer(
        hf, params, i, kind, x, mem, k, v), static_argnums=2)


def hidden_states(params, hf: Dict, tokens) -> jnp.ndarray:
    """The final-normed hidden states [T, H] of one prompt (token ids
    [T]); call under ``jax.default_matmul_precision("highest")``."""
    layer = _layer_program(_numbers(hf))
    emb = params["embed"]
    x = (emb["w8"][tokens].astype(jnp.float32)
         * emb["scale"][tokens].astype(jnp.float32)[..., None]
         if isinstance(emb, dict) else emb[tokens].astype(jnp.float32))
    x = _at(hf, x)
    T = x.shape[0]
    nkv = hf["num_key_value_heads"]
    hd = hf["hidden_size"] // hf["num_attention_heads"]
    mem = jnp.zeros((T, params["mamba_layers"]["D"].shape[-1]),
                    jnp.float32)
    k = v = jnp.zeros((T, nkv, hd), jnp.float32)
    for i in range(hf["num_hidden_layers"]):
        x, mem, k, v = layer(params, jnp.int32(i), kind_of(hf, i), x, mem,
                             k, v)
    return _ln(x, params["final_norm"], params["final_norm_bias"],
               hf["layer_norm_eps"])


def _padded(tokens) -> jnp.ndarray:
    T = len(tokens)
    return jnp.zeros((-(-T // 128) * 128,), jnp.int32).at[:T].set(
        jnp.asarray(tokens, jnp.int32))


def _head(params):
    emb = params["embed"]
    if isinstance(emb, dict):       # per-ROW scales (the embedding's)
        return (emb["w8"].astype(jnp.float32)
                * emb["scale"].astype(jnp.float32)[:, None]).T
    return emb.astype(jnp.float32).T


def logprobs(params, hf: Dict, tokens, at=None) -> jnp.ndarray:
    """Log-probabilities of the next token after EVERY position of one
    prompt [T, V], or after the positions ``at`` alone (a long prompt
    under a wide head: 16k x 200 064 float32 is 13 GB) (right-padded to
    a multiple of 128: causal layers, so what follows a position cannot
    reach it)."""
    rows = jnp.arange(len(tokens)) if at is None \
        else jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, hf, _padded(tokens))
        return jax.nn.log_softmax(x[rows] @ _head(params), axis=-1)


def next_token_logprobs(params, hf: Dict, prompts: List[List[int]],
                        ids: List[List[int]]) -> List[Dict]:
    """For each prompt (token ids) the reference's log-probabilities of
    the next token: at ``ids[n]`` and its own top-20. A prompt at a
    time, right-padded to a multiple of 128."""

    @jax.jit
    def head(embed, x, want):
        lps = jax.nn.log_softmax(x @ _head({"embed": embed}), axis=-1)
        top_lp, top_id = jax.lax.top_k(lps, TOP)
        return lps[want], top_id, top_lp

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, want in zip(prompts, ids):
            x = hidden_states(params, hf, _padded(prompt))
            at, top_id, top_lp = jax.device_get(head(
                params["embed"], x[len(prompt) - 1],
                jnp.asarray(want, jnp.int32)))
            out.append({"prompt_tokens": len(prompt),
                        "logprobs": [float(v) for v in at],
                        "top_ids": [int(v) for v in top_id],
                        "top_logprobs": [float(v) for v in top_lp]})
    return out
