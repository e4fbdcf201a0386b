"""The least time the steps of a model whose every mixer is a power
retention layer of degree 2 (Brumby, ``brumby``) could take on the
chip, from the configuration's published keys: the yardstick of
``retention_decode_step_roofline``, ``retention_decode_kernel_roofline``
and ``retention_prefill_kernel_roofline``.

The same work whatever implements it. A layer keeps, a sequence and
key-value head, a float32 state over the ``F = hd (hd + 1) / 2``
monomials of the key's symmetric square: ``S [F, hd]`` and ``z [F]``.
A call READS a live row's ``S`` and ``z`` ONCE, takes q, k, v and the
gate in and gives y out, and runs the recurrence's products: a token and
key-value head decays and updates the state (3 operations an element of
``S``), each of the group's queries reads it (2 an element), and the
normaliser and the monomials cost ``F`` each a vector. **THE STATE'S
WRITE IS NOT IN THE LEAST**: the recurrent form writes every live row's
state back every step, but a form that buffers the last W keys beside
the state and folds them in every W tokens writes 1/W as often and is
the same model, so a write a step is the implementation's choice; were
it counted, such a form would read over 100 %. What a chunked form
multiplies besides (the attention form inside a chunk) is not counted
either. Every weight is read once a step: the projections and the MLP
of every layer, the gate and the norms, and the head; the embedding's
rows a step reads are left out (a row a sequence). Peaks and
``least_seconds`` are ``chipbench/roofline.py``'s.
"""

from typing import Dict

STATE_BYTES = 4.0       # float32 S and z
ACT_BYTES = 2.0         # bfloat16 activations in and out of a kernel
SMALL_BYTES = 2.0       # the leaves that stay bfloat16 under int8


def sizes(hf: Dict):
    """(query heads, key-value heads, head size, monomials a head)."""
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // nh
    return nh, nkv, hd, hd * (hd + 1) // 2


def state_bytes(hf: Dict) -> float:
    """One sequence's state in ONE layer, as a step reads it: ``S`` and
    ``z`` of every key-value head (34 080 768 B at 8 heads of 128)."""
    _, nkv, hd, F = sizes(hf)
    return nkv * F * (hd + 1) * STATE_BYTES


def layer_weights(hf: Dict):
    """(quantised, small) parameters of ONE layer: q, k, v, o and the
    SwiGLU MLP | the gate and its bias, the two head norms, the two
    layer norms."""
    h, i = hf["hidden_size"], hf["intermediate_size"]
    nh, nkv, hd, _ = sizes(hf)
    return (2 * h * nh * hd + 2 * h * nkv * hd + 3 * h * i,
            h * nkv + nkv + 2 * hd + 2 * h)


def retention_call_needs(hf: Dict, rows: int, tokens: float
                         ) -> Dict[str, float]:
    """ONE layer's power retention over ``tokens`` positions in all of
    ``rows`` live rows: each row's state in once, q, k, v and the gate
    in, y out, the recurrence's products (module text)."""
    nh, nkv, hd, F = sizes(hf)
    group = nh // nkv
    per_token = ((nh + 2 * nkv) * hd * ACT_BYTES     # q, k, v
                 + nkv * 4.0                         # the log-gate
                 + nh * hd * ACT_BYTES)              # y
    return {"bytes": tokens * per_token + rows * state_bytes(hf),
            "ops": tokens * nkv * ((3.0 + 2.0 * group) * F * hd
                                   + 4.0 * (1 + group) * F)}


def decode_step_needs(hf: Dict, rows: int,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of ``rows`` live rows: every weight read once,
    every live row's state read once a layer."""
    L, h = hf["num_hidden_layers"], hf["hidden_size"]
    quantised, small = layer_weights(hf)
    head = h * hf["vocab_size"]
    call = retention_call_needs(hf, rows, rows)
    return {"bytes": (weight_bytes_per_param * (L * quantised + head)
                      + SMALL_BYTES * (L * small + h) + L * call["bytes"]),
            "ops": (2.0 * rows * (L * (quantised + small) + head)
                    + L * call["ops"])}
