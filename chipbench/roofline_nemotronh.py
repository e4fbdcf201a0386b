"""The least time the steps of Nemotron-H (``nemotron_h``: blocks that
are ONE sublayer each: Mamba-2 mixers over state pages, grouped-query
attention over a K/V pool of the attention blocks alone, ungated relu^2
experts of which this chip holds a share) could take on the chip, from
the configuration's published keys: the yardstick of
``nemotronh_decode_step_roofline``, ``nemotronh_prefill_chunk_roofline``,
``ssd_decode_kernel_roofline`` and ``ssd_prefill_kernel_roofline``.

The same work whatever implements it, at the PUBLISHED shapes: an
expert is 2 x hidden x ``moe_intermediate_size`` parameters however
wide the program stores it; a Mamba-2 state is heads x head_dim x
``ssm_state_size`` float32 however it is laid out. A decode step reads
every weight outside the routed experts once (the Mamba-2 mixers, the
attention blocks, routers, shared experts, norms, the head's slice),
the routed experts the PROGRAM's counter says its rows hit
(``totals.moe``: one source, as roofline_latent's), K and V of the live
contexts in the attention blocks, and reads and writes a live row's
state page (the float32 state and the convolution's inputs) once a
Mamba-2 block. A prefill chunk is held to the larger of its operations
at the matrix unit's peak and its bytes: two operations a weight a
token passes (the held experts at the assignments the program counted
to have landed here), the causal attention products at the keys in
context, and the scan. The scan's operations are the RECURRENCE's,
five an entry of the state and token (the decay's product, ``dt x (x)
B`` and its sum into the state, ``h C`` and its sum over the state) and
one exponential a head: what a chunked form multiplies besides is the
implementation's choice, and its products run where the fastest unit
that can take them is, so they are held to the matrix unit's peak (a
peak taken high makes a share read LOW, never over 100 %).

Peaks: ``chipbench/roofline.py``'s.
"""

from typing import Dict

from chipbench import roofline

CACHE_BYTES = 2.0       # bfloat16 K and V, convolution inputs
STATE_BYTES = 4.0       # float32 state
ACT_BYTES = 2.0         # bfloat16 x, B, C into the scan
OUT_BYTES = 4.0         # float32 y out of it, dt into it
SMALL_BYTES = 2.0       # the leaves that stay bfloat16 under int8
ENTRY_OPS = 5.0         # operations an entry of h and token


def is_nemotronh(hf: Dict) -> bool:
    return hf.get("model_type") == "nemotron_h"


def sizes(hf: Dict) -> Dict[str, int]:
    """The model's sizes and how many blocks of each kind it has."""
    letters = hf["hybrid_override_pattern"]
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N = hf["n_groups"], hf["ssm_state_size"]
    return dict(
        h=hf["hidden_size"], V=hf["vocab_size"],
        nh=hf["num_attention_heads"], nkv=hf["num_key_value_heads"],
        hd=hf["head_dim"], H=H, P=P, G=G, N=N, di=H * P,
        ch=H * P + 2 * G * N, taps=hf.get("conv_kernel", 4),
        held=hf["n_routed_experts"],
        router=(hf.get("deployment") or {}).get(
            "router_experts", hf["n_routed_experts"]),
        k=hf["num_experts_per_tok"], mi=hf["moe_intermediate_size"],
        si=hf.get("n_shared_experts", 0) * hf.get(
            "moe_shared_expert_intermediate_size", 0),
        mamba=letters.count("M"), moe=letters.count("E"),
        attn=letters.count("*"))


def kv_token_bytes(hf: Dict) -> float:
    """K and V of one token in ONE attention block."""
    s = sizes(hf)
    return 2 * s["nkv"] * s["hd"] * CACHE_BYTES


def state_page_bytes(hf: Dict) -> float:
    """ONE Mamba-2 block's share of a sequence's page: the float32
    state and the convolution's last inputs."""
    s = sizes(hf)
    return (s["N"] * s["di"] * STATE_BYTES
            + (s["taps"] - 1) * s["ch"] * CACHE_BYTES)


def block_weights(hf: Dict) -> Dict[str, tuple]:
    """kind -> (quantised, small) parameters of ONE block outside the
    routed experts; "expert": ONE routed expert."""
    s = sizes(hf)
    h, di, ch = s["h"], s["di"], s["ch"]
    return {
        "mamba": (h * (di + ch + s["H"]) + di * h,
                  ch * (s["taps"] + 1) + 3 * s["H"] + di + h),
        "attn": (2 * h * s["nh"] * s["hd"] + 2 * h * s["nkv"] * s["hd"],
                 h),
        "moe": (2 * h * s["si"], h * s["router"] + s["router"] + h),
        "expert": (2 * h * s["mi"], 0),
    }


def weights_outside_experts(hf: Dict, weight_bytes_per_param: float = 1.0):
    """(bytes of every weight outside the routed experts, read once;
    the parameters a token passes outside them): all blocks, the final
    norm and the head."""
    s, w = sizes(hf), block_weights(hf)
    q = sum(s[k] * w[k][0] for k in ("mamba", "attn", "moe")) \
        + s["h"] * s["V"]
    sm = sum(s[k] * w[k][1] for k in ("mamba", "attn", "moe")) + s["h"]
    return weight_bytes_per_param * q + SMALL_BYTES * sm, q + sm


def expert_bytes(hf: Dict, weight_bytes_per_param: float = 1.0) -> float:
    return weight_bytes_per_param * block_weights(hf)["expert"][0]


def ssd_call_needs(hf: Dict, rows: float, tokens: float
                   ) -> Dict[str, float]:
    """ONE block's scan over ``tokens`` positions in all of ``rows``
    rows: each row's float32 state in and out once; x, B, C and dt in
    and y out; the recurrence's operations (module text)."""
    s = sizes(hf)
    entries = tokens * s["N"] * s["di"]
    return {"bytes": rows * 2.0 * s["N"] * s["di"] * STATE_BYTES
            + tokens * (s["ch"] * ACT_BYTES
                        + (s["di"] + s["H"]) * OUT_BYTES),
            "ops": ENTRY_OPS * entries}


def attend_ops(hf: Dict, keys: float) -> float:
    """Operations of ONE attention block over ``keys`` (query, key)
    pairs: a query head's score and its weighted sum over hd each."""
    s = sizes(hf)
    return keys * s["nh"] * 4.0 * s["hd"]


def decode_step_needs(hf: Dict, rows: float, context_tokens: float,
                      experts_read: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of ``rows`` live rows whose contexts sum to
    ``context_tokens``, reading ``experts_read`` routed experts a
    block of them (the program's count)."""
    s = sizes(hf)
    read, passed = weights_outside_experts(hf, weight_bytes_per_param)
    scan = ssd_call_needs(hf, rows, rows)
    conv = rows * 2.0 * (s["taps"] - 1) * s["ch"] * CACHE_BYTES
    share = s["k"] * s["held"] / s["router"]    # experts a token passes
    return {"bytes": read
            + s["moe"] * experts_read * expert_bytes(
                hf, weight_bytes_per_param)
            + s["attn"] * context_tokens * kv_token_bytes(hf)
            + s["mamba"] * (scan["bytes"] + conv),
            "ops": 2.0 * rows * (passed + s["moe"] * share
                                 * block_weights(hf)["expert"][0])
            + attend_ops(hf, s["attn"] * context_tokens)
            + s["mamba"] * scan["ops"]}


def prefill_chunk_needs(hf: Dict, tokens: int, in_context: float,
                        held_rows: float = None,
                        weight_bytes_per_param: float = 1.0
                        ) -> Dict[str, float]:
    """ONE prefill chunk of ``tokens`` queries of one row that have on
    average ``in_context`` keys at or before them; ``held_rows``: the
    assignments that landed on this chip's experts, a block (None: the
    even share, tokens x top-k x held / router). Bytes: every weight
    outside the experts once, every held expert once a block (a chunk
    of 2048 tokens hits them all), K and V of the context in the
    attention blocks, the row's page in and out in every Mamba-2
    block."""
    s = sizes(hf)
    read, passed = weights_outside_experts(hf, weight_bytes_per_param)
    if held_rows is None:
        held_rows = tokens * s["k"] * s["held"] / s["router"]
    context = in_context + tokens / 2.0         # the chunk's last query's
    scan = ssd_call_needs(hf, 1, tokens)
    expert = block_weights(hf)["expert"][0]
    return {"bytes": read
            + s["moe"] * min(s["held"], held_rows) * expert_bytes(
                hf, weight_bytes_per_param)
            + s["attn"] * context * kv_token_bytes(hf)
            + s["mamba"] * (scan["bytes"] + 2.0 * (s["taps"] - 1)
                            * s["ch"] * CACHE_BYTES),
            "ops": tokens * 2.0 * passed
            + s["moe"] * held_rows * 2.0 * expert
            + attend_ops(hf, s["attn"] * tokens * in_context)
            + s["mamba"] * scan["ops"]}


least_seconds = roofline.least_seconds
