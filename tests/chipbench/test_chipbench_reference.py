"""chipbench's plain reference against the program's forward pass at
tiny size on the CPU, for both blocks: dense GQA, and the Qwen2-MoE
block (shared expert, sigmoid gate, norm_topk_prob false)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import engine_child, reference, roofline
from chipbench.references import llama_family

HERE = os.path.dirname(os.path.abspath(__file__))
# The program computes in bfloat16 and the reference in float32 on the
# same int8 weights; through two tiny layers that measures 0.002-0.006
# on the CPU. 0.02 is three times that, and far under what a wrong
# mask (0.04 and more), a missing shared expert or renormalised top-k
# weights measure (tests below).
TINY_TOLERANCE = 0.02


def setup(name):
    with open(os.path.join(HERE, "rehearsal", "configs", name + ".json")) as f:
        conf = json.load(f)
    from production_stack_tpu.models import llama
    cfg = engine_child.model_config(conf, name)
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 77)]
    served = []
    for p in prompts:
        logits = llama.forward_train(params, cfg, jnp.asarray([p]))[0, -1]
        lps = jax.nn.log_softmax(logits.astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_reference_agrees_with_the_program(name):
    conf, params, prompts, served = setup(name)
    rows = llama_family.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("name,breakage", [
    ("tiny-dense", {"rms_norm_eps": 0.01}),
    ("tiny-dense", {"num_key_value_heads": 4}),
    ("tiny-moe", {"norm_topk_prob": True}),
    ("tiny-moe", {"shared_expert_intermediate_size": 0}),
    ("tiny-moe", {"num_experts_per_tok": 1}),
])
def test_the_tolerance_sees_a_wrong_block(name, breakage):
    """A reference that departs from the served mathematics in one
    place must fall outside the tolerance: the comparison can tell."""
    conf, params, prompts, served = setup(name)
    wrong = {**conf, **breakage}
    if "num_key_value_heads" in breakage:
        # the same weights read as if every query head had its own kv
        layers = dict(params["layers"])
        for k in ("k", "v"):
            leaf = layers[k]
            layers[k] = {"w8": jnp.concatenate([leaf["w8"]] * 2, -1),
                         "scale": jnp.concatenate([leaf["scale"]] * 2, -1)}
        params = {**params, "layers": layers}
    rows = llama_family.next_token_logprobs(
        params, wrong, prompts, [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]


def test_token_ids_of_the_chat_api_entries():
    assert reference.token_id({"bytes": [65]}) == 65
    assert reference.token_id({"bytes": list(b"<unk:31999>")}) == 31999
    assert reference.token_id({"bytes": list(b"<eos>")}) == 257
    with pytest.raises(ValueError):
        reference.token_id({"bytes": list(b"ab")})


def test_compare_refuses_a_wrong_prompt_length_and_a_short_overlap():
    s = [{"prompt_tokens": 5, "ids": list(range(20)),
          "logprobs": [-1.0] * 20}]
    good = [{"prompt_tokens": 5, "logprobs": [-1.1] * 20,
             "top_ids": list(range(20))}]
    assert reference.compare(s, good)["ok"]
    assert not reference.compare(s, [{**good[0], "prompt_tokens": 6}])["ok"]
    assert not reference.compare(
        s, [{**good[0], "top_ids": list(range(12, 32))}])["ok"]
    assert not reference.compare(
        s, [{**good[0], "logprobs": [-1.0] * 19 + [-1.4]}])["ok"]
    assert not reference.compare([], [])["ok"]


def test_roofline_of_a_decode_step():
    with open(os.path.join(os.path.dirname(HERE), "..", "chipbench",
                           "configs", "mistral-7b-int8.json")) as f:
        hf = json.load(f)
    needs = roofline.decode_step_needs(hf, rows=16, context_tokens=6400)
    # 32 layers x 218 M + 131 M head, one byte each, + 131 KB per cached
    # token
    assert needs["bytes"] == pytest.approx(7.11e9 + 6400 * 131072, rel=0.01)
    least = roofline.least_seconds(needs, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(needs["bytes"] / 819e9)
    with pytest.raises(KeyError, match="no peaks known"):
        roofline.least_seconds(needs, "TPU v9")
    with open(os.path.join(os.path.dirname(HERE), "..", "chipbench",
                           "configs",
                           "qwen15-moe-a2.7b-int8-l12.json")) as f:
        moe = json.load(f)
    one = roofline.decode_step_needs(moe, rows=1, context_tokens=0)
    full = roofline.decode_step_needs(moe, rows=16, context_tokens=0)
    # one row touches its 4 experts; 16 rows about 40 of the 60
    per_expert = 3 * 2048 * 1408
    assert (full["bytes"] - one["bytes"]) / (12 * per_expert) == \
        pytest.approx(39.6 - 3.9, abs=0.5)


@pytest.mark.parametrize("limits, ok", [
    ((0.3, None), False),       # one prompt's widest gap: 0.5 is over
    ((None, 0.3), True),        # the run's mean gap: 0.0725 is under
    ((None, 0.05), False), ((0.6, 0.3), True), ((0.6, 0.05), False)],
    ids=["widest", "mean", "mean_over", "both", "both_mean_over"])
def test_compare_holds_a_run_to_the_limits_it_is_given(limits, ok):
    """Two prompts of twenty served log-probabilities: one token 0.5
    off in the first, every token 0.12 off in the second; the widest
    gap a prompt is 0.5 and 0.12, the mean over all forty 0.0725. A
    limit of None is not held; both numbers are always returned."""
    ids = list(range(20))
    served = [{"prompt_tokens": 9, "ids": ids, "logprobs": [-3.0] * 20},
              {"prompt_tokens": 40, "ids": ids, "logprobs": [-3.0] * 20}]
    rows = [{"prompt_tokens": 9, "top_ids": ids,
             "logprobs": [-3.5] + [-3.0] * 19},
            {"prompt_tokens": 40, "top_ids": ids, "logprobs": [-3.12] * 20}]
    out = reference.compare(served, rows, *limits)
    assert out["ok"] is ok
    assert (out["tolerance"], out["mean_limit"]) == limits
    assert out["mean_abs_logprob_diff"] == pytest.approx(0.0725)
    assert [r["max_abs_logprob_diff"] for r in out["rows"]] == \
        pytest.approx([0.5, 0.12])
    assert [r["mean_abs_logprob_diff"] for r in out["rows"]] == \
        pytest.approx([0.025, 0.12])
