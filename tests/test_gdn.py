"""Two kinds of mixer in one model (Qwen3-Next, ``qwen3_next``): Gated
DeltaNet layers over state pages beside gated attention over the K/V
pool, on the CPU at tiny sizes with seeded weights.

- ops/gdn.py's two forms (``gdn_recurrent``, ``gdn_chunk``), in
  ``jax.numpy`` and as the kernels in interpret mode, against the
  SEQUENTIAL rule of chipbench/references/qwen3_next.py
  (``delta_rule``), at lengths that are not a multiple of the chunk,
  with padded tails and a carried state; the chunkwise kernel
  (``gdn_chunk_scan``: q, k, v read in place, the transform made in
  VMEM) against ``_chunk_prep`` + ``_scan_jnp``, and its substitution
  alone against the ``jax.numpy`` loop;
- the model through both caches (prefill in several chunks with a
  padded last one, then decode steps beside a parked row) against the
  reference's full forward pass, float32, to 1e-4 on the
  log-probabilities: the reference with its activations rounded to
  bfloat16 stands 30 times farther;
- the engine: a slot move, a preemption with recompute, an abort and
  a page's reuse leave every request's tokens and log-probabilities
  as a run alone gives them; admission counts pages;
- every refusal by name; the configuration's mapping; the K/V prefill
  kernel in q blocks at 8 groups of 256 (interpret mode) against
  ``jax.numpy``; the CPU rehearsal of the benchmark's cell.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next as ref
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config
from production_stack_tpu.ops import gdn, pallas_paged

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dataclasses.replace(get_config("debug-gdn"), dtype=jnp.float32)
# debug-gdn under the published keys, for the reference
HF = dict(
    model_type="qwen3_next", num_hidden_layers=8, full_attention_interval=4,
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    head_dim=128, partial_rotary_factor=0.25, rope_theta=10000.0,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, moe_intermediate_size=128,
    shared_expert_intermediate_size=128, vocab_size=512,
    decoder_sparse_step=1, mlp_only_layers=[], hidden_act="silu",
    max_position_embeddings=512, tie_word_embeddings=False)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def kernels():
    """ops/gdn.py's and the paged kernels in interpret mode."""
    was = pallas_paged._override
    pallas_paged.set_flash_enabled(True)
    yield
    pallas_paged.set_flash_enabled(was)


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def params_with_live_norms(cfg=CFG, seed=3, quantization=None):
    """Seeded weights, the zero / one norm weights and the convolution
    moved off their initial values (a norm that ignored its weight
    would pass at the initialisation)."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed),
                               quantization=quantization)
    key = jax.random.PRNGKey(seed + 100)
    for group, names in (("layers", ("attn_norm", "mlp_norm")),
                         ("attn_layers", ("q_norm", "k_norm")),
                         ("gdn_layers", ("gdn_norm", "conv"))):
        for n in names:
            key, sub = jax.random.split(key)
            leaf = params[group][n]
            params[group][n] = leaf + 0.3 * jax.random.normal(
                sub, leaf.shape, leaf.dtype)
    return params


# ---------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------

def _rule_inputs(T, B=2, hk=2, hv=4, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, hk, d))
    k = jax.random.normal(ks[1], (B, T, hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, hv, d))
    # decays from nearly none to e^-7 a token: A up to 16
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, hv), minval=-4.0,
                                    maxval=2.7)) * 0.5
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, hv)))
    state = jax.random.normal(ks[5], (3, 5, hv, d, d))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("T", [1, 5, 8, 9, 64, 100, 192, 512])
@pytest.mark.parametrize("how", ["jnp", "kernel"])
def test_both_forms_are_the_sequential_rule(T, how, request):
    """T <= 8 the recurrent form, above it the chunkwise one (chunks
    of 64: 9, 100 are no multiple; the kernel takes them in pairs, so
    64 and 192 are padded there too); two value heads a key head; row
    0 from its page, row 1 from a zero state (a chunk at position 0);
    other pages and layers are left as they were."""
    if how == "kernel":
        request.getfixturevalue("kernels")
    q, k, v, g, beta, state = _rule_inputs(T)
    ids, fresh = jnp.array([2, 4]), jnp.array([False, True])
    assert gdn.gdn_path(T) == ("gdn_recurrent" if T <= 8 else "gdn_chunk")
    o, new = jax.jit(gdn.mix)(q, k, v, g, beta, state, ids, jnp.int32(1),
                              fresh)
    for b in range(2):
        S0 = (jnp.zeros_like(state[1, 0]) if fresh[b]
              else state[1, ids[b]])
        want, S = ref.delta_rule(jnp.repeat(q[b], 2, 1),
                                 jnp.repeat(k[b], 2, 1), v[b], g[b],
                                 beta[b], S0)
        assert worst(o[b], want) < 2e-5
        assert worst(new[1, ids[b]], S) < 2e-5
    untouched = np.array([0, 1, 3])
    assert worst(new[1][untouched], state[1][untouched]) == 0
    assert worst(new[0], state[0]) == 0 and worst(new[2], state[2]) == 0


@pytest.mark.parametrize("T,B,hk,hv,ids,fresh", [
    (64, 2, 2, 4, (2, 4), (False, True)),       # one pair, half padding
    (100, 2, 4, 4, (4, 1), (True, False)),      # Hk == Hv, pages apart
    (192, 1, 1, 4, (3,), (False,)),             # four value heads a key's
    (512, 2, 2, 2, (1, 3), (False, False)),     # four pairs a grid step
    (640, 1, 1, 2, (2,), (False,)),             # five: a stack part full
    (1536, 1, 1, 1, (4,), (True,)),             # two steps of six pairs
    (2048, 1, 1, 2, (0,), (False,)),            # two of eight: a chunk
])
def test_the_chunk_kernel_is_the_jnp_form(T, B, hk, hv, ids, fresh,
                                          kernels):
    """``gdn_chunk_scan`` in interpret mode against ``_chunk_prep`` +
    ``_scan_jnp`` on the same inputs, to float32 rounding: the key head
    picked by the block's index map (h // (Hv // Hk)), q, k, v and o
    as [B, T, H * D]; several pairs of chunks a grid step (the most,
    up to eight, that divide the row's: 5 of 5, 6 of 12, 8 of 16),
    the head's matrix carried across the steps; a row that continues
    from its page beside one that starts from zero, their pages not
    adjacent; no other page or layer touched."""
    q, k, v, g, beta, state = _rule_inputs(T, B=B, hk=hk, hv=hv, seed=T)
    ids, fresh = jnp.array(ids), jnp.array(fresh)
    pairs = -(-T // gdn._PAIR)
    assert pairs % gdn._group_pairs(pairs) == 0
    got_o, got = jax.jit(lambda *a: gdn.mix(*a))(
        q, k, v, g, beta, state, ids, jnp.int32(2), fresh)
    pallas_paged.set_flash_enabled(False)
    want_o, want = jax.jit(lambda *a: gdn.mix(*a))(
        q, k, v, g, beta, state, ids, jnp.int32(2), fresh)
    assert got_o.shape == want_o.shape == (B, T, hv, 128)
    assert worst(got_o, want_o) < 5e-6
    assert worst(got[2, ids], want[2, ids]) < 2e-5
    untouched = np.setdiff1d(np.arange(5), np.asarray(ids))
    assert worst(got[2][untouched], state[2][untouched]) == 0
    assert worst(got[:2], state[:2]) == 0


def test_the_chunk_kernel_rounds_where_the_jnp_form_rounds(kernels):
    """bfloat16 activations: ``w``, ``attn``, ``qg``, ``kd`` and the
    writes are rounded once, where ``_chunk_prep`` and ``_scan_jnp``
    round them, so the two differ by a few of those roundings taking
    the other neighbour (an entry of ``o`` in 0.2 by under 1e-3: one
    bfloat16 step of it is 1e-3), not by a bfloat16 ``T`` or ``u``
    (1e-2 and more here)."""
    q, k, v, g, beta, state = _rule_inputs(256, B=1, seed=7)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    args = (q, k, v, g * 0.1, beta, state, jnp.array([1]), jnp.int32(0),
            jnp.array([False]))
    got_o, got = jax.jit(lambda *a: gdn.mix(*a))(*args)
    pallas_paged.set_flash_enabled(False)
    want_o, want = jax.jit(lambda *a: gdn.mix(*a))(*args)
    assert worst(got_o, want_o) < 1e-3 * max(
        1.0, float(jnp.abs(want_o).max()))
    assert worst(got[0, 1], want[0, 1]) < 2e-3 * float(
        jnp.abs(want[0, 1]).max())


def _diagonal_blocks(T, B, hv, seed, monkeypatch):
    """What ``_chunk_prep`` hands the substitution for seeded inputs of
    T positions, padded to whole chunks as ``_chunked`` pads them
    (g = 0, beta = 0): [16, 16, blocks], negated and strictly lower."""
    q, k, v, g, beta, _ = _rule_inputs(T, B=B, hk=hv, hv=hv, seed=seed)
    pad = (-T) % gdn.CHUNK
    q, k, v, g, beta = (
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, g, beta))
    seen, loop = [], gdn._solve_rows_jnp
    with monkeypatch.context() as patch:
        patch.setattr(gdn, "_solve_rows_jnp", lambda At: (
            seen.append(At), loop(At))[1])
        gdn._chunk_prep(q, k, v, g, beta)
    return seen[0]


def _solve_in_the_kernel(At):
    """``_solve_stacked``, the kernel's substitution, on blocks
    [16, 16, n]: laid out as a grid step lays them (row i of pair p's
    eight blocks at row ``i * 8 + p``, a block after the other on the
    lanes; 64 blocks a step, the last step's stack part full), solved
    in interpret mode, and laid back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, g, n = gdn._SOLVE_BLOCK, gdn._GROUP, At.shape[-1]
    per = gdn._PAIR // b
    steps = -(-n // (g * per))
    A = jnp.pad(At, ((0, 0), (0, 0), (0, steps * g * per - n)))
    A = A.reshape(b, b, steps, g, per).transpose(2, 0, 3, 4, 1)

    def kernel(in_ref, out_ref, a_ref, e_ref, x_ref):
        a_ref[...] = in_ref[0]
        gdn._solve_stacked(a_ref, e_ref, x_ref)
        out_ref[0] = x_ref[...]
    tile = pl.BlockSpec((1, b * g, gdn._PAIR), lambda s: (s, 0, 0))
    X = pl.pallas_call(
        kernel, grid=(steps,), in_specs=[tile], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((steps, b * g, gdn._PAIR),
                                       At.dtype),
        scratch_shapes=[pltpu.VMEM((b * g, gdn._PAIR), At.dtype),
                        pltpu.VMEM((b - 1, b * g, gdn._PAIR), At.dtype),
                        pltpu.VMEM((b * g, gdn._PAIR), At.dtype)],
        interpret=True)(A.reshape(steps, b * g, gdn._PAIR))
    X = X.reshape(steps, b, g, per, b).transpose(1, 4, 0, 2, 3)
    return X.reshape(b, b, -1)[..., :n]


@pytest.mark.parametrize("T,B,hv", [(64, 2, 4), (100, 2, 4), (192, 2, 4),
                                    (2048, 1, 2)])
def test_the_kernels_substitution_is_the_jnp_substitution(T, B, hv,
                                                          monkeypatch):
    """The substitution inside ``gdn_chunk_scan`` (``_solve_stacked``,
    interpret mode) against the ``jax.numpy`` loop on the diagonal
    blocks of real chunks, to 1e-6 of the largest entry: 32, 64, 96
    and 256 blocks (half a grid step's stack, one, one and a half,
    four). A padded position is a row of zeros in L and stays one (the
    identity's row, once I is added)."""
    At = _diagonal_blocks(T, B, hv, 4, monkeypatch)
    blocks = B * hv * (-(-T // gdn.CHUNK)) * (gdn.CHUNK // 16)
    assert At.shape == (16, 16, blocks)
    want = gdn._solve_rows_jnp(At)
    got = _solve_in_the_kernel(At)
    assert worst(got, want) <= 1e-6 * float(jnp.abs(want).max())
    upper = jnp.arange(16)[:, None] <= jnp.arange(16)[None, :]
    assert float(jnp.abs(jnp.where(upper[..., None], got, 0.0)).max()) == 0
    if T == 100:        # positions 100-127 of each row's second chunk
        rows = np.asarray(got).reshape(16, 16, B, hv, 2, 4)
        assert np.abs(rows[..., 1, 2:][4:]).max() == 0      # 100-111
        assert np.abs(rows[..., 1, 3]).max() == 0           # 112-127
        assert np.abs(rows[..., 0, :]).max() > 0


@pytest.mark.parametrize("blocks", [64, 40, 200])
def test_the_kernels_substitution_by_stacks(blocks):
    """One whole stack of a grid step (eight pairs of eight blocks), a
    stack five pairs full, and three steps and an eighth: every block
    is solved and none leaks into its neighbour's sixteen lanes (an
    entry is spread over its OWN block's lanes, exactly: three bfloat16
    terms against a 0 / 1 matrix). Entries up to 1.5: sixteen rows
    compound them to hundreds."""
    low = jnp.arange(16)[:, None] > jnp.arange(16)[None, :]
    At = jnp.where(low[..., None], 0.5 * jax.random.normal(
        jax.random.PRNGKey(blocks), (16, 16, blocks)), 0.0)
    want = gdn._solve_rows_jnp(At)
    got = _solve_in_the_kernel(At)
    assert worst(got, want) <= 1e-6 * float(jnp.abs(want).max())
    # the inverse it is: (I + L) (I + X) = I, block by block
    eye = jnp.eye(16)[..., None]
    prod = jnp.einsum("ijn,jkn->ikn", eye - At, eye + got,
                      precision=jax.lax.Precision.HIGHEST)
    assert worst(prod, jnp.broadcast_to(eye, prod.shape)) \
        <= 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("T,real", [(64, 40), (128, 70), (192, 1)])
@pytest.mark.parametrize("how", ["jnp", "kernel"])
def test_a_padded_tail_advances_nothing(T, real, how, request):
    """Positions past ``real`` carry g = 0 and beta = 0 (what the layer
    hands the rule for positions that are not real): the state after
    the chunk is the state after its real positions, and carrying it
    into a second call gives what one call over both gives."""
    if how == "kernel":
        request.getfixturevalue("kernels")
    q, k, v, g, beta, state = _rule_inputs(T, B=1, seed=1)
    live = (jnp.arange(T) < real)[None, :, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    ids, lyr = jnp.array([3]), jnp.int32(0)
    o, new = gdn.mix(q, k, v, g, beta, state, ids, lyr, jnp.array([True]))
    want, S = ref.delta_rule(
        jnp.repeat(q[0, :real], 2, 1), jnp.repeat(k[0, :real], 2, 1),
        v[0, :real], g[0, :real], beta[0, :real],
        jnp.zeros_like(state[0, 0]))
    assert worst(o[0, :real], want) < 2e-5 and worst(new[0, 3], S) < 2e-5
    # the next chunk from the carried page: as one sequence
    q2, k2, v2, g2, beta2, _ = _rule_inputs(100, B=1, seed=2)
    o2, _ = gdn.mix(q2, k2, v2, g2, beta2, new, ids, lyr,
                    jnp.array([False]))
    both, _ = ref.delta_rule(
        *(jnp.concatenate([a, b]) for a, b in (
            (jnp.repeat(q[0, :real], 2, 1), jnp.repeat(q2[0], 2, 1)),
            (jnp.repeat(k[0, :real], 2, 1), jnp.repeat(k2[0], 2, 1)),
            (v[0, :real], v2[0]), (g[0, :real], g2[0]),
            (beta[0, :real], beta2[0]))), jnp.zeros_like(state[0, 0]))
    assert worst(o2[0], both[real:]) < 2e-5


@pytest.mark.parametrize("how", ["jnp", "kernel"])
def test_keys_that_repeat_do_not_break_the_solve(how, request):
    """Identical keys and beta near one make I + L the matrix whose
    inverse the series I - L + L^2 - ... reaches only through terms of
    1e17: forward substitution in blocks stays exact, as XLA's loop and
    as the kernel."""
    if how == "kernel":
        request.getfixturevalue("kernels")
    T = 128
    q, k, v, g, beta, state = _rule_inputs(T, B=1, seed=3)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = g * 1e-3, jnp.full_like(beta, 0.999)
    o, _ = gdn.mix(q, k, v, g, beta, state, jnp.array([1]), jnp.int32(0),
                   jnp.array([True]))
    want, _ = ref.delta_rule(jnp.repeat(q[0], 2, 1), jnp.repeat(k[0], 2, 1),
                             v[0], g[0], beta[0],
                             jnp.zeros_like(state[0, 0]))
    assert worst(o[0], want) < 1e-4 * max(1.0, float(jnp.abs(want).max()))


def test_the_transform_table_tool_rehearses_on_the_cpu():
    """tools/gdn_prep_table.py at 2 key / 4 value heads and 128 tokens,
    the kernel in interpret mode (a process of its own: the tool
    switches the kernels for itself): a row a form, the whole rule's
    time in each and the transform's alone where ``_chunk_prep`` runs,
    the kernel's ``o`` and page with what they differ by from the
    ``jax.numpy`` form's."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "gdn_prep_table.py"),
         "--tiny", "--allow-cpu", "--tokens", "128", "--repeat", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    plain, kernel = line["rows"]
    assert line["one_kernel"] and (plain["form"], kernel["form"]) == (
        "jnp", "kernel")
    assert plain["kernels"] == [] and kernel["kernels"] == ["gdn_chunk_scan"]
    for row in (plain, kernel):     # no device plane here
        assert row["clock"] == "host" and row["ops_us"] == {}
        assert row["rule_ms"] > 0 and row["heads"] == [2, 4]
    assert plain["prep_ms"] > 0 and "prep_ms" not in kernel
    assert set(kernel["largest_difference"]) == {"o", "state"}
    for name, entry in kernel["largest_entry"].items():
        assert 0 < kernel["largest_difference"][name] <= 1e-3 * max(
            entry, 1.0)


def test_the_convolution_keeps_its_last_real_inputs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    prev = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 16))
    y, new = gdn.causal_conv(x, w, prev, jnp.array([10, 4]))
    full = jnp.concatenate([prev, x], axis=1)
    want = sum(full[:, j:j + 10] * w[j] for j in range(4))
    assert worst(y, jax.nn.silu(want)) < 1e-6
    assert worst(new[0], x[0, 7:10]) == 0       # all ten are real
    assert worst(new[1], x[1, 1:4]) == 0        # four are
    _, none = gdn.causal_conv(x, w, prev, jnp.array([0, 2]))
    assert worst(none[0], prev[0]) == 0
    assert worst(none[1], jnp.concatenate([prev[1, 2:], x[1, :2]])) == 0


# ---------------------------------------------------------------------
# the model through both caches
# ---------------------------------------------------------------------

def _served_logprobs(params, toks, kernel_tables=None, chunk=64,
                     prefill_to=140, cfg=CFG):
    """Row 0 of a batch of two (row 1 parked): the prompt's first
    ``prefill_to`` tokens in chunks of ``chunk`` (the last one padded
    in its bucket), the rest as decode steps -> log-probabilities after
    every position [T, V]."""
    B, Bs, MB = 2, 16, 16
    T = len(toks)
    cache = kv_pool.cache_for(cfg, B * MB + 1, Bs, cfg.dtype, state_pages=3)
    tables = jnp.concatenate(
        [1 + jnp.arange(B * MB).reshape(B, MB), jnp.array([[2], [1]])],
        axis=1).astype(jnp.int32)
    fwd = jax.jit(lambda p, t, pos, c, tv: llama.forward(
        p, cfg, t, pos, c, block_tables=tables, token_valid=tv,
        kv_len=256)[:2])
    out = []
    for c0 in range(0, prefill_to, chunk):
        n = min(chunk, prefill_to - c0)
        t = np.zeros((B, chunk), np.int32)
        t[0, :n] = toks[c0:c0 + n]
        pos = np.stack([np.arange(chunk) + c0, np.arange(chunk) + 10000])
        tv = np.zeros((B, chunk), bool)
        tv[0, :n] = True
        logits, cache = fwd(params, jnp.asarray(t), jnp.asarray(pos),
                            cache, jnp.asarray(tv))
        out.append(logits[0, :n])
    for i in range(prefill_to, T):
        logits, cache = fwd(
            params, jnp.asarray([[toks[i]], [0]], jnp.int32),
            jnp.asarray([[i], [10000]]), cache,
            jnp.asarray([[True], [False]]))
        out.append(logits[0, :1])
    # the parked row wrote the trash page alone
    assert float(jnp.abs(cache.state[:, 1]).max()) == 0
    assert float(jnp.abs(cache.state[:, 2]).max()) > 0
    return jax.nn.log_softmax(jnp.concatenate(out, 0), -1)


def test_prefill_in_chunks_then_decode_is_the_reference_forward():
    """150 tokens: two whole chunks of 64, a chunk of 12 padded to 64
    after a carried state, ten decode steps, against the reference's
    ONE pass (the sequential recurrence, full causal attention, every
    expert evaluated). 1e-4 on a log-probability: float32 against
    float32; the reference with its activations rounded to bfloat16
    between blocks stands over 30 times farther, so a bfloat16 product
    anywhere on the served path would show."""
    params = params_with_live_norms()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (150,), 0,
                                         512)).tolist()
    got = _served_logprobs(params, toks)
    want = ref.logprobs(params, HF, toks)
    assert worst(got, want) < 1e-4
    rounded = ref.logprobs(params, {**HF, "round_to": "bfloat16"}, toks)
    assert worst(rounded, want) > 30 * 1e-4


def test_the_kernels_serve_what_the_jnp_forms_do(kernels):
    """The same through the kernels in interpret mode: ops/gdn.py's
    two, the paged prefill and decode kernels at heads of 128, the
    experts' list and grouped kernels."""
    params = params_with_live_norms()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (80,), 0,
                                         512)).tolist()
    got = _served_logprobs(params, toks, prefill_to=76)
    assert worst(got, ref.logprobs(params, HF, toks)) < 1e-4


@pytest.mark.parametrize("breakage", [
    {"gdn_control": "no_decay"}, {"gdn_control": "beta_one"},
    {"gdn_control": "no_conv_carry", "conv_chunk": 64},
    {"attn_control": "no_gate"}, {"attn_control": "rotary_all"},
    {"num_experts_per_tok": 1}, {"norm_topk_prob": False},
    {"partial_rotary_factor": 0.5}],
    ids=lambda b: "-".join(map(str, b.values())))
def test_a_reference_that_departs_in_one_place_stands_apart(breakage):
    """What the 1e-4 would catch: each equation of the issue, changed
    in the reference alone, moves a log-probability by over 1e-2."""
    params = params_with_live_norms()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (150,), 0,
                                         512)).tolist()
    want = ref.logprobs(params, HF, toks)
    assert worst(ref.logprobs(params, {**HF, **breakage}, toks),
                 want) > 1e-2


# ---------------------------------------------------------------------
# the engine: pages at admission, moves, preemption, abort, reuse
# ---------------------------------------------------------------------

def _engine(**kw):
    cfg = dict(model="debug-gdn", max_num_seqs=4, max_model_len=256,
               kv_pool_tokens=1024, prefill_chunk=64, kv_block_size=16,
               dtype="float32", seed=3)
    return LLMEngine(EngineConfig(**{**cfg, **kw}))


def _run(eng, ids, between=None, limit=400):
    done = {}
    for n in range(limit):
        if not eng.has_work:
            break
        for out in eng.step():
            if out.finished:
                done[out.seq_id] = out
        if between is not None:
            between(n)
    return done


PROMPTS = [list(map(int, np.random.default_rng(0).integers(0, 256, n)))
           for n in (150, 40, 90, 200, 33, 70)]
GREEDY = SamplingOptions(max_tokens=12, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def alone():
    """Each prompt served alone: its tokens and log-probabilities."""
    out = {}
    with jax.default_matmul_precision("highest"):
        eng = _engine()
        for i, p in enumerate(PROMPTS):
            sid = eng.add_request(p, GREEDY)
            _run(eng, [sid])
            seq = eng.seqs[sid]
            out[i] = (list(seq.output_tokens), list(seq.output_logprobs))
    return out


def _same(eng, sid, want):
    """The tokens, and the log-probabilities to 2e-3: a batch of four
    rows sums in another order than a row alone (3e-4 seen), a page
    that leaked into another sequence moves them by tenths."""
    seq = eng.seqs[sid]
    n = len(want[0])
    assert list(seq.output_tokens)[:n] == want[0]
    assert np.allclose(seq.output_logprobs[:n], want[1], atol=2e-3)


def test_turnover_and_page_reuse_leave_every_request_as_alone(alone):
    """Six requests through four slots and four pages: two wait for a
    page and a slot, take a freed page (never cleared: the first chunk
    starts from zero inside the layer) and read as they read alone;
    the pages all come back."""
    eng = _engine()
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
    _run(eng, ids)
    for i, sid in enumerate(ids):
        _same(eng, sid, alone[i])
    pool = eng.block_mgr.frag_report()
    assert pool["state_pages"] == {"total": 4, "live": 0}
    assert pool["layout"] == "kv+state"
    assert pool["state_bytes_per_slot"] == CFG.state_bytes_per_seq \
        == eng.runner.cache.state_bytes_per_slot
    state = eng.eff.report()["state"]
    assert state["pages_alloc"] == state["pages_freed"] == 6
    assert state["scan_tokens"] == sum(map(len, PROMPTS))
    assert state["prefill_keys"] == sum(n * (n + 1) // 2
                                        for n in map(len, PROMPTS))
    assert state["step_rows"] > 0 and state["alloc_failures"] >= 0
    paths = eng.device_report()["mixer_paths"]
    assert {v for k, v in paths.items() if k.startswith("decode")} \
        == {"gdn_recurrent"}
    assert {v for k, v in paths.items() if k.startswith("prefill")} \
        == {"gdn_chunk"}
    assert eng.load_report()["state_pages_live"] == 0


def test_a_slot_move_a_preemption_and_an_abort_change_nothing(alone):
    """Mid-run: the request in the lowest slot is aborted (its page
    goes back), a running one is preempted (its page goes back; it
    recomputes from position 0 into whatever page it is handed next),
    and compaction moves rows to lower slots (a table row rewritten,
    no state copied). The survivors read as they read alone."""
    eng = _engine()
    longer = SamplingOptions(max_tokens=50, temperature=0.0,
                             ignore_eos=True)
    # the longest prompts first, so that every row has few tokens when
    # the last one joins
    order = [3, 0, 2, 1]
    ids = [eng.add_request(PROMPTS[i], longer) for i in order]
    did = {}

    def between(n):
        running = sorted(eng.scheduler.running.values(),
                         key=lambda s: s.slot)
        if "abort" not in did and len(running) == 4 and all(
                s.output_tokens for s in running):
            _run_dry(eng)
            running = sorted(eng.scheduler.running.values(),
                             key=lambda s: s.slot)
            if len(running) < 3:
                return
            did["abort"] = running[0].seq_id
            eng.abort(running[0].seq_id)
            victim = running[-1]
            did["preempt"] = victim.seq_id
            did["page"] = victim.state_page
            with eng._lock:
                eng._preempt(victim)
            assert victim.state_page == 0
            before = {s.seq_id: s.slot for s in running[1:-1]}
            with eng._lock:
                eng._compact_slots()
            did["moved"] = [s.seq_id for s in running[1:-1]
                            if s.slot != before[s.seq_id]]
            for s in running[1:-1]:     # the page rides the table row
                assert eng._tables[s.slot, -1] == s.state_page > 0

    def _run_dry(eng):
        while eng._inflight:
            eng._retire_window("decode")

    _run(eng, ids, between)
    assert did.get("moved"), did
    for i, sid in zip(order, ids):
        if sid != did["abort"]:
            _same(eng, sid, alone[i])
    assert eng.block_mgr.live_pages == 0
    assert eng.metrics.preemptions._value.get() >= 1 \
        if hasattr(eng.metrics.preemptions, "_value") else True


def test_admission_counts_pages():
    """max_num_seqs pages: a sequence is admitted with its page or not
    at all, and a missing page is counted with the blocks' failures."""
    from production_stack_tpu.engine.block_manager import BlockManager
    mgr = BlockManager(65, 16, state_pages=3, state_bytes_per_slot=7)
    a, b = mgr.alloc_page(), mgr.alloc_page()
    assert {a, b} == {1, 2} and mgr.alloc_page() is None
    assert mgr.live_pages == 2 and mgr.page_alloc_failures == 1
    assert mgr.frag_report()["alloc_failures_exhausted"] == 1
    mgr.free_page(a)
    mgr.free_page(0)            # "none": ignored
    assert mgr.alloc_page() == a and mgr.pages_freed == 1
    none = BlockManager(65, 16)
    assert none.frag_report()["state_pages"] == {"total": 0, "live": 0}


# ---------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------

@pytest.mark.parametrize("kw,names", [
    (dict(enable_prefix_caching=True), "prefix caching"),
    (dict(kv_transfer_config={"kv_role": "kv_both"}), "KV connector"),
    (dict(speculative_ngram_tokens=3), "n-gram speculation"),
    (dict(checkpoint="/nowhere"), "checkpoint loader"),
    (dict(lora_adapters={"a": "random:1"}), "LoRA"),
    (dict(kv_dtype="int8"), "int8 KV pool")])
def test_what_state_pages_cannot_run_with_is_refused_by_name(kw, names):
    with pytest.raises(ValueError) as err:
        ModelRunner(get_config("debug-gdn"), EngineConfig(
            model="debug-gdn", max_num_seqs=2, max_model_len=128, **kw))
    assert names in str(err.value) and "state pages" in str(err.value)


def test_a_mesh_and_the_chunk_tools_are_refused_by_name():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh.*state pages"):
        ModelRunner(get_config("debug-gdn"), EngineConfig(
            model="debug-gdn", max_num_seqs=2, max_model_len=128),
            mesh=mesh)
    runner = ModelRunner(get_config("debug-gdn"), EngineConfig(
        model="debug-gdn", max_num_seqs=2, max_model_len=128))
    for call in (lambda: runner.extract_chunk(0, 0, 16),
                 lambda: runner.inject_chunk(0, 0, np.zeros((2, 16, 2, 128)),
                                             np.zeros((2, 16, 2, 128)))):
        with pytest.raises(ValueError, match="kv\\+state"):
            call()
    with pytest.raises(ValueError, match="without caches"):
        llama.encode(runner.params, get_config("debug-gdn"),
                     jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="checkpoint loader"):
        from production_stack_tpu.models.hf_loader import load_checkpoint
        load_checkpoint(get_config("debug-gdn"), "/nowhere")


@pytest.mark.parametrize("change,names", [
    (dict(decoder_sparse_step=2), "dense interleaving"),
    (dict(mlp_only_layers=[0]), "dense interleaving"),
    (dict(num_hidden_layers=6), "whole periods"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"type": "linear", "factor": 2}), "rope_scaling"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(linear_num_value_heads=3), "linear_num_value_heads"),
    (dict(layer_types=["full_attention"] * 8), "layer_types"),
    (dict(deployment={"chips_per_layer": 3, "router_experts": 8}),
     "deployment")])
def test_the_mapping_refuses_what_the_tree_does_not_build(change, names):
    with pytest.raises(ValueError, match=names):
        ModelConfig.from_hf_config({**HF, **change}, name="x")


def test_the_mapping_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(
        {**HF, "deployment": {"chips_per_layer": 2, "chip_index": 1,
                              "router_experts": 16}}, name="x")
    assert cfg.layer_pattern == ("gdn", "gdn", "gdn", "attn")
    assert (cfg.num_periods, cfg.gdn_layers, cfg.attn_layers) == (2, 6, 2)
    assert (cfg.router_experts, cfg.expert_offset) == (16, 8)
    assert (cfg.rotary_dim, cfg.rope_dim_, cfg.head_dim_) == (32, 32, 128)
    assert cfg.rms_norm_offset and cfg.attn_gate and cfg.qk_norm
    assert cfg.norm_topk_prob and cfg.shared_expert_gate
    assert cfg.gdn_channels == 2 * 2 * 128 + 4 * 128
    params = jax.eval_shape(lambda: llama.init_params(
        dataclasses.replace(cfg, router_experts=0, expert_offset=0),
        jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(params)) == dataclasses.replace(
        cfg, router_experts=0, expert_offset=0).num_params
    # every other model is a period of one attention layer
    for name in ("debug-tiny", "debug-moe", "debug-mla", "debug-dsa"):
        other = get_config(name)
        assert other.pattern_ == ("attn",) and other.gdn_layers == 0
        assert other.attn_layers == other.num_layers
        assert other.state_bytes_per_seq == 0


# ---------------------------------------------------------------------
# the K/V prefill kernel in q blocks
# ---------------------------------------------------------------------

def test_the_kv_prefill_kernel_in_q_blocks_is_the_jnp_attention(kernels):
    """2048 positions x 8 query heads a kv head x 256 miss VMEM whole
    and are cut into q blocks of 256 (paged_viable): the kernel in
    interpret mode against the gathered jax.numpy attention, a row
    whose chunk starts past a context of 128."""
    from production_stack_tpu.ops.attention import attention_with_cache
    T, H, Hkv, D, Bs = 2048, 16, 2, 256, 64
    assert not pallas_paged.paged_viable(T, H // Hkv, D, Bs)
    assert pallas_paged.attention_path(T, H // Hkv, D, Bs) == "pallas_paged"
    start, nb = 128, (128 + T) // Bs
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, T, H, D), jnp.float32)
    pool_k = jax.random.normal(ks[1], (nb + 1, Hkv, Bs, D), jnp.float32)
    pool_v = jax.random.normal(ks[2], (nb + 1, Hkv, Bs, D), jnp.float32)
    tables = (1 + jnp.arange(nb, dtype=jnp.int32))[None]
    got = pallas_paged.paged_attention(
        q, pool_k, pool_v, tables, jnp.array([start]), nb=nb,
        interpret=True)
    positions = start + jnp.arange(T)[None]
    want = attention_with_cache(
        q, kv_pool.gather_view(pool_k, tables, nb),
        kv_pool.gather_view(pool_v, tables, nb), positions,
        scale=D ** -0.5, sliding_window=None, logit_softcap=None)
    assert worst(got, want) < 2e-4


# ---------------------------------------------------------------------
# the cell on the CPU
# ---------------------------------------------------------------------

def test_rehearsal_of_the_cell_at_a_tiny_file(tmp_path):
    """The benchmark's new cell in shape on the CPU, end to end through
    router and engine (tests/chipbench/rehearsal/BENCHMARK.hybrid.json):
    state pages behind the program's normal server entry point, the
    probe against chipbench/references/qwen3_next.py, and the counter
    metrics in a traced line (no device metric from a CPU run). From a
    tree of links, so that the run keeps its ``.chipbench/`` to
    itself (tests/test_dsa.py)."""
    base = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.hybrid.json"), "--data", base,
         "--rehearse", "--workload", "tiny-gdn-closed", "--seed",
         str(2**31 + 79), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["probe"]["ok"] and len(line["probe"]["rows"]) == 3
    got = line["metrics"]
    # 6 layers x (4 matrices of 128 x 128 float32 + 3 x 1024 bfloat16)
    assert got["state_bytes_per_slot"]["value"] == 6 * (4 * 65536 + 6144)
    assert got["compiles_in_window"]["value"] == 0
    assert got["kv_alloc_failures"]["value"] == 0
    assert not set(got) & {"hybrid_decode_step_device_ms",
                           "hybrid_decode_step_roofline",
                           "hybrid_prefill_chunk_roofline",
                           "gdn_decode_kernel_roofline",
                           "gdn_prefill_kernel_roofline",
                           "device_idle_share"}
