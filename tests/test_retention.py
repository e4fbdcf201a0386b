"""A model whose only cache is state pages (Brumby, ``brumby``): power
retention layers of degree 2 in every layer, on the CPU at tiny sizes
with seeded weights.

- ops/retention.py's two forms (``retention_recurrent``,
  ``retention_chunk``), in ``jax.numpy`` and as the kernels in
  interpret mode, against the ATTENTION form of
  chipbench/references/brumby.py (``power_attention``), at lengths that
  are not a multiple of the chunk, with padded tails and a carried
  state; the chunked rule against the recurrent one across a chunk
  boundary; a decode window's form (the pages read a step, written once
  by the fold) against the recurrent rule a position at a time;
- the model through its pages (prefill in several chunks with a padded
  last one, then decode steps beside a parked row) against the
  reference's full forward pass, float32, to 1e-4 on the
  log-probabilities: the reference with its activations rounded to
  bfloat16 stands 50 times farther, one that leaves out a part of the
  equations thousands;
- the engine: admission by pages (a sequence holds ONE, a full batch
  makes the next wait), a freed page's reuse, a slot move, a preemption
  with recompute and an abort leave every request's tokens and
  log-probabilities as a run alone gives them;
- every refusal by name; the configuration's mapping.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import brumby as ref
from production_stack_tpu.engine.block_manager import BlockManager
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config
from production_stack_tpu.ops import pallas_paged, retention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dataclasses.replace(get_config("debug-brumby"), dtype=jnp.float32)
# debug-brumby under the published keys, for the reference
HF = dict(model_type="brumby", num_hidden_layers=2, hidden_size=64,
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          intermediate_size=128, vocab_size=512, rope_theta=10000.0,
          rms_norm_eps=1e-6, hidden_act="silu",
          max_position_embeddings=512, tie_word_embeddings=False)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def kernels():
    """ops/retention.py's kernels in interpret mode."""
    was = pallas_paged._override
    pallas_paged.set_flash_enabled(True)
    yield
    pallas_paged.set_flash_enabled(was)


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


# ---------------------------------------------------------------------
# the two forms against the attention form
# ---------------------------------------------------------------------

def _inputs(T, Hkv, G, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, T, Hkv * G, D)) * D ** -0.5
    k = jax.random.normal(ks[1], (1, T, Hkv, D))
    v = jax.random.normal(ks[2], (1, T, Hkv, D))
    # gates from 0.7 to 0.999: memories of three to a thousand tokens
    logg = jax.nn.log_sigmoid(
        jax.random.normal(ks[3], (1, T, Hkv)) * 2 + 4)
    F = retention.features(D)
    # pages that hold something: a fresh row must start from zero anyway
    state = jax.random.normal(ks[4], (2, 3, Hkv, F, D))
    norm = jax.random.normal(ks[4], (2, 3, F * Hkv // D, D))
    return q, k, v, logg, state, norm


def _through(q, k, v, logg, state, norm, splits, page=2, layer=1):
    """The T positions in calls of ``splits`` positions each, the state
    carried in page ``page`` of layer ``layer``."""
    ids, out, at = jnp.array([page], jnp.int32), [], 0
    for n in splits:
        cut = slice(at, at + n)
        y, state, norm = retention.retain(
            q[:, cut], k[:, cut], v[:, cut], logg[:, cut], state, norm,
            ids, layer, jnp.array([at == 0]))
        out.append(y)
        at += n
    return jnp.concatenate(out, axis=1)[0], state, norm


@pytest.mark.parametrize("splits", [
    [40], [9, 1, 1, 5, 24], [130, 128, 1, 1, 5, 35], [1] * 12],
    ids=["one-chunk", "chunk-steps-chunk", "three-chunks-and-steps",
         "steps"])
def test_both_forms_are_the_attention_form(splits):
    """``jax.numpy``, heads of 16 (136 monomials): whichever way the
    positions are cut into chunks (padded to 128) and steps, the sum is
    the reference's ``[T, T]`` matrix; 1e-4: float32 sums in another
    order (3e-6 seen)."""
    T = sum(splits)
    q, k, v, logg, state, norm = _inputs(T, 2, 2, 16)
    want = ref.power_attention(q[0], k[0], v[0], logg[0])
    got, state, norm = _through(q, k, v, logg, state, norm, splits)
    assert worst(got, want) < 1e-4
    # nothing but the row's page of its layer was written
    _, _, _, _, before, norm_before = _inputs(T, 2, 2, 16)
    assert worst(state[0], before[0]) == 0 == worst(state[1, :2],
                                                    before[1, :2])
    assert worst(norm[0], norm_before[0]) == 0
    assert worst(state[1, 2], before[1, 2]) > 0.1


def test_the_chunked_rule_is_the_recurrent_rule_across_a_boundary():
    """200 positions through the chunked rule (two chunks of 128, the
    second padded) and then steps, against the recurrent rule alone:
    outputs and the page's ``S`` and ``z``."""
    q, k, v, logg, state, norm = _inputs(204, 2, 2, 16, seed=4)
    a, sa, na = _through(q, k, v, logg, state, norm, [200, 1, 1, 2])
    b, sb, nb = _through(q, k, v, logg, state, norm, [8] * 25 + [4])
    assert worst(a, b) < 1e-4
    scale = float(jnp.max(jnp.abs(sb[1, 2])))
    assert worst(sa[1, 2], sb[1, 2]) < 1e-5 * max(scale, 1.0)
    assert worst(na[1, 2], nb[1, 2]) < 1e-5 * max(scale, 1.0)


@pytest.mark.parametrize("T,real", [(128, 70), (20, 1), (5, 3)])
def test_a_padded_tail_advances_nothing(T, real):
    """Positions that are not real (log g = 0, k = 0) leave the state
    where the real ones left it, in either form."""
    q, k, v, logg, state, norm = _inputs(T, 2, 2, 16, seed=2)
    live = (jnp.arange(T) < real)[None, :, None]
    k, logg = jnp.where(live[..., None], k, 0), jnp.where(live, logg, 0)
    _, s_pad, n_pad = _through(q, k, v, logg, state, norm, [T])
    cut = [x[:, :real] for x in (q, k, v, logg)]
    _, s_real, n_real = _through(*cut, state, norm, [real])
    assert worst(s_pad, s_real) < 1e-5 and worst(n_pad, n_real) < 1e-5


def _window_inputs(W, windows, Hkv, G, D, seed=5):
    """Three rows through ``windows`` windows of W steps, one layer's
    pool of five pages: row 0 from a page that 6 tokens built, with a
    position that is not real in mid-window; row 1 parked (nothing
    real: the trash page); row 2 ``fresh`` (its first position is 0)
    over a page that holds something. Gates drawn as the
    configuration's: ``b_g`` from U(2, 8), heads that forget in eight
    tokens and in three thousand."""
    T = W * windows
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (3, T, Hkv * G, D)) * D ** -0.5
    k = jax.random.normal(ks[1], (3, T, Hkv, D))
    v = jax.random.normal(ks[2], (3, T, Hkv, D))
    b_g = jax.random.uniform(ks[3], (Hkv,), minval=2.0, maxval=8.0)
    logg = jax.nn.log_sigmoid(
        jax.random.normal(ks[4], (3, T, Hkv)) + b_g)
    real = jnp.ones((3, T), bool).at[1].set(False).at[0, W // 2].set(False)
    k = jnp.where(real[..., None, None], k, 0)
    logg = jnp.where(real[..., None], logg, 0)
    F = retention.features(D)
    state = jax.random.normal(ks[5], (1, 5, Hkv, F, D))
    norm = jax.random.normal(ks[5], (1, 5, F * Hkv // D, D))
    warm = _inputs(6, Hkv, G, D, seed=7)
    was = pallas_paged._override
    pallas_paged.set_flash_enabled(False)
    _, state, norm = _through(*warm[:4], state, norm, [6], layer=0)
    pallas_paged.set_flash_enabled(was)
    return q, k, v, logg, state, norm, jnp.array([2, 0, 4], jnp.int32)


def _by_windows(q, k, v, logg, state, norm, ids, W):
    """Every W positions a window of ops/retention's window form: the
    steps read the pages, the fold writes them. -> (y [3, T, H, D], the
    pools after each fold)."""
    out, pools = [], []
    for first in range(0, q.shape[1], W):
        win = retention.open_window(
            1, W, k.shape[2], k.shape[3], ids,
            jnp.array([False, False, first == 0]))
        for t in range(first, first + W):
            at = slice(t, t + 1)
            y, *taken = retention.retain_in_window(
                q[:, at], k[:, at], v[:, at], logg[:, at], win.k[0],
                win.v[0], win.G[0], win, state, norm, 0)
            win = win._replace(
                **{n: a[None] for n, a in zip("kvG", taken)},
                step=win.step + 1)
            out.append(y)
        state, norm = retention.fold_window(win, state, norm)
        pools.append((state, norm))
    return jnp.concatenate(out, axis=1), pools


def _by_positions(q, k, v, logg, state, norm, ids, W):
    """The same through ``_recurrent_jnp`` a position at a time."""
    B, T, Hkv, D = k.shape
    out, pools = [], []
    for t in range(T):
        at = slice(t, t + 1)
        y, state, norm = retention._recurrent_jnp(
            q[:, at].reshape(B, 1, Hkv, -1, D), k[:, at], v[:, at],
            logg[:, at], state, norm, ids, 0,
            jnp.array([False, False, t == 0]))
        out.append(y.reshape(B, 1, -1, D))
        if (t + 1) % W == 0:
            pools.append((state, norm))
    return jnp.concatenate(out, axis=1), pools


def _same_window(got, want, ids):
    """Every step's y of the rows that are real, and their pages after
    each fold, to the kernel tests' tolerances (3e-6 and 3e-7 of the
    largest value seen: float32 sums in another order)."""
    (y, pools), (y_want, pools_want) = got, want
    for b in (0, 2):
        assert worst(y[b], y_want[b]) \
            < 1e-4 * float(jnp.max(jnp.abs(y_want[b])))
        for (s, n), (s_want, n_want) in zip(pools, pools_want):
            big = float(jnp.max(jnp.abs(s_want[0, ids[b]])))
            assert worst(s[0, ids[b]], s_want[0, ids[b]]) < 1e-5 * big
            assert worst(n[0, ids[b]], n_want[0, ids[b]]) < 1e-5 * big
    # pages nobody named stay as they were
    assert worst(pools[-1][0][0, (1, 3), ], pools_want[-1][0][0, (1, 3), ]) \
        == 0


@pytest.mark.parametrize("W", [4, 8])
def test_the_window_form_is_the_recurrent_form(W):
    """``jax.numpy``, heads of 16, two windows in a row: the steps of a
    window answer from the page as the window found it plus the
    window's own keys, and its fold leaves what W steps of the
    recurrent rule leave. The first token of the fresh row is a
    one-term quotient, which the window form computes as ``(q . k)^2``
    itself."""
    *x, ids = _window_inputs(W, 2, 2, 2, 16)
    got, want = _by_windows(*x, ids, W), _by_positions(*x, ids, W)
    _same_window(got, want, ids)
    q, k, v = (a[2, 0] for a in x[:3])
    qk2 = jnp.einsum("hgd,hd->hg", q.reshape(2, 2, 16), k) ** 2
    first = (qk2 / (qk2 + retention.EPS))[..., None] * v[:, None]
    assert worst(got[0][2, 0], first.reshape(4, 16)) \
        < 1e-6 * float(jnp.max(jnp.abs(first)))


def test_the_layout_packs_exactly_the_monomials():
    """``phi(a) . phi(b) = (a . b)^2``; a page unpacks and packs to
    itself; a head keeps F (D + 1) numbers and not one more."""
    a, b = jax.random.normal(jax.random.PRNGKey(0), (2, 16))
    pa, pb = retention.phi_rows(a), retention.phi_rows(b)
    assert float(jnp.sum(pa * pb)) == pytest.approx(float(a @ b) ** 2,
                                                    rel=1e-4)
    assert int(jnp.sum(pa != 0)) == retention.features(16) == 136
    S = jnp.arange(2 * 4 * 136 * 16, dtype=jnp.float32).reshape(2, 4, 136,
                                                                16)
    z = jnp.arange(2 * 34 * 16, dtype=jnp.float32).reshape(2, 34, 16)
    Su, zu = retention.unpack_state(S, z)
    assert Su.shape == (2, 4, 9, 16, 16) and zu.shape == (2, 4, 9, 16)
    S2, z2 = retention.pack_state(Su, zu)
    assert worst(S2, S) == 0 and worst(z2, z) == 0
    cache = kv_pool.cache_for(
        dataclasses.replace(CFG, num_heads=40, num_kv_heads=8,
                            head_dim=128, num_layers=10), 2, 0)
    assert cache.state_bytes_per_slot == 340807680
    assert cache.state.shape == (10, 2, 8, 8256, 128)
    assert cache.norm.shape == (10, 2, 516, 128)


@pytest.mark.parametrize("splits", [[3, 1, 2], [130, 1, 9], "window"],
                         ids=["steps", "chunks-and-a-step", "window"])
def test_the_kernels_are_the_jnp_forms(splits, kernels):
    """Interpret mode, at the kernels' own shapes (heads of 128, 8
    key-value heads, two queries a group): ``retention_recurrent_step``
    at 1, 2 and 3 positions a row from a carried page, and
    ``retention_chunk_scan`` over two chunks with a padded tail and a
    nine-position call; the window form's two (the step that only
    reads, under the recurrent step's name, and
    ``retention_window_fold``) over a window of 4 steps and three rows.
    About 15 s each: the interpreter walks 65 tiles of 128 x 128 a
    head."""
    assert retention.retention_path(1) == "retention_recurrent"
    assert retention.retention_path(9) == "retention_chunk"
    assert retention.retention_path(1, steps=4) == "retention_window"
    if splits == "window":
        *x, ids = _window_inputs(4, 1, 8, 2, 128)
        got = _by_windows(*x, ids, 4)
        pallas_paged.set_flash_enabled(False)
        _same_window(got, _by_windows(*x, ids, 4), ids)
        return
    T = sum(splits)
    q, k, v, logg, state, norm = _inputs(T, 8, 2, 128, seed=1)
    # a page that is NOT fresh: the kernels start from what it holds
    warm = _inputs(6, 8, 2, 128, seed=7)
    pallas_paged.set_flash_enabled(False)
    _, state, norm = _through(*warm[:4], state * 0, norm * 0, [6])

    def run(on):
        pallas_paged.set_flash_enabled(on)
        ids, out, at, s, n = jnp.array([2], jnp.int32), [], 0, state, norm
        for m in splits:
            cut = slice(at, at + m)
            y, s, n = retention.retain(
                q[:, cut], k[:, cut], v[:, cut], logg[:, cut], s, n, ids,
                1, jnp.array([False]))
            out.append(y)
            at += m
        return jnp.concatenate(out, axis=1), s, n
    want, s_want, n_want = run(False)
    got, s_got, n_got = run(True)
    assert worst(got, want) < 1e-4 * float(jnp.max(jnp.abs(want)))
    big = float(jnp.max(jnp.abs(s_want[1, 2])))
    assert worst(s_got[1, 2], s_want[1, 2]) < 1e-5 * big
    assert worst(n_got[1, 2], n_want[1, 2]) < 1e-5 * big
    assert worst(s_got[0], s_want[0]) == 0      # another layer's pages


def test_the_rule_by_shape_names_what_runs():
    """One rule by shape, asked before anything compiles; off the TPU,
    or at shapes that are not the kernels', the names end in _jnp,
    which ``correct``'s kernel clause reads."""
    assert retention.retention_path(1) == "retention_recurrent_jnp"
    assert retention.retention_path(8) == "retention_recurrent_jnp"
    assert retention.retention_path(9) == "retention_chunk_jnp"
    # a decode window of 4 steps and more reads the pages a step and
    # writes them once; shorter ones, and any T > 1, as before
    assert [retention.retention_path(1, steps=w) for w in (1, 2, 3, 4, 8)] \
        == ["retention_recurrent_jnp"] * 3 + ["retention_window_jnp"] * 2
    assert retention.retention_path(2, steps=8) == "retention_recurrent_jnp"
    assert retention.retention_path(9, steps=8) == "retention_chunk_jnp"
    assert [retention.pages_moved(w, retention.windowed(1, w))
            for w in (1, 2, 4, 8)] == [2, 4, 6, 10]
    pallas_paged.set_flash_enabled(True)
    try:
        assert retention.retention_path(1, steps=8) == "retention_window"
        assert retention.retention_path(1, steps=2) == "retention_recurrent"
        assert retention.retention_path(1, 16, 2, steps=8) \
            == "retention_window_jnp"
        assert retention.retention_path(256) == "retention_chunk"
        assert retention.retention_path(1, 16, 2) \
            == "retention_recurrent_jnp"
        assert retention.retention_path(64, 128, 4) == "retention_chunk_jnp"
    finally:
        pallas_paged.set_flash_enabled(None)


# ---------------------------------------------------------------------
# the model through its pages against the reference's full forward
# ---------------------------------------------------------------------

def params_with_live_norms(seed=3):
    """Seeded weights with every norm's weight off one, so that a norm
    left out or misplaced shows."""
    params = llama.init_params(CFG, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        w = params["layers"][name]
        params["layers"][name] = 1 + 0.2 * jax.random.normal(
            next(keys), w.shape)
    params["final_norm"] = 1 + 0.2 * jax.random.normal(
        next(keys), params["final_norm"].shape)
    return params


TOKENS = np.array(jax.random.randint(jax.random.PRNGKey(1), (200,), 0, 512))


@pytest.fixture(scope="module")
def served():
    """(params, the served log-probabilities [200, V]): the prompt in
    chunks of 130 (padded to 144) and 30 (32), three decode steps, a
    3-position and a 35-position call, in row 0 of 2; row 1 is parked
    (nothing real: the trash page)."""
    with jax.default_matmul_precision("highest"):
        params = params_with_live_norms()
        cache = kv_pool.cache_for(CFG, 3, 0)
        tables = jnp.array([[2], [0]], jnp.int32)
        got, at = [], 0
        for n in (130, 30, 1, 1, 3, 35):
            T = -(-n // 16) * 16 if n > 8 else n
            chunk = np.zeros((2, T), np.int32)
            chunk[0, :n] = TOKENS[at:at + n]
            valid = np.zeros((2, T), bool)
            valid[0, :n] = True
            pos = at + np.arange(T)[None, :] + np.zeros((2, 1), np.int32)
            logits, cache, work = llama.forward(
                params, CFG, jnp.asarray(chunk), jnp.asarray(pos), cache,
                block_tables=tables, token_valid=jnp.asarray(valid))
            assert work is None
            got.append(jax.nn.log_softmax(logits[0, :n], -1))
            at += n
        return params, jnp.concatenate(got), cache


def test_prefill_in_chunks_then_decode_is_the_reference_forward(served):
    params, got, cache = served
    want = ref.logprobs(params, HF, list(TOKENS))
    assert worst(got, want) < 1e-4
    assert cache.k is None and cache.layout == "state"
    assert cache.bytes_per_token == 0 and cache.num_blocks == 3
    assert cache.state_bytes_per_slot == CFG.state_bytes_per_seq \
        == 2 * 2 * 136 * 17 * 4
    # the trash page took the parked row's nothing; page 1 never named
    assert worst(cache.state[:, :2], 0 * cache.state[:, :2]) == 0
    jax.block_until_ready(cache.k)      # a pytree of no leaves


@pytest.mark.parametrize("change,least", [
    (dict(round_to="bfloat16"), 2e-3), (dict(ret_control="no_gate"), 0.05),
    (dict(ret_control="no_norm"), 0.3),
    (dict(ret_control="degree_one"), 0.3)])
def test_a_reference_that_departs_in_one_place_stands_apart(change, least,
                                                            served):
    """The tolerance sees a lower precision (20 times the 1e-4) and
    every part of the equations: the gate, the normaliser, the square."""
    params, got, _ = served
    other = ref.logprobs(params, {**HF, **change}, list(TOKENS))
    assert worst(got, other) > least


# ---------------------------------------------------------------------
# the engine: pages are what it allocates
# ---------------------------------------------------------------------

def _engine(**kw):
    cfg = dict(model="debug-brumby", max_num_seqs=4, max_model_len=256,
               prefill_chunk=64, dtype="float32", seed=3)
    return LLMEngine(EngineConfig(**{**cfg, **kw}))


def _run(eng, between=None, limit=400):
    for n in range(limit):
        if not eng.has_work:
            break
        eng.step()
        if between is not None:
            between(n)


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
            _run(eng)
            seq = eng.seqs[sid]
            out[i] = (list(seq.output_tokens), list(seq.output_logprobs))
    return out


def _same(eng, sid, want):
    """The tokens, and the log-probabilities to 2e-3: a batch of four
    rows sums in another order than a row alone, a page that leaked
    into another sequence moves them by tenths."""
    seq = eng.seqs[sid]
    n = len(want[0])
    assert list(seq.output_tokens)[:n] == want[0]
    assert np.allclose(seq.output_logprobs[:n], want[1], atol=2e-3)


def test_turnover_and_page_reuse_leave_every_request_as_alone(alone):
    """Six requests through four slots and four pages: two wait for a
    page, take a freed one (never cleared: the first chunk starts from
    zero inside the layer) and read as they read alone; the pages all
    come back; the pool is counted in pages."""
    eng = _engine()
    assert eng.runner.table_shape == (4, 1)
    assert eng.cfg.kv_len_buckets == (256,) and eng.cfg.kv_block_size == 256
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
    most = []
    _run(eng, lambda n: most.append(eng.block_mgr.active_blocks))
    for i, sid in enumerate(ids):
        _same(eng, sid, alone[i])
    assert max(most) == 4
    pool = eng.load_report()["kv_pool"]
    assert pool["num_blocks"] == 4 and pool["active"] == 0
    assert pool["state_pages"] == {"total": 4, "live": 0}
    assert pool["layout"] == "state" and pool["bytes_per_token"] == 0
    assert pool["state_bytes_per_slot"] == CFG.state_bytes_per_seq
    assert pool["alloc_failures_fragmented"] == 0
    assert eng.load_report()["state_pages_live"] == 0
    state = eng.eff.report()["state"]
    assert state["pages_alloc"] == state["pages_freed"] == 6
    assert state["scan_tokens"] == sum(map(len, PROMPTS))
    assert state["step_rows"] > 0 and state["steps"] > 0
    # a window moves pages of every row of its batch bucket: in and out
    # a step, or in a step and its fold's in and out
    assert state["step_bytes"] % (2 * CFG.state_bytes_per_seq) == 0
    assert (CFG.state_bytes_per_seq
            <= state["step_bytes"] / state["steps"]
            <= 2 * 4 * CFG.state_bytes_per_seq)
    assert state["scan_bytes"] > 0
    device = eng.device_report()
    assert device["attention_paths"] == {}
    paths = device["mixer_paths"]
    decode = {int(k.split("|")[1]): v for k, v in paths.items()
              if k.startswith("decode")}
    assert 8 in decode and decode == {
        w: "retention_window_jnp" if w >= 4 else "retention_recurrent_jnp"
        for w in decode}
    assert {v for k, v in paths.items() if k.startswith("prefill")} \
        == {"retention_chunk_jnp"}
    assert {k.split("|")[2] for k in paths} == {"256"}   # ONE kv bucket


def test_a_window_of_eight_serves_what_a_window_of_one_serves():
    """Float32, six requests through four slots and four pages (two
    take a freed page): with ``decode_window`` 8 the decode steps run
    the window form (W = 4 and 8), with 1 the recurrent rule a step;
    the same greedy ids and, to 1e-5, the same log-probabilities. The
    counter of state bytes says which form ran: a window moves ``W +
    2`` pages a row where it is the window form, ``2 W`` where not.
    (Two engines' warm-ups: about 20 s.)"""
    seen = {}
    for window in (8, 1):
        eng = _engine(decode_window=window)
        ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
        _run(eng)
        seen[window] = [(list(eng.seqs[s].output_tokens),
                         list(eng.seqs[s].output_logprobs)) for s in ids]
        windows = eng.eff.recent_windows(1000)
        steps = {e["steps"] for e in windows}
        assert steps == {1} if window == 1 else 8 in steps
        pages = sum((e["steps"] + 2 if e["steps"] >= 4 else 2 * e["steps"])
                    * e["batch"] for e in windows)
        state = eng.eff.report()["state"]
        assert state["step_bytes"] == pages * CFG.state_bytes_per_seq
        assert state["pages_alloc"] == state["pages_freed"] == 6
    for (ids8, lps8), (ids1, lps1) in zip(seen[8], seen[1]):
        assert ids8 == ids1 and len(ids8) == 12
        assert np.allclose(lps8, lps1, atol=1e-5)


def test_a_slot_move_a_preemption_and_an_abort_change_nothing(alone):
    """Mid-run: the request in the lowest slot is aborted (its page
    goes back), a running one is preempted (its page goes back; it
    recomputes from position 0 into whatever page it is handed next),
    and compaction moves rows to lower slots (a table row rewritten, no
    state copied). The survivors read as they read alone."""
    eng = _engine()
    longer = SamplingOptions(max_tokens=50, temperature=0.0,
                             ignore_eos=True)
    order = [3, 0, 2, 1]
    ids = [eng.add_request(PROMPTS[i], longer) for i in order]
    did = {}

    def between(n):
        running = sorted(eng.scheduler.running.values(),
                         key=lambda s: s.slot)
        if "abort" in did or len(running) < 4 or not all(
                s.output_tokens for s in running):
            return
        while eng._inflight:
            eng._retire_window("decode")
        running = sorted(eng.scheduler.running.values(),
                         key=lambda s: s.slot)
        if len(running) < 3:
            return
        did["abort"] = running[0].seq_id
        eng.abort(running[0].seq_id)
        victim = running[-1]
        did["page"] = victim.block_ids[0]
        with eng._lock:
            eng._preempt(victim)
        assert victim.block_ids == [] and did["page"] > 0
        before = {s.seq_id: s.slot for s in running[1:-1]}
        with eng._lock:
            eng._compact_slots()
        did["moved"] = [s.seq_id for s in running[1:-1]
                        if s.slot != before[s.seq_id]]
        for s in running[1:-1]:     # the page rides the table row
            assert eng._tables[s.slot, 0] == s.block_ids[0] > 0

    _run(eng, between)
    assert did.get("moved"), did
    for i, sid in zip(order, ids):
        if sid != did["abort"]:
            _same(eng, sid, alone[i])
    assert eng.block_mgr.live_pages == 0


def test_admission_counts_pages():
    """A pool of pages: a sequence is admitted with its ONE page or
    waits, at any length of prompt; the report counts pages."""
    mgr = BlockManager(3, 256, layout="state", state_pages=3,
                       state_bytes_per_slot=7)
    assert mgr.keeps_pages and mgr.state_pages == 0
    assert mgr.blocks_for(1) == mgr.blocks_for(256) == 1
    a, b = mgr.alloc(1), mgr.alloc(1)
    assert sorted(a + b) == [1, 2] and mgr.alloc(1) is None
    report = mgr.frag_report()
    assert report["num_blocks"] == 2 and report["active"] == 2
    assert report["state_pages"] == {"total": 2, "live": 2}
    assert report["alloc_failures_exhausted"] == 1
    assert mgr.alloc_page() is None     # no second pool beside it
    mgr.free(a)
    assert mgr.alloc(1) == a
    assert mgr.page_counts() == {"pages_alloc": 3, "pages_freed": 1,
                                 "alloc_failures": 1}
    eng = _engine(max_num_seqs=2)
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS[:3]]
    seen = []
    _run(eng, lambda n: seen.append((eng.block_mgr.active_blocks,
                                     len(eng.scheduler.waiting))))
    assert max(a for a, _ in seen) == 2 and (2, 1) in seen
    assert all(eng.seqs[s].output_tokens for s in ids)


# ---------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------

@pytest.mark.parametrize("kw,names", [
    (dict(enable_prefix_caching=True), "prefix caching"),
    (dict(kv_transfer_config={"kv_role": "kv_both"}), "KV connector"),
    (dict(speculative_ngram_tokens=3), "n-gram speculation"),
    (dict(checkpoint="/nowhere"), "checkpoint loader"),
    (dict(lora_adapters={"a": "random:1"}), "LoRA"),
    (dict(kv_dtype="int8"), "int8 cache")])
def test_what_state_pages_cannot_run_with_is_refused_by_name(kw, names):
    with pytest.raises(ValueError) as err:
        ModelRunner(get_config("debug-brumby"), EngineConfig(
            model="debug-brumby", max_num_seqs=2, max_model_len=128, **kw))
    assert names in str(err.value)
    assert "state pages (KV pool layout 'state')" in str(err.value)


def test_a_mesh_and_a_forward_without_pages_are_refused_by_name():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh.*state pages"):
        ModelRunner(get_config("debug-brumby"), EngineConfig(
            model="debug-brumby", max_num_seqs=2, max_model_len=128),
            mesh=mesh)
    with pytest.raises(ValueError, match="state pages"):
        llama.encode(params_with_live_norms(), CFG,
                     jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="beside another kind"):
        kv_pool.cache_for(dataclasses.replace(
            CFG, layer_pattern=("ret", "attn")), 3, 16)


# ---------------------------------------------------------------------
# the mapping of the published keys
# ---------------------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "brumby-14b-int8-l10.json")) as f:
        return json.load(f)


def test_the_mapping_reads_the_published_keys():
    conf = _published()
    cfg = ModelConfig.from_hf_config(conf, name="b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.vocab_size) == (10, 5120, 17408, 40, 8, 128, 151936)
    assert cfg.layer_pattern == ("ret",) and cfg.qk_norm
    assert (cfg.attn_layers, cfg.gdn_layers, cfg.ret_layers,
            cfg.state_layers) == (0, 0, 10, 10)
    assert cfg.rope_theta == 1e6 and cfg.max_position_embeddings == 32768
    assert cfg.ret_features == 8256
    assert cfg.state_bytes_per_seq == 340807680
    whole = ModelConfig.from_hf_config(
        {**conf, "num_hidden_layers": conf["published"]["num_hidden_layers"]})
    assert abs(whole.num_params / 14.77e9 - 1) < 0.002
    assert ModelConfig.from_hf_config(
        {k: v for k, v in conf.items() if k != "model_type"}
        | {"architectures": ["BrumbyForCausalLM"]}).ret_layers == 10


@pytest.mark.parametrize("change,names", [
    (dict(attention_bias=True), "attention_bias"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"rope_type": "linear", "factor": 2}),
     "rope_scaling"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_key_value_heads=5), "key-value heads")])
def test_the_mapping_refuses_what_the_tree_does_not_build(change, names):
    with pytest.raises(ValueError, match=names):
        ModelConfig.from_hf_config({**_published(), **change})
