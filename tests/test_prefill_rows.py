"""The prefill dispatch's row bucket: a row is not a slot.

``runner.prefill`` runs as many rows as it is given and gathers what
is kept per slot (block tables, sampling, guided and penalty state) by
``slots``; ``engine._do_prefill`` gives it one row a chunk, or the
whole batch for a burst. Held here, on the CPU at debug sizes: a chunk prefilled at
rows 1, 2 and 4 from arbitrary slots comes out as the
``max_num_seqs``-row dispatch (row = slot) leaves it — first token,
its log-probability, the top-k alternatives and every KV block the
slot's table references — for every kind of per-slot state a wrong
gather would mix up; and the engine picks the rows and counts them.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampler import SamplingParams
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import config as model_configs
from production_stack_tpu.models.kv import KVCache

B, S, BS, CHUNK, TOPK = 8, 128, 16, 32, 4
MB = S // BS
# the slots the chunks live in, in dispatch-row order: not ascending,
# not starting at 0, so that row != slot in every small dispatch
SLOTS = (5, 2, 7, 0)

# 8 experts, top-2, factor 2: the full dispatch (8 x 32 tokens) takes
# the capacity path at 128 per expert, which one chunk cannot fill
model_configs.PRESETS.setdefault("debug-moe-e8", dataclasses.replace(
    model_configs.PRESETS["debug-moe"], name="debug-moe-e8",
    num_experts=8))

CASES = {
    "dense": dict(model="debug-tiny"),
    "moe": dict(model="debug-moe-e8"),
    "int8_kv": dict(model="debug-tiny", kv_dtype="int8"),
    "start_gt_0": dict(model="debug-tiny"),
    "guided": dict(model="debug-tiny"),
    "penalized": dict(model="debug-tiny"),
    "seeded": dict(model="debug-tiny"),
    "lora": dict(model="debug-tiny",
                 lora_adapters={"a": "random:11", "b": "random:12"}),
}


def _tables() -> np.ndarray:
    """Slot s owns blocks 1 + s*MB ..; block 0 is the trash block."""
    return 1 + np.arange(B * MB, dtype=np.int32).reshape(B, MB)


def _snapshot(cache: KVCache):
    return [None if a is None else np.asarray(a) for a in cache]


def _restore(runner, snap) -> None:
    runner.cache = KVCache(*[None if a is None else jnp.asarray(a)
                             for a in snap])


class _Case:
    """One runner, its per-slot state, and the cache every dispatch of
    the case starts from."""

    def __init__(self, name: str):
        self.name = name
        self.runner = LLMEngine(EngineConfig(
            max_model_len=S, max_num_seqs=B, prefill_chunk=CHUNK,
            prefill_buckets=(CHUNK,), kv_block_size=BS,
            **CASES[name])).runner
        self.runner.set_block_tables(_tables())
        rng = np.random.default_rng(7)
        V = self.runner.model_cfg.vocab_size
        self.chunks = {s: rng.integers(1, V, size=n).astype(np.int32)
                       for s, n in zip(SLOTS, (32, 17, 25, 9))}
        self.start = 0
        self.kw = {}
        sp = SamplingParams.filled(B, temperature=0.0)
        per_slot = np.arange(B)
        if name == "seeded":
            # sampled, each slot from a seed of its own: (seed,
            # position) alone decides the draw
            sp = sp._replace(
                temperature=jnp.full((B,), 1.0, jnp.float32),
                seed=jnp.asarray(1000 + per_slot, jnp.int32))
        if name == "lora":
            sp = sp._replace(adapter=jnp.asarray(per_slot % 3, jnp.int32))
        if name == "penalized":
            # a bias that decides the token, on a token of the slot's
            # own; counts and prompt membership that differ by slot
            sp = sp._replace(
                bias_ids=sp.bias_ids.at[:, 0].set(100 + per_slot),
                bias_vals=sp.bias_vals.at[:, 0].set(
                    jnp.where(per_slot % 2 == 0, 60.0, 0.0)),
                repetition=jnp.asarray(1.0 + 0.3 * per_slot, jnp.float32),
                frequency=jnp.asarray(0.2 * per_slot, jnp.float32),
                prompt_len=jnp.asarray(3 + per_slot, jnp.int32))
            self.runner.set_penalty_state(
                rng.integers(0, 3, size=(B, V)).astype(np.int32),
                rng.random((B, V)) < 0.3)
            self.kw["penalized"] = True
        if name == "guided":
            # grammar g (1 or 2) in state s allows only tokens v with
            # v % 7 == g + s: the mask differs by slot through both
            G, NS = 3, 4
            v = np.arange(V)
            table = np.full((G, NS, V), -1, np.int32)
            for g in (1, 2):
                for st in range(NS):
                    table[g, st, v % 7 == g + st] = (st + 1) % NS
            table[0] = 0
            self.kw.update(
                guide_table=jnp.asarray(table),
                guide_ids=(per_slot % 3).astype(np.int32),
                guide_states=(per_slot % NS).astype(np.int32))
        self.sampling = sp
        if name == "start_gt_0":
            # the chunk under test is each prompt's SECOND: the first
            # (a full bucket) is written by the full dispatch and is
            # part of what every dispatch then starts from
            first = {s: rng.integers(1, V, size=CHUNK).astype(np.int32)
                     for s in SLOTS}
            self._full(first, 0)
            self.start = CHUNK
        self.snap = _snapshot(self.runner.cache)
        self.ref = self.dispatch(B)

    def _call(self, tokens, starts, lengths, slots):
        ids, lps, tops, _ = self.runner.prefill(
            tokens, starts, lengths, self.sampling,
            self.runner.engine_cfg.kv_bucket_for(S), topk=TOPK,
            slots=slots, **self.kw)
        return (np.asarray(ids), np.asarray(lps), np.asarray(tops[0]),
                np.asarray(tops[1]))

    def _full(self, chunks, start):
        """The full-batch dispatch: row = slot, ``slots`` defaulted."""
        tokens = np.zeros((B, CHUNK), np.int32)
        starts = np.full((B,), S, np.int32)
        lengths = np.ones((B,), np.int32)
        for s, c in chunks.items():
            tokens[s, :len(c)] = c
            starts[s], lengths[s] = start, len(c)
        return self._call(tokens, starts, lengths, None)

    def dispatch(self, rows: int) -> dict:
        """Prefill the case's chunks ``rows`` at a time from the
        snapshot; what came back per slot, and the blocks each slot's
        table references afterwards."""
        _restore(self.runner, self.snap)
        if rows == B:
            res = self._full(self.chunks, self.start)
            out = {s: [a[s] for a in res] for s in SLOTS}
        else:
            out = {}
            for i in range(0, len(SLOTS), rows):
                group = SLOTS[i:i + rows]
                tokens = np.zeros((rows, CHUNK), np.int32)
                starts = np.full((rows,), S, np.int32)
                lengths = np.ones((rows,), np.int32)
                slots = np.zeros((rows,), np.int32)
                for r, s in enumerate(group):
                    c = self.chunks[s]
                    tokens[r, :len(c)] = c
                    starts[r], lengths[r], slots[r] = (
                        self.start, len(c), s)
                res = self._call(tokens, starts, lengths, slots)
                for r, s in enumerate(group):
                    out[s] = [a[r] for a in res]
        pool = _snapshot(self.runner.cache)
        tables = _tables()
        return {"rows": out,
                "blocks": {s: [a[:, tables[s]] for a in pool
                               if a is not None] for s in SLOTS}}


@pytest.fixture(scope="module")
def cases():
    made = {}

    def get(name):
        if name not in made:
            made[name] = _Case(name)
        return made[name]
    return get


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("name", list(CASES))
def test_prefill_rows_match_the_full_dispatch(cases, name, rows):
    case = cases(name)
    got, ref = case.dispatch(rows), case.ref
    for s in SLOTS:
        ids, lp, top_ids, top_lps = got["rows"][s]
        r_ids, r_lp, r_top_ids, r_top_lps = ref["rows"][s]
        assert ids == r_ids, (name, rows, s)
        # bf16 activations: the same sums in another matmul shape
        np.testing.assert_allclose(lp, r_lp, atol=2e-2)
        np.testing.assert_allclose(top_lps, r_top_lps, atol=2e-2)
        assert set(top_ids[:2]) == set(r_top_ids[:2]), (name, rows, s)
        for a, b in zip(got["blocks"][s], ref["blocks"][s]):
            if a.dtype == np.int8:
                assert np.abs(a.astype(np.int32) - b).max() <= 1
            else:
                np.testing.assert_allclose(
                    a.astype(np.float32), b.astype(np.float32),
                    atol=3e-2)
    if name == "penalized":
        # the bias decided: even slots sampled their own biased token
        assert [int(got["rows"][s][0]) for s in SLOTS if s % 2 == 0] \
            == [100 + s for s in SLOTS if s % 2 == 0]
    if name == "guided":
        for s in SLOTS:
            if s % 3:
                assert int(got["rows"][s][0]) % 7 == s % 3 + s % 4


def test_a_wrong_gather_would_show(cases):
    """The cases have teeth: served from the wrong slot's state, the
    penalized case's row samples another token."""
    case = cases("penalized")
    _restore(case.runner, case.snap)
    c = case.chunks[2]
    tokens = np.zeros((1, CHUNK), np.int32)
    tokens[0, :len(c)] = c
    ids, *_ = case._call(tokens, np.zeros((1,), np.int32),
                         np.array([len(c)], np.int32),
                         np.array([4], np.int32))
    assert int(ids[0]) == 104 != int(case.ref["rows"][2][0])


def test_spare_rows_write_nowhere_a_table_points(cases):
    """A dispatch of 4 rows with one chunk: the three spare rows name
    slot 0 and are parked, and slot 0's blocks stay as they were."""
    case = cases("dense")
    _restore(case.runner, case.snap)
    c = case.chunks[5]
    tokens = np.zeros((4, CHUNK), np.int32)
    tokens[0, :len(c)] = c
    starts = np.array([0, S, S, S], np.int32)
    case._call(tokens, starts, np.array([len(c), 1, 1, 1], np.int32),
               np.array([5, 0, 0, 0], np.int32))
    pool = _snapshot(case.runner.cache)
    t = _tables()
    for a, before in zip(pool, case.snap):
        if a is not None:
            np.testing.assert_array_equal(a[:, t[0]], before[:, t[0]])
    np.testing.assert_allclose(
        pool[0][:, t[5]].astype(np.float32),
        case.ref["blocks"][5][0].astype(np.float32), atol=3e-2)


# ------------------------------------------------------------ the engine

@pytest.fixture(scope="module")
def engine():
    return LLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=S, max_num_seqs=B,
        prefill_chunk=CHUNK, prefill_buckets=(16, CHUNK)))


def _spy(engine):
    calls, inner = [], engine.runner.prefill

    def spy(tokens, starts, lengths, *a, slots=None, **kw):
        if slots is not None:       # not the runner's own parked call
            calls.append((tokens.shape, list(slots), list(starts)))
        return inner(tokens, starts, lengths, *a, slots=slots, **kw)
    engine.runner.prefill = spy
    return calls, lambda: setattr(engine.runner, "prefill", inner)


def _finish(engine, ids):
    done, steps = set(), 0
    while len(done) < len(ids):
        done |= {o.seq_id for o in engine.step() if o.finished}
        steps += 1
        assert steps < 500
    return done


@pytest.mark.parametrize("due,rows", [(1, 1), (2, 1), (3, 8), (5, 8)])
def test_do_prefill_runs_one_row_a_chunk_or_the_whole_batch(
        engine, due, rows):
    """Up to a quarter of the batch (2 of 8) the chunks due run a
    one-row dispatch each; more, one dispatch of all 8 rows: what
    cfg.prefill_rows_for says, counted by /debug/perf totals.prefill
    on the rows dispatched."""
    before = engine.eff.report()["prefill"]
    calls, undo = _spy(engine)
    try:
        opts = SamplingOptions(temperature=0.0, max_tokens=2)
        ids = [engine.add_request(list(range(1, 21 + i)), opts)
               for i in range(due)]
        _finish(engine, ids)
    finally:
        undo()
    assert engine.cfg.prefill_rows_for(due) == rows
    # prompts of 20-24 tokens: one chunk each, all in the 32 bucket,
    # admitted in one step
    n = due if rows == 1 else 1
    assert [c[0] for c in calls] == [(rows, CHUNK)] * n
    served = [s for _, slots, starts in calls
              for s, st in zip(slots, starts) if st == 0]
    assert len(served) == len(set(served)) == due
    assert sum(st == S for _, _, starts in calls
               for st in starts) == n * rows - due
    after = engine.eff.report()["prefill"]
    real = sum(20 + i for i in range(due))
    assert after["real"] - before["real"] == real
    assert after["pad"] - before["pad"] == n * rows * CHUNK - real
    assert after["dispatches"] - before["dispatches"] == n
    assert (after["by_rows"].get(str(rows), 0)
            - before["by_rows"].get(str(rows), 0)) == n
    assert sum(after["by_rows"].values()) == after["dispatches"]


@pytest.mark.parametrize("seqs,expect", [
    (1, {1: 1, 2: 1}), (2, {1: 1, 2: 2}), (4, {1: 1, 2: 4, 4: 4}),
    (8, {1: 1, 2: 1, 3: 8, 8: 8}), (16, {1: 1, 4: 1, 5: 16, 16: 16})])
def test_prefill_rows_for(seqs, expect):
    cfg = EngineConfig(model="debug-tiny", max_model_len=S,
                       max_num_seqs=seqs)
    assert {n: cfg.prefill_rows_for(n) for n in expect} == expect


def test_a_shape_built_at_many_rows_is_built_at_one_row_too(cases):
    """Whoever warms a prefill shape at max_num_seqs rows (warmup(),
    a benchmark's launcher) has warmed the one-row executable serving
    runs in the steady case: nothing compiles at the first chunk."""
    runner = LLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=S, max_num_seqs=4,
        prefill_chunk=CHUNK, prefill_buckets=(16, CHUNK))).runner
    runner.prefill(np.zeros((4, 16), np.int32), np.full((4,), S, np.int32),
                   np.ones((4,), np.int32), SamplingParams.filled(4), S)
    assert sorted(k[:2] for k in runner._prefill_fns) == [(1, 16), (4, 16)]
    assert sorted(runner.attention_paths) == [
        f"prefill|16|{S}|1", f"prefill|16|{S}|4"]
    # and one row alone builds only itself
    runner.prefill(np.zeros((1, CHUNK), np.int32),
                   np.full((1,), S, np.int32), np.ones((1,), np.int32),
                   SamplingParams.filled(4), S)
    assert sorted(k[:2] for k in runner._prefill_fns) == [
        (1, 16), (1, CHUNK), (4, 16)]


def test_first_tokens_are_the_same_alone_and_in_a_burst(engine):
    """Through the whole engine: a prompt's greedy tokens and top
    alternatives do not depend on how many rows its prefill ran at."""
    opts = SamplingOptions(temperature=0.0, max_tokens=4, logprobs=True,
                           top_logprobs=2)
    prompts = [list(range(3 + i, 25 + 2 * i)) for i in range(5)]

    def run(batch):
        ids = [engine.add_request(p, opts) for p in batch]
        _finish(engine, ids)
        return [engine.seqs[i] for i in ids]

    alone = [run([p])[0] for p in prompts]
    burst = run(prompts)
    for a, b in zip(alone, burst):
        assert a.output_tokens == b.output_tokens
        assert ([t for t, _ in a.output_top[0]]
                == [t for t, _ in b.output_top[0]])
