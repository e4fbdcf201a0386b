"""The small pieces that let the stack be brought up on a chip and say
what it runs on: where the compile cache goes, which HBM peak an MBU is
quoted against, the strict per-executable compile, one chip-owning
child at a time, and chip_smoke.py's verdict on a device block. (The
``device`` block of GET /debug/perf itself is in test_engine_server.py;
what the TPU compiler accepts is in test_chip_compile.py.)"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from production_stack_tpu.utils import (compile_cache_dir,
                                        place_compile_cache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


def _python(code: str, **env) -> str:
    """Run ``code`` in a fresh interpreter from the repo root."""
    full = {k: v for k, v in os.environ.items() if k != CACHE_VAR}
    full.update(env)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ---------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------

def test_cache_dir_set_variable_sets_nothing_in_code(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_VAR, str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    assert place_compile_cache() == str(tmp_path)
    # JAX read the variable (or not) at import; the helper left it alone
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(CACHE_VAR, raising=False)
    here = compile_cache_dir()
    assert here == os.path.join(REPO, ".jax_cache") == compile_cache_dir()
    # another pid resolves the same directory, twice, and configures it
    there = _python(
        "import jax\n"
        "from production_stack_tpu.utils import place_compile_cache\n"
        "print(place_compile_cache())\n"
        "print(place_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n").split()
    assert there == [here, here, here]


def test_launchers_and_smoke_parent_stay_off_jax():
    """A parent that has touched JAX holds the chip and its engine
    child then fails or hangs: none of the launchers may import it."""
    _python(
        "import sys\n"
        "import chip_smoke\n"
        "import production_stack_tpu.loadgen.__main__\n"
        "import production_stack_tpu.loadgen.orchestrator\n"
        "import production_stack_tpu.autoscaler.actuator\n"
        "import production_stack_tpu.router.app\n"
        "assert 'jax' not in sys.modules, 'a launcher imported jax'\n")


# ---------------------------------------------------------------------
# HBM peak by device kind
# ---------------------------------------------------------------------

def test_hbm_peak_lookup_by_device_kind():
    from production_stack_tpu.engine.efficiency import HBM_PEAK_GBPS
    assert HBM_PEAK_GBPS.get("TPU v5 lite") == 819.0
    assert HBM_PEAK_GBPS.get("cpu") is None
    assert HBM_PEAK_GBPS.get("TPU v99") is None


def test_unknown_peak_reports_no_mbu():
    from production_stack_tpu.engine.efficiency import EngineEffAccounting
    from production_stack_tpu.engine.metrics import EngineMetrics
    acct = EngineEffAccounting(weight_bytes=1000, kv_position_bytes=10,
                               hbm_peak_bytes_per_s=None)
    acct.note_window(steps=4, positions=1, batch=2, live_rows=2,
                     kv_len=64, real=8, pad=0, dead=0, window_s=0.01)
    rates = acct.rates()
    assert rates["mbu_perc"] is None          # not 0, not x / 819
    assert rates["effective_bytes_per_s"] > 0
    assert acct.perf_block()["mbu_perc"] is None
    metrics = EngineMetrics("m")
    metrics.sync_eff(acct.report(), rates)
    assert math.isnan(metrics.mbu_perc._value.get())


def test_engine_looks_its_peak_up_and_cpu_has_none():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    kw = dict(model="debug-tiny", max_model_len=64, max_num_seqs=2,
              prefill_chunk=16)
    assert EngineConfig(**kw).hbm_peak_gbps is None
    assert LLMEngine(EngineConfig(**kw)).eff.hbm_peak_bytes_per_s is None
    # the explicit override keeps working
    eng = LLMEngine(EngineConfig(hbm_peak_gbps=100.0, **kw))
    assert eng.eff.hbm_peak_bytes_per_s == 100e9
    with pytest.raises(ValueError):
        EngineConfig(hbm_peak_gbps=0.0, **kw)


# ---------------------------------------------------------------------
# weights built and quantized leaf by leaf
# ---------------------------------------------------------------------

def test_fused_init_keeps_the_values_of_the_unfused_recipe():
    """Every seeded parity test was written against ``normal(key) *
    0.02`` computed op by op; fused into one executable XLA folds the
    0.02 into the draw and a quarter of the values move by a float32
    step, unless the leaf builder stops it."""
    import jax.numpy as jnp
    from production_stack_tpu.models import llama
    from production_stack_tpu.models.config import get_config
    cfg, key = get_config("debug-tiny"), jax.random.PRNGKey(0)
    k_embed = jax.random.split(key, 16)[0]
    want = (jax.random.normal(
        k_embed, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        * 0.02).astype(cfg.dtype)
    got = llama.init_params(cfg, key)["embed"]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("model", ["debug-tiny", "debug-moe"])
def test_leafwise_int8_init_matches_quantizing_the_tree(model):
    from production_stack_tpu.models import llama, quant
    from production_stack_tpu.models.config import get_config
    cfg, key = get_config(model), jax.random.PRNGKey(3)
    tree = quant.quantize_params(llama.init_params(cfg, key))
    leafwise = llama.init_params(cfg, key, quantization="int8")
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(leafwise))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(leafwise)):
        assert a.dtype == b.dtype and a.shape == b.shape
        is_int8 = a.dtype == np.int8
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if is_int8:
            # the fused executable divides once where the tree path
            # divides twice: a scale's last bit, and with it the odd
            # int8 value on a rounding boundary, by one step
            off = np.abs(a - b)
            assert off.max() <= 1 and (off > 0).mean() < 1e-3
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6)


# ---------------------------------------------------------------------
# no recompile on another attention path
# ---------------------------------------------------------------------

def test_refused_compile_raises_naming_the_executable():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.models.config import get_config
    runner = ModelRunner(get_config("debug-tiny"), EngineConfig(
        model="debug-tiny", max_model_len=64, max_num_seqs=2,
        prefill_chunk=16))
    built, cache, key = [], {}, (16, 64, False, None, False, 0)

    def make_fn():
        built.append(1)
        raise ValueError("Mosaic says no")

    with pytest.raises(RuntimeError) as err:
        runner._compile(cache, key, make_fn, (), kind="prefill",
                        window=16, kv_len=64, batch=2, positions=16)
    msg = str(err.value)
    assert "prefill executable" in msg and repr(key) in msg
    assert "jnp_gather attention path" in msg and "Mosaic says no" in msg
    # built once: nothing was retried on another path, nothing cached
    assert built == [1] and not cache and not runner.attention_paths


@pytest.mark.parametrize("T,gate,mesh_dims,want", [
    (1, True, None, "pallas_paged_decode"),
    (8, True, None, "pallas_paged_decode"),       # T = DECODE_T_MAX
    (9, True, None, "pallas_paged"),              # DECODE_T_MAX + 1
    (512, True, None, "pallas_paged"),
    (512, False, None, "jnp_gather"),             # the gate off
    # a chunk whose whole q panel misses VMEM stays on the kernel, cut
    # into q blocks (paged_viable asks of the smallest)
    (1 << 16, True, None, "pallas_paged"),
    (1, True, dict(dp=1, tp=2), "pallas_paged_decode_sharded"),
    (1, True, dict(dp=2, tp=2), "jnp_gather"),    # the pool's blocks
    #                                               sharded: the dp cliff
], ids=["decode", "decode_t_max", "past_decode_t_max", "prefill_chunk",
        "gate_off", "cut_into_q_blocks", "tp_only_mesh", "dp_mesh"])
def test_attention_path_is_chosen_by_shape_and_recorded(
        monkeypatch, T, gate, mesh_dims, want):
    from production_stack_tpu.models.config import get_config
    from production_stack_tpu.ops import pallas_paged
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    assert pallas_paged.DECODE_T_MAX == 8
    monkeypatch.setattr(pallas_paged, "_override", gate)
    mesh = mesh_dims and build_mesh(
        MeshConfig(**mesh_dims),
        jax.devices()[:mesh_dims["dp"] * mesh_dims["tp"]])
    cfg = get_config("mistral-7b")
    assert pallas_paged.attention_path(
        T, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_, 64,
        mesh) == want


# ---------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------

class _FakePopen:
    pid, returncode = 4242, None

    def poll(self):
        return self.returncode


def test_second_chip_owning_engine_child_is_refused(monkeypatch, tmp_path):
    from production_stack_tpu.loadgen import orchestrator as orch
    spawned = []

    def fake_spawn(name, cmd, url, log_dir, env=None):
        spawned.append((cmd, env))
        return orch.Proc(name, _FakePopen(), url, "")

    monkeypatch.setattr(orch, "_spawn", fake_spawn)
    monkeypatch.setattr(orch, "_chip_owners", [])
    first = orch.launch_engine("debug-tiny", 1, log_dir=str(tmp_path),
                               platform="", geometry=["--no-warmup"],
                               env={"PSTPU_FLASH": "0"})
    cmd, env = spawned[0]
    assert "JAX_PLATFORMS" not in env and env["PSTPU_FLASH"] == "0"
    assert cmd[-1] == "--no-warmup" and "--kv-len-buckets" not in cmd
    with pytest.raises(RuntimeError, match="already owns"):
        orch.launch_engine("debug-tiny", 2, log_dir=str(tmp_path),
                           platform="tpu")
    # CPU children never hold a chip, and a dead owner frees it
    orch.launch_engine("debug-tiny", 3, log_dir=str(tmp_path))
    first.popen.returncode = 0
    orch.launch_engine("debug-tiny", 4, log_dir=str(tmp_path),
                       platform="tpu")
    assert len(spawned) == 3


# ---------------------------------------------------------------------
# chip_smoke.py's verdict
# ---------------------------------------------------------------------

def _device(platform="tpu", count=1, mode="compiled", paths=None):
    return {"platform": platform, "device_kind": "TPU v5 lite",
            "count": count, "pallas_attention": mode,
            "engine_devices": [], "attention_paths": paths if paths
            is not None else {"decode|8|512|8": "pallas_paged_decode",
                              "prefill|512|512|8": "pallas_paged"}}


def test_smoke_device_check_fails_on_cpu(capsys):
    import chip_smoke
    cpu = _device(platform="cpu", mode="off",
                  paths={"decode|8|512|8": "jnp_gather"})
    cpu["device_kind"] = "cpu"
    problems = chip_smoke.device_problems(cpu, chips=1,
                                          paths=chip_smoke.KERNEL)
    assert any("platform is 'cpu'" in p for p in problems)
    assert chip_smoke.report(not problems, cpu) == 1       # exit code
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_smoke_device_check_accepts_only_the_expected_paths(capsys):
    import chip_smoke
    ok = _device()
    assert chip_smoke.device_problems(ok, chips=1,
                                      paths=chip_smoke.KERNEL) == []
    assert chip_smoke.report(True, ok) == 0
    assert capsys.readouterr().out.strip() == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}')
    # one executable quietly on the jax.numpy path
    hidden = _device(paths={"decode|8|512|8": "pallas_paged_decode",
                            "prefill|512|512|8": "jnp_gather"})
    assert any("prefill|512|512|8" in p for p in chip_smoke.
               device_problems(hidden, chips=1, paths=chip_smoke.KERNEL))
    # interpreted kernels, a wrong count, nothing compiled
    for bad in (_device(mode="interpret"), _device(count=4),
                _device(paths={})):
        assert chip_smoke.device_problems(bad, chips=1,
                                          paths=chip_smoke.KERNEL)
    # the reference child: kernels off, every executable jnp_gather
    ref = _device(mode="off", paths={"prefill|32|512|8": "jnp_gather"})
    assert chip_smoke.device_problems(ref, chips=1,
                                      paths=chip_smoke.JNP) == []
    assert chip_smoke.device_problems(ok, chips=1, paths=chip_smoke.JNP)


def test_smoke_shard_check_wants_a_share_on_every_device():
    import chip_smoke
    gib = 1 << 30

    def dev(*used):
        return {"engine_devices": [{"bytes_in_use": u} for u in used]}

    even = dev(2 * gib, 2 * gib, 2 * gib, 2 * gib)
    assert chip_smoke.shard_problems(even, 7 * gib) == []
    first_only = dev(9 * gib, 0, 0, 0)
    assert len(chip_smoke.shard_problems(first_only, 7 * gib)) == 4
    assert chip_smoke.shard_problems(dev(None, None), 7 * gib)


def test_smoke_compares_logprobs_not_tokens():
    import chip_smoke
    a = [{"prompt_tokens": 9,
          "top": {(i,): -1.0 - 0.1 * i for i in range(20)}}]
    near = [{"prompt_tokens": 9,
             "top": {(i,): -1.0 - 0.1 * i + 0.01 for i in range(20)}}]
    chip_smoke.compare_logprobs(a, near, "kernel_vs_jnp")
    far = [{"prompt_tokens": 9,
            "top": {(i,): -1.0 - 0.1 * i + 0.5 for i in range(20)}}]
    with pytest.raises(chip_smoke.SmokeFailure, match="differ by"):
        chip_smoke.compare_logprobs(a, far, "kernel_vs_jnp")
    other = [{"prompt_tokens": 9,
              "top": {(i + 100,): -1.0 for i in range(20)}}]
    with pytest.raises(chip_smoke.SmokeFailure, match="share only"):
        chip_smoke.compare_logprobs(a, other, "kernel_vs_jnp")


# ---------------------------------------------------------------------
# the smoke itself, rehearsed on the CPU (slow: ~3 min each)
# ---------------------------------------------------------------------

@pytest.fixture
def tiny_smoke(monkeypatch, tmp_path):
    """chip_smoke.py steered to debug-tiny on the CPU, kernels in
    interpret mode, an empty compile cache of its own: the first
    rehearsal to make before a chip run (``on-chip-measurement`` 2.1).
    Everything is steered here, in the test — the script has no option
    for it."""
    import chip_smoke
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(CACHE_VAR, str(tmp_path / "cache"))
    # tiny executables compile in under the cache's 1 s threshold
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("PSTPU_FLASH", "1")
    # one "chip" for the children, not conftest's eight
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    monkeypatch.setattr(chip_smoke, "MODEL", "debug-tiny")
    monkeypatch.setattr(chip_smoke, "ENGINE_FLAGS", [
        "--seed", "0", "--max-model-len", "1024", "--prefill-chunk", "64",
        "--kv-block-size", "16", "--decode-batch-buckets", "8",
        "--decode-window-buckets", "8"])
    monkeypatch.setattr(chip_smoke, "PREFILL_CHUNK", 64)
    monkeypatch.setattr(chip_smoke, "BLOCK", 16)
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "KERNEL_MODE", "interpret")
    monkeypatch.setattr(chip_smoke, "LOG_DIR", str(tmp_path / "logs"))
    return chip_smoke


@pytest.mark.slow
def test_smoke_rehearsal_one_chip(tiny_smoke, capsys):
    assert tiny_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = [json.loads(line)["phase"] for line in lines[:-1]]
    assert phases.count("request") == 8
    assert phases.count("kernel_vs_jnp") == 3
    for phase in ("cold_start", "cached_start", "serve",
                  "served_device", "reference_start"):
        assert phase in phases


@pytest.mark.slow
def test_smoke_rehearsal_tensor_parallel(tiny_smoke, monkeypatch, capsys):
    """--chips on two virtual devices (debug-tiny has two kv heads)."""
    import asyncio
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    # the CPU backend has no memory_stats(): nothing to weigh
    monkeypatch.setattr(tiny_smoke, "shard_problems", lambda *a: [])
    seen = {}
    asyncio.run(tiny_smoke.run(2, seen))
    assert seen["count"] == 2 and len(seen["engine_devices"]) == 2
    assert set(seen["attention_paths"].values()) == {
        "pallas_paged_sharded", "pallas_paged_decode_sharded"}
    out = capsys.readouterr().out
    assert out.count('"phase": "tp4_vs_tp1"') == 3
