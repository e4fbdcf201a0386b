"""Compile-only checks for the TPU v5e, made without the chip.

Every other kernel test runs Pallas in interpret mode on the CPU, and
the interpreter accepts what the TPU's compiler refuses (the int8-KV
scale block of the general paged kernel passed every interpret test and
was refused at every prefill chunk size). The TPU compiler is installed
wherever libtpu is, and compiles for a chip that is described and not
attached (``on-chip-measurement`` guide, section 2.3). These tests hand
the serving path's kernels to it at the real widths — Mistral-7B head
geometry (32 q / 8 kv heads, head dim 128, block 64) and the 16/16-head
MoE geometry — one to two seconds each, and skip where the topology
cannot be described. A compile that passes is not a chip run. Each
kernel case compiles twice: on one bare layer of the pool and, as the
serving path calls it, on the whole pool with the layer as an operand.

One decode window and one prefill chunk of the runner, at the same head
geometry with two layers and a small pool, are compiled whole and their
optimised HLO is read: nothing in it may copy or slice the KV pool
(models/kv.py: the pool is carried, never stacked).

``-m slow`` adds one whole decode window and one whole prefill step of
the runner at Mistral-7B widths (int8 weights, all 32 layers), on one
chip and on the tp=4 mesh, read against the chip's 16 GB: the rehearsal
to make before spending chip time on a change to the step programs.
"""

import os
from functools import cached_property, partial

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler
#                                                   logs under /tmp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.ops import pallas_paged
from production_stack_tpu.parallel.mesh import AXES

D, BS = 128, 64          # head dim, KV block size (the engine default)
MB = 32                  # table width: 2048-token slots
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2 host, persistent compile cache off: a
    compile for a described device is written to the cache but cannot
    be read back without a chip, and the next one would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu / no such topology here
        pytest.skip(f"TPU v5e topology cannot be described: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", autouse=True)
def compiler_defaults():
    """tests/conftest.py builds the suite's CPU executables with most
    optimisations off. These tests read the TPU compiler's OPTIMISED HLO
    and what it fits into the chip, so this module compiles at the
    compiler's defaults."""
    was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", was)


def _placements(topo, tp: int, layers: int = 0):
    """Shardings for (q, pool, replicated, scales): one described chip,
    or head-sharded over a tp mesh of the four (a whole pool's leading
    layer axis unsharded)."""
    if tp == 1:
        one = SingleDeviceSharding(topo.devices[0])
        return None, (one, one, one, one)
    devs = np.array(topo.devices[:tp]).reshape(
        [tp if a == "tp" else 1 for a in AXES])
    mesh = Mesh(devs, AXES)
    lead = (None,) * bool(layers)
    return mesh, (NamedSharding(mesh, P(None, None, "tp", None)),
                  NamedSharding(mesh, P(*lead, None, "tp", None, None)),
                  NamedSharding(mesh, P()),
                  NamedSharding(mesh, P(*lead, None, "tp", None)))


def _lower_attention(topo, *, B, T, H, Hkv, int8, window=0, tp=1,
                     layers=0, nb=MB, softcap=0.0):
    """Lower the attention call the serving path makes for this shape
    (pallas_paged.attention_path's choice of kernel) for the described
    chip. layers: 0 = one bare layer of the pool [N, Hkv, Bs, D]; n =
    the whole pool [n, N, Hkv, Bs, D] and the layer index as an
    operand, as models/kv.attend calls it. nb: the kv bucket in
    blocks (8: the 512 bucket both cells decode in)."""
    mesh, (q_sh, kv_sh, rep_sh, sc_sh) = _placements(topo, tp, layers)
    n_blocks = B * MB + 1
    lead = (layers,) * bool(layers)
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=q_sh)
    pool = jax.ShapeDtypeStruct(
        lead + (n_blocks, Hkv, BS, D),
        jnp.int8 if int8 else jnp.bfloat16, sharding=kv_sh)
    tables = jax.ShapeDtypeStruct((B, MB), jnp.int32, sharding=rep_sh)
    starts = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep_sh)
    layer = (jax.ShapeDtypeStruct((), jnp.int32, sharding=rep_sh)
             if layers else None)
    scales = (jax.ShapeDtypeStruct(lead + (n_blocks, Hkv, BS),
                                   jnp.float32, sharding=sc_sh)
              if int8 else None)
    if mesh is not None:
        kernel = partial(pallas_paged.paged_attention_sharded, mesh=mesh)
    elif T <= pallas_paged.DECODE_T_MAX:
        kernel = pallas_paged.paged_decode_attention
    else:
        kernel = pallas_paged.paged_attention

    def call(q, k, v, tables, starts, ks, vs, layer):
        return kernel(q, k, v, tables, starts, nb=nb, window=window,
                      softcap=softcap, k_scales=ks, v_scales=vs,
                      layer=layer)

    return jax.jit(call).lower(q, pool, pool, tables, starts, scales,
                               scales, layer)


def _compile_attention(topo, **kw):
    """_lower_attention, compiled: raises what the chip's compiler
    would raise."""
    return _lower_attention(topo, **kw).compile()


KV = pytest.mark.parametrize("int8", [False, True],
                             ids=["kv_bf16", "kv_int8"])
POOL = pytest.mark.parametrize("layers", [0, 4],
                               ids=["layer_4d", "whole_pool"])
# the kv bucket: 512 tokens (where both cells decode) and 2048
NB = pytest.mark.parametrize("nb", [8, 32], ids=["kv512", "kv2048"])
# (H, Hkv): Mistral-7B's GQA and Qwen1.5-MoE's MHA
GEOMETRY = pytest.mark.parametrize("heads", [(32, 8), (16, 16)],
                                   ids=["gqa_32_8", "mha_16_16"])


@POOL
@KV
@NB
@pytest.mark.parametrize("B", [8, 32])
def test_wide_decode_kernel_compiles(topo, B, nb, int8, layers):
    _compile_attention(topo, B=B, T=1, H=32, Hkv=8, int8=int8,
                       layers=layers, nb=nb)


@KV
@GEOMETRY
@pytest.mark.parametrize("how", [
    dict(T=5), dict(T=8), dict(window=1000), dict(softcap=50.0),
    dict(T=4, window=1000, softcap=50.0, nb=8)],
    ids=["T5", "T8", "window", "softcap", "all_at_once"])
def test_decode_kernel_variants_compile(topo, how, heads, int8):
    """What no benchmark cell runs of the decode kernel: speculative
    windows (T*G rows a head that are no multiple of 8), a sliding
    window whose first live block lies inside a chunk, Gemma-2's
    softcap, on either pool."""
    H, Hkv = heads
    _compile_attention(topo, B=16, H=H, Hkv=Hkv, int8=int8, layers=4,
                       **{"T": 1, **how})


def _kernel_module(lowered) -> str:
    """The Mosaic module of the one kernel in a lowered program, as
    text: the custom call carries it serialized."""
    import base64
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    bodies = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                        lowered.as_text())
    assert len(bodies) == 1, len(bodies)
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True   # the serialized dialect
    with ctx:
        module = ir.Module.parse(base64.b64decode(bodies[0]))
        return module.operation.get_asm(enable_debug_info=False)


@KV
@GEOMETRY
def test_decode_kernel_feeds_the_mxu_native_panels(topo, heads, int8):
    """In the decode kernel's module for the described v5e no matmul
    takes a float32 operand, and nothing converts a K or V panel
    ([>= Bs, D]) to float32: the pool's bf16 goes to the MXU as it
    lies (int8: converted to bf16, which is exact), products
    accumulate in float32. The kernel this replaced up-cast every
    [Bs, D] panel on the vector unit and ran float32 matmuls of
    several MXU passes each (PERF.md, PR 30)."""
    import re
    H, Hkv = heads
    text = _kernel_module(_lower_attention(
        topo, B=16, T=1, H=H, Hkv=Hkv, int8=int8, layers=4, nb=8))
    matmuls = re.findall(
        r'tpu\.matmul"?\(.*?: \(vector<([\dx]+)x(\w+)>, '
        r'vector<([\dx]+)x(\w+)>, vector<[\dx]+xf32>\)', text)
    assert matmuls, "no matmul found in the kernel's module"
    assert {(a, b) for _, a, _, b in matmuls} == {("bf16", "bf16")}, \
        matmuls
    widened = [m for m in re.findall(
        r'arith\.(?:extf|sitofp|uitofp)"?\(.*?-> vector<([\dx]+)xf32>',
        text) if int(m.split("x")[-1]) == D
        and int(np.prod([int(n) for n in m.split("x")[:-1]])) >= BS]
    assert not widened, widened


@KV
@pytest.mark.parametrize("T,heads,nb", [
    (256, (32, 8), 8),      # M's one-row prefill in the 512 bucket
    (128, (16, 16), 8),     # Q's
    (512, (32, 8), 32),     # a 512-token chunk in the 2048 bucket
], ids=["m_256", "q_128", "m_512_kv2048"])
def test_prefill_kernel_feeds_the_mxu_native_panels(topo, T, heads, nb,
                                                    int8):
    """In the K/V prefill kernel's module for the described v5e no
    matmul takes a float32 operand, each takes a PANEL of pool blocks
    (``prefill_tiles``: 512 keys here, where the kernel this replaced
    took one 64-token block a grid step), and nothing converts a K or
    V panel to float32: the pool's bf16 goes to the MXU as it lies
    (int8: converted to bf16, which is exact), products accumulate in
    float32 (PERF.md, PR 43)."""
    import re
    H, Hkv = heads
    text = _kernel_module(_lower_attention(
        topo, B=1, T=T, H=H, Hkv=Hkv, int8=int8, layers=4, nb=nb))
    matmuls = re.findall(
        r'tpu\.matmul"?\(.*?: \(vector<([\dx]+)x(\w+)>, '
        r'vector<([\dx]+)x(\w+)>, vector<[\dx]+xf32>\)', text)
    assert len(matmuls) == 2, matmuls
    assert {(a, b) for _, a, _, b in matmuls} == {("bf16", "bf16")}, \
        matmuls
    block_q, R = pallas_paged.prefill_tiles(T, H // Hkv, D, nb, BS)
    assert (block_q, R) == (T, 8)
    rows = block_q * H // Hkv
    assert {(a, b) for a, _, b, _ in matmuls} == {
        (f"{rows}x{D}", f"{R * BS}x{D}"), (f"{rows}x{R * BS}", f"{R * BS}x{D}")}
    widened = [m for m in re.findall(
        r'arith\.(?:extf|sitofp|uitofp)"?\(.*?-> vector<([\dx]+)xf32>',
        text) if int(m.split("x")[-1]) == D]
    assert not widened, widened


@POOL
@KV
@pytest.mark.parametrize("window", [0, 4096])
@pytest.mark.parametrize("T", [16, 128, 512])
def test_general_paged_kernel_compiles(topo, T, window, int8, layers):
    # int8: the dequant scales ride as [1, Hkv, Bs] blocks — one head's
    # [1, Bs] row is neither 8-aligned nor the whole axis, and the TPU
    # lowering refuses it
    _compile_attention(topo, B=8, T=T, H=32, Hkv=8, int8=int8,
                       window=window, layers=layers)


@POOL
@KV
@NB
@pytest.mark.parametrize("T", [1, 128])
def test_mha_16_16_geometry_compiles(topo, T, nb, int8, layers):
    """Qwen1.5-MoE attention geometry: 16 q / 16 kv heads (G = 1)."""
    _compile_attention(topo, B=8, T=T, H=16, Hkv=16, int8=int8,
                       layers=layers, nb=nb)


@POOL
@KV
@pytest.mark.parametrize("T", [1, 128])
def test_tp4_sharded_wrapper_compiles(topo, T, int8, layers):
    """shard_map over the head axis on a mesh of the four described
    chips: the kernel stays one custom call per shard, and the wrapper
    adds no collective."""
    hlo = _compile_attention(topo, B=8, T=T, H=32, Hkv=8, int8=int8,
                             tp=4, layers=layers).as_text()
    assert "tpu_custom_call" in hlo
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective + "(" not in hlo, collective


# ---------------------------------------------------------------------
# whole step programs of the runner (at all 32 layers slow: ~1-2 min
# each; at two layers ~10 s each)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 8], ids=["T1", "T8"])
@pytest.mark.parametrize("B,MB,pools", [
    (16, 32, [(8, D)] * 2),         # Mistral-7B
    (16, 8, [(16, D)] * 2),         # Ouro-2.6B, Qwen1.5-MoE
    (8, 256, [(10, D)] * 2),        # Phi-4-mini-flash's paired heads
    (8, 256, [(2, D)] * 2),         # Nemotron-3-Nano
    (16, 32, [(1, 640)]),           # the latent pool (GLM-4.7-Flash)
    (8, 256, [(1, 640), (1, D)]),   # and the index pool beside it (GLM-5)
], ids=["gqa_8x128", "mha_16x128", "paired_10x128", "gqa_2x128", "latent",
        "latent_index"])
def test_append_rows_kernel_compiles(topo, B, MB, pools, T):
    """The decode window's write (ops/pallas_paged.append_rows) at the
    cells' pool geometries, on the whole pool [4, N, Hkv, 64, D] with
    the layer as an operand and the pool donated, for one position a
    row and for a speculative window of 8: a slab of 8 bfloat16 rows
    of every kv head (one tile of the pool in HBM) is copied out at a
    dynamic, tile-aligned row of a dynamic block and copied back; every pool comes back
    aliased to its argument."""
    one = SingleDeviceSharding(topo.devices[0])
    N = 2 * B + 1

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(pallas_paged.append_rows, donate_argnums=(0,)).lower(
        tuple(sds((4, N, h, BS, d)) for h, d in pools),
        tuple(sds((B, T, h, d)) for h, d in pools),
        sds((B, MB), jnp.int32), sds((B,), jnp.int32),
        sds((B, T), jnp.bool_), sds((), jnp.int32)).compile()
    assert "kv_append_rows" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        4 * N * h * BS * d * 2 for h, d in pools)


@pytest.mark.parametrize("B,T", [(128, 1), (64, 8)], ids=["B128-T1",
                                                         "B64-T8"])
def test_append_rows_compiles_at_the_largest_batch_the_rule_allows(
        topo, tpu_branches, B, T):
    """``append_rows`` holds every row's slabs in flight, a DMA
    semaphore a slab and pool, and the core has 512 words of them
    (``sflag``): K and V of 256 rows at one position ask for 512 and
    the chip's compiler refuses the call. ``kv_append_path`` says
    ``rows`` up to 256 semaphores, at Ouro's 16 heads of 128, and that
    compiles here; past it the executable keeps the block rewrite,
    which compiles at any batch."""
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pools = (sds((2, 2 * B + 1, 16, BS, D)),) * 2
    assert pallas_paged.kv_append_path(pools, B, T, None) == "rows"
    assert pallas_paged.kv_append_path(pools, 2 * B, T, None) == "blocks"
    compiled = jax.jit(pallas_paged.append_rows, donate_argnums=(0,)).lower(
        pools, (sds((B, T, 16, D)),) * 2, sds((B, MB), jnp.int32),
        sds((B,), jnp.int32), sds((B, T), jnp.bool_),
        sds((), jnp.int32)).compile()
    assert "kv_append_rows" in compiled.as_text()


@pytest.fixture
def tpu_branches(monkeypatch):
    """The serving path asks ``jax.default_backend()`` which attention
    to take and whether to interpret the kernel, and here that answers
    "cpu": steer both gates to their TPU answers for the compile."""
    monkeypatch.setattr(pallas_paged, "_override", True)
    monkeypatch.setattr(pallas_paged, "needs_interpret", lambda: False)


def _runner_shapes(topo, tp: int, layers=None, kv_blocks=None,
                   model: str = "mistral-7b"):
    """A ModelRunner skeleton (no arrays: a described device cannot
    hold one) plus ShapeDtypeStructs of its params and KV pool at
    Mistral-7B widths — int8 weights, all 32 layers, the server's
    default geometry — placed as the runner places them. layers and
    kv_blocks cut the depth and the pool, and nothing else; model
    names another preset's widths."""
    import dataclasses
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.models import llama
    from production_stack_tpu.models.config import get_config
    from production_stack_tpu.models.kv import cache_for
    from production_stack_tpu.ops.rope import rope_table
    from production_stack_tpu.parallel.sharding import (
        cache_pspec, param_shardings)

    mesh, (_, _, rep_sh, _) = _placements(topo, tp)
    mcfg = get_config(model)
    if layers:
        mcfg = dataclasses.replace(mcfg, num_layers=layers)
    ecfg = EngineConfig(model=model, quantization="int8")
    runner = ModelRunner.__new__(ModelRunner)
    runner.model_cfg, runner.engine_cfg, runner.mesh = mcfg, ecfg, mesh
    runner._lora, runner._lora_scaling = None, 1.0
    runner.rope = rope_table(ecfg.max_model_len, mcfg.rope_dim_,
                             mcfg.rope_theta, scaling=mcfg.rope_scaling)

    params = jax.eval_shape(
        partial(llama.init_params, mcfg, quantization="int8"),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(partial(
        cache_for, mcfg, kv_blocks or ecfg.num_kv_blocks,
        ecfg.kv_block_size,
        state_pages=ecfg.max_num_seqs + 1 if mcfg.gdn_layers else 0))
    if mesh is None:
        p_sh = jax.tree.map(lambda _: rep_sh, params)
        c_sh = jax.tree.map(lambda _: rep_sh, cache)
    else:
        p_sh = param_shardings(mesh, params)
        c_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, cache_pspec()), cache)

    def place(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=s),
            tree, shardings)

    def rep(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep_sh)

    return runner, place(params, p_sh), place(cache, c_sh), rep


def _step_args(runner, rep, B: int):
    """The replicated small operands of a step program, as the runner's
    host API builds them for an unguided, unpenalized batch."""
    from production_stack_tpu.engine.sampler import SamplingParams
    ecfg = runner.engine_cfg
    sampling = jax.tree.map(
        lambda x: rep(x.shape, x.dtype), SamplingParams.filled(B))
    return dict(
        tables=rep((B, ecfg.max_blocks_per_seq), jnp.int32),
        sampling=sampling, key=rep((2,), jnp.uint32),
        guide_next=rep((1, 1, 1), jnp.int32),
        guide_id=rep((B,), jnp.int32), guide_state=rep((B,), jnp.int32),
        counts=rep((B, 1), jnp.int32), seen=rep((B, 1), jnp.bool_))


def _fits(compiled, what: str) -> None:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{what}: arguments {m.argument_size_in_bytes / 2**30:.2f} "
          f"GiB, temp {m.temp_size_in_bytes / 2**30:.2f} GiB, "
          f"aliased {m.alias_size_in_bytes / 2**30:.2f} GiB, "
          f"needs {need / 2**30:.2f} GiB per device")
    assert need < HBM_BYTES, what


def _lower_decode_window(runner, params, cache, rep, rows: int = 0):
    """A greedy decode window of 8 steps at the first kv bucket, the
    pool donated, as the runner jits it, at the batch bucket ``rows``
    (0: all max_num_seqs)."""
    B = rows or runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    fn = jax.jit(partial(runner._decode_impl, steps=8, kv_len=512,
                         greedy=True), donate_argnums=(1,))
    return fn.lower(
        params, cache, a["tables"], rep((B,), jnp.int32),
        rep((B,), jnp.int32), a["sampling"], a["key"], a["guide_next"],
        a["guide_id"], a["guide_state"], a["counts"], a["seen"])


def _lower_prefill_chunk(runner, params, cache, rep, Tb: int,
                         rows: int = 0):
    """A prefill chunk of ``rows`` rows (0: all max_num_seqs); what is
    kept per slot keeps max_num_seqs rows and is gathered by slots."""
    B = runner.engine_cfg.max_num_seqs
    R = rows or B
    a = _step_args(runner, rep, B)
    fn = jax.jit(partial(runner._prefill_impl, kv_len=512),
                 donate_argnums=(1,))
    return fn.lower(
        params, cache, a["tables"], rep((R,), jnp.int32),
        rep((R, Tb), jnp.int32),
        rep((R,), jnp.int32), rep((R,), jnp.int32), a["sampling"],
        a["key"], a["guide_next"], a["guide_id"], a["guide_state"],
        a["counts"], a["seen"])


class _StepProgram:
    """One step program lowered for the described chip, compiled when
    first asked for; every test that asserts on it reads this one."""

    def __init__(self, lowered):
        self.lowered = lowered

    @cached_property
    def compiled(self):
        return self.lowered.compile()

    @cached_property
    def hlo(self) -> str:
        return self.compiled.as_text()


@pytest.fixture(scope="module")
def step_program(topo):
    """(model, layers, tokens, rows) -> the _StepProgram of a runner's
    decode window (``tokens`` 0) or prefill chunk of ``tokens`` positions
    a row over a pool of ``kv_blocks`` blocks, at the batch bucket
    ``rows`` (0: all max_num_seqs) and the 512 kv bucket, on one
    described chip: lowered
    and compiled once a worker, whichever test asks first
    (docs/testing.md; the persistent cache cannot keep a compile for a
    described device). Ask with ``tpu_branches`` in force."""
    made = {}

    def get(model: str, layers: int, *, tokens: int = 0, rows: int = 0,
            kv_blocks: int = 97):
        key = (model, layers, tokens, rows, kv_blocks)
        if key not in made:
            shapes = _runner_shapes(topo, 1, layers=layers,
                                    kv_blocks=kv_blocks, model=model)
            made[key] = _StepProgram(
                _lower_prefill_chunk(*shapes, tokens, rows) if tokens
                else _lower_decode_window(*shapes, rows))
        return made[key]
    return get


# a pool no smaller than a cell's, for the tests that read what the
# compiler does with the pool: the chip's 128 MiB of VMEM can hold a
# pool of 97 blocks whole (12.5 MB each for K and V at two layers), and
# XLA then stages it there around the kernels, which no serving pool
# allows. 1201 blocks are 315 MB of keys, 265 MB of latents
POOL_BLOCKS = 1201


def _block_rewrites(hlo: str, minor: str) -> dict:
    """{(result type, opcode): count} of the gathers, scatters and
    selects in an optimised HLO (fused computations' bodies are in the
    text) whose result ends in a pool block's ``Bs,D``: what
    models/kv.append_chunk's whole-block rewrite compiles to."""
    import re
    block = re.compile(r" = \(?(\w+\[[\d,]*,{}\])\S* ([\w\-]+)\("
                       .format(minor))
    found = {}
    for m in filter(None, map(block.search, hlo.splitlines())):
        if re.search(r"gather|scatter|select", m.group(2)):
            found[m.groups()] = found.get(m.groups(), 0) + 1
    return found


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk",
                                     "prefill_chunk_1row",
                                     "prefill_chunk_2rows"])
def test_step_program_never_copies_the_pool(tpu_branches, step_program,
                                            program):
    """Mistral head geometry, two layers, a pool of 1201 blocks: in the
    optimised HLO no copy, dynamic-slice or dynamic-update-slice (nor
    a fusion named for one) yields an array of the pool's shape or of
    one layer's. The pool handed through the layer scan's xs -> ys was
    sliced, re-laid-out and stacked per layer and copied whole per step
    (`copy.108`, `dynamic-slice_bitcast_fusion.5`,
    `bitcast_dynamic-update-slice_fusion.4` of PERF.md, PR 25); scattered
    token by token it is copied whole into a token-major layout and
    back, per layer (models/kv.py: appends rewrite whole blocks)."""
    import re
    L, N = 2, POOL_BLOCKS
    if program == "decode_window":
        made = step_program("mistral-7b", L, kv_blocks=N)
    else:
        rows = {"prefill_chunk": 0, "prefill_chunk_1row": 1,
                "prefill_chunk_2rows": 2}[program]
        made = step_program("mistral-7b", L, tokens=128, rows=rows,
                            kv_blocks=N)
    compiled, hlo = made.compiled, made.hlo
    assert "tpu_custom_call" in hlo
    # "%name = bf16[2,97,8,64,128]{layout} opcode(": a pool-shaped result
    pool_result = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:{},)?{},8,{},{}\]\S* ([\w\-]+)\("
        .format(L, N, BS, D))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pool_result.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    # and the pool is one buffer from argument to result
    pool_bytes = 2 * L * N * 8 * BS * D * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    # a decode window lands its new rows through the writer kernel (PR
    # 58: ops/pallas_paged.append_rows): no block is gathered, merged
    # under a mask or scattered back; a prefill chunk rewrites blocks
    rewrites = _block_rewrites(hlo, f"{BS},{D}")
    if program == "decode_window":
        assert not rewrites, rewrites
        assert "kv_append_rows" in hlo
    else:
        assert rewrites and "kv_append_rows" not in hlo


# ---------------------------------------------------------------------
# the experts of a decode step (ops/moe.py, the list path)
# ---------------------------------------------------------------------

QWEN_MOE = dict(E=60, h=2048, i=1408, k=4)    # Qwen1.5-MoE-A2.7B


@pytest.mark.parametrize("weights,N", [
    ("int8", 16), ("bf16", 16), ("int8", 1), ("int8", 4), ("int8", 64)])
def test_moe_list_kernel_compiles(topo, tpu_branches, weights, N):
    """The list path's kernel at the Qwen cell's widths (60 experts of
    2048 x 1408, top-4) on a 12-layer stack with the layer as an
    operand, as models/llama.forward calls it, at the cell's 16 rows,
    at the smallest decode batch buckets and at the most rows the rule
    sends it (moe.DENSE_THRESHOLD): two slots of three 2.88 MB int8
    matrices (5.77 MB in bf16) and the converted copy have to fit the
    VMEM limit, and the copies' slices the tiling."""
    from production_stack_tpu.ops import moe
    L = 12
    assert moe.list_path(N, 1, QWEN_MOE["h"], QWEN_MOE["i"],
                         jnp.dtype(weights if weights == "int8"
                                   else jnp.bfloat16), jnp.bfloat16)
    E, h, i, k = (QWEN_MOE[n] for n in "Ehik")
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def stack(dims):
        if weights == "bf16":
            return shape(dims, jnp.bfloat16)
        return {"w8": shape(dims, jnp.int8),
                "scale": shape(dims[:2] + dims[3:], jnp.float32)}

    def call(x, top_p, top_i, gate, up, down, ids, count, layer):
        return moe._moe_list(x, top_p, top_i, gate, up, down,
                             jax.nn.silu, ids, count, layer)

    lowered = jax.jit(call).lower(
        shape((N, h), jnp.bfloat16), shape((N, k), jnp.float32),
        shape((N, k), jnp.int32), stack((L, E, h, i)),
        stack((L, E, h, i)), stack((L, E, i, h)),
        shape((min(E, N * k),), jnp.int32), shape((), jnp.int32),
        shape((), jnp.int32))
    hlo = lowered.compile().as_text()
    assert "moe_list_experts" in hlo and "tpu_custom_call" in hlo


def _stack_makers(hlo: str, dims: str) -> list:
    """Instructions of the optimised HLO whose result has the
    dimensions ``dims`` (a regex) and which are more than a name for
    an operand (parameter, get-tuple-element, bitcast): "name: opcode"
    each. A fusion that yields such an array is a copy of it, whatever
    its name."""
    import re
    result = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:{})\]\S* ([\w\-]+)\(".format(dims))
    return [m.group(1) + ": " + m.group(2)
            for m in map(result.search, hlo.splitlines()) if m
            and m.group(2) not in ("parameter", "get-tuple-element",
                                   "bitcast")]


def test_decode_window_reads_the_experts_in_place(tpu_branches,
                                                  step_program):
    """One decode window of the runner at the Qwen1.5-MoE geometry with
    two layers, compiled whole: the expert matmuls are the list path's
    custom call, and nothing in the optimised HLO yields an array of
    an expert stack's shape [2, 60, 2048, 1408] / [2, 60, 1408, 2048]
    or of one layer's: no copy, no slice, no fusion. Handed a layer as the layer scan's xs, the custom call
    would be handed a 173 MB copy of each matrix stack per layer
    (it cannot fuse its operand's slice; Mistral's
    ``constant_dynamic-slice_fusion.6 s8[1,4096,4096]`` is that kind
    of copy), so models/llama.forward closes over the stacks."""
    L = 2
    hlo = step_program("qwen1.5-moe-a2.7b", L).hlo
    assert "moe_list_experts" in hlo
    E, h, i = (QWEN_MOE[n] for n in "Ehi")
    stack = r"(?:(?:{}|1),)?{},(?:{},{}|{},{})".format(L, E, h, i, i, h)
    made = _stack_makers(hlo, stack)
    assert not made, made


@pytest.mark.parametrize("rows", [1, 4])
def test_mixtral_decode_window_keeps_the_exact_path(tpu_branches,
                                                    step_program, rows):
    """Mixtral-8x7B unsharded, two layers: one expert matrix is 58.7 MB
    in int8, two slots of three miss VMEM (moe.list_path), so a decode
    window of a small batch bucket (4 rows x top-2 hit 5.3 of 8 experts
    under even routing, one row 2: shares a list would pay for)
    compiles the exact path, as it did."""
    hlo = step_program("mixtral-8x7b", 2, rows=rows).hlo
    assert "moe_list_experts" not in hlo and "moe_experts" in hlo


def test_qwen_speculative_window_keeps_the_exact_path(topo,
                                                      tpu_branches):
    """A speculative window of 4 rows x 4 positions holds as many
    tokens as a decode batch and is not the list path's: that is the
    decode step's (one position a row), the only program measured with
    it. (A prefill chunk, of whatever length: tests/test_moe.py.)"""
    runner, params, cache, rep = _runner_shapes(
        topo, 1, layers=2, kv_blocks=97, model="qwen1.5-moe-a2.7b")
    B, K = 4, 3
    a = _step_args(runner, rep, B)
    fn = jax.jit(partial(runner._decode_spec_impl, steps=4,
                         kv_len=512, spec=K), donate_argnums=(1,))
    hlo = fn.lower(
        params, cache, a["tables"], rep((B,), jnp.int32),
        rep((B,), jnp.int32), rep((B, 64), jnp.int32),
        rep((B,), jnp.bool_), a["sampling"], a["key"],
        a["guide_next"], a["guide_id"], a["guide_state"],
        a["counts"], a["seen"]).compile().as_text()
    assert "moe_list_experts" not in hlo and "moe_experts" in hlo


# ---------------------------------------------------------------------
# the experts of a prefill chunk (ops/moe.py, the grouped path)
# ---------------------------------------------------------------------

GLM_MOE = dict(E=64, h=2048, i=1536, k=4)     # GLM-4.7-Flash


@pytest.mark.parametrize("rows,tokens", [(1, 128), (1, 256), (16, 256)])
@pytest.mark.parametrize("experts", [QWEN_MOE, GLM_MOE],
                         ids=["qwen15moe", "glm47flash"])
def test_moe_grouped_kernel_compiles(topo, tpu_branches, experts, rows,
                                     tokens):
    """The grouped path's kernel at the MoE cells' widths (int8 stacks
    of 12 layers, the layer an operand) and their prefill shapes: the
    one-row chunk of both buckets and the lead-in's 16-row burst. Two
    slots of three int8 matrices, the converted copy, two passes' rows
    in and out have to fit the VMEM limit; the rows' copies start at
    any multiple of 16 rows of a buffer in HBM."""
    from production_stack_tpu.ops import moe
    E, h, i, k = (experts[n] for n in "Ehik")
    N, L = rows * tokens, 12
    assert moe.grouped_path(rows, tokens, h, i, jnp.int8, jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def stack(dims):
        return {"w8": shape(dims, jnp.int8),
                "scale": shape(dims[:2] + dims[3:], jnp.float32)}

    def call(x, top_p, top_i, valid, gate, up, down, layer):
        return moe._moe_grouped(x, top_p, top_i, gate, up, down,
                                jax.nn.silu, valid, layer)

    hlo = jax.jit(call).lower(
        shape((N, h), jnp.bfloat16), shape((N, k), jnp.float32),
        shape((N, k), jnp.int32), shape((N,), jnp.bool_),
        stack((L, E, h, i)), stack((L, E, h, i)), stack((L, E, i, h)),
        shape((), jnp.int32)).compile().as_text()
    assert "moe_grouped_experts" in hlo and "tpu_custom_call" in hlo


@pytest.mark.parametrize("model,layers,experts,rows", [
    ("qwen1.5-moe-a2.7b", 2, QWEN_MOE, 1),
    ("glm-4.7-flash", 3, GLM_MOE, 1),
    ("qwen1.5-moe-a2.7b", 2, QWEN_MOE, 0)],
    ids=["qwen15moe-1row", "glm47flash-1row", "qwen15moe-16rows"])
def test_prefill_chunk_multiplies_only_routed_rows(tpu_branches,
                                                   step_program, model,
                                                   layers, experts, rows):
    """A prefill chunk of 256 tokens of the runner at the MoE cells'
    widths, compiled whole: the expert matmuls are the grouped path's
    custom call on the stacks in place; no instruction yields a product
    of every expert over every token ([E, N, i] or [E, N, h], the exact
    path's; [E, C, ...] at any capacity, the dispatch's) and none an
    array of an expert stack's shape or of one layer's."""
    import re
    # (the latent model's chunk is the one never_copies_the_pool reads)
    hlo = step_program(
        model, layers, tokens=256, rows=rows,
        kv_blocks=POOL_BLOCKS if model == "glm-4.7-flash" else 97).hlo
    assert "moe_grouped_experts" in hlo
    assert "moe_list_experts" not in hlo
    # (every expert is held: no rounds, so no sum by token, PR 53)
    assert "moe_held_sum" not in hlo
    E, h, i = (experts[n] for n in "Ehi")
    per_expert = [m.group(0) for m in re.finditer(
        r"\w+\[{},\d+,(?:{}|{})\]".format(E, i, h), hlo)]
    assert not per_expert, per_expert[:5]
    stack = r"(?:\d+,)?{},(?:{},{}|{},{})".format(E, h, i, i, h)
    assert not _stack_makers(hlo, stack)


def _program_digest(lowered) -> str:
    """sha256 of a lowered program's text, each kernel's serialized
    Mosaic module replaced by its MLIR without debug info (the text
    carries no source locations; the serialized modules do, and those
    move with every line added to ops/)."""
    import hashlib
    import re

    def module_digest(m):
        return "body: " + hashlib.sha256(
            _kernel_asm(m.group(1)).encode()).hexdigest()

    text = re.sub(_KERNEL_BODY, module_digest, lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


_KERNEL_BODY = r'body\\22: \\22([A-Za-z0-9+/=]+)\\22'


def _kernel_asm(serialized: str) -> str:
    """A kernel's serialized Mosaic module as MLIR without debug
    info."""
    import base64
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(base64.b64decode(
            serialized)).operation.get_asm(enable_debug_info=False)


def _kernel_bodies(lowered) -> list:
    """(sha256, length) of the MLIR of every kernel call site of a
    lowered program, in the text's order: what Pallas lowered to Mosaic
    once a call site to make this build."""
    import hashlib
    import re
    return [(hashlib.sha256(asm.encode()).hexdigest(), len(asm))
            for asm in map(_kernel_asm, re.findall(_KERNEL_BODY,
                                                   lowered.as_text()))]


@pytest.mark.parametrize("model,layers,digest", [
    ("qwen1.5-moe-a2.7b", 2,
     "689cc4bdc775e502aa120c0e0a3368a34fbaea652d85a5a4a39787e6e0463e30"),
    ("glm-4.7-flash", 3,
     "248acc4e752f7ff462b32f3405ad7d969a2406c92b5fa66061c0b0d505b57dd9")],
    ids=["qwen15moe", "glm47flash"])
def test_moe_decode_window_lowers_to_the_pinned_text(tpu_branches,
                                                     step_program, model,
                                                     layers, digest):
    """``jit_decode_window`` of both MoE configurations (16 rows, 8
    steps, the 512 kv bucket, greedy) lowers for the described v5e to
    the text it lowered to on PR 58's tree (digests taken on that tree
    by this test, under pytest: the suite's conftest.py enters the
    text). From commit 5d902fa, PR 38, to PR 57 the text stood (PR 39
    changed the prefill's experts and nothing a decode step runs);
    PR 58 MEANT to change the decode program: a layer's new K/V rows,
    or latents, land through ``append_rows`` where the gather, select
    and scatter of whole blocks stood, and nothing else moved (the
    decode kernel's and the list path's bodies are the parent's: the
    Phi-4-mini-flash test below holds the first bit for bit). A PR that
    means to change the decode program pins its own digests here and
    says so."""
    assert _program_digest(step_program(model, layers).lowered) == digest


def test_dense_decode_window_has_no_expert_call(tpu_branches,
                                                step_program):
    """The dense model's decode window knows nothing of the list path:
    its only custom call is the attention kernel's, and no instruction
    carries a ``moe_`` scope. (That its optimised HLO is the parent
    commit's, instruction for instruction, was read off both trees'
    compiles for PR 34: PERF.md.)"""
    import re
    hlo = step_program("mistral-7b", 2, kv_blocks=POOL_BLOCKS).hlo
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = \S+ custom-call\(", hlo)
        if "tpu_custom_call" in hlo}
    kernels = {c for c in calls if c.startswith(("paged", "moe"))}
    assert kernels == {"paged_decode_attention"}, calls
    # instructions only: the text's table of stack frames names whatever
    # test first traced a shared helper in this worker
    assert not [line for line in hlo.splitlines()
                if " = " in line and "moe_" in line]


# ---------------------------------------------------------------------
# latent attention over the latent pool (GLM-4.7-Flash: 20 heads on one
# cached vector of 512 + 64 values a token, the first 512 its value)
# ---------------------------------------------------------------------

GLM = dict(H=20, W=640, R=512)     # W: 576 values in whole lanes


def _lower_latent_attention(topo, *, B, T, nb, layers=13):
    """The latent pool's attention call at GLM-4.7-Flash's widths as
    models/kv.attend makes it: the whole pool [L, N, 1, Bs, W] with the
    layer as an operand, no V pool, the absorbed queries [B, T, 20, W]."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    kernel = (pallas_paged.paged_decode_attention
              if T <= pallas_paged.DECODE_T_MAX
              else pallas_paged.paged_attention)

    def call(q, pool, tables, starts, layer):
        return kernel(q, pool, None, tables, starts, nb=nb, layer=layer,
                      scale=256 ** -0.5, value_dim=GLM["R"])

    return jax.jit(call).lower(
        shape((B, T, GLM["H"], GLM["W"]), jnp.bfloat16),
        shape((layers, 386, 1, BS, GLM["W"]), jnp.bfloat16),
        shape((B, MB), jnp.int32), shape((B,), jnp.int32),
        shape((), jnp.int32))


@pytest.mark.parametrize("B,T,nb", [
    (16, 1, 8), (16, 1, 32), (1, 1, 8),          # decode steps
    (1, 256, 4), (1, 256, 8), (16, 256, 4), (16, 256, 8),  # prefill
    (1, 64, 4), (1, 128, 4)])
def test_latent_kernels_compile(topo, B, T, nb):
    """Both paged kernels' latent case at the cell's shapes: decode
    steps of 16 rows (and one) at the 512 and 2048 kv buckets, prefill
    chunks of 1 and 16 rows x 256 tokens (and the smaller buckets)
    against contexts of 256 and 512. The keys are 576 values padded to
    640 (five lanes of 128: the compiler refuses to copy 576 columns
    out of the 640 the array takes in HBM anyway) and the values their
    first 512 columns."""
    hlo = _lower_latent_attention(topo, B=B, T=T, nb=nb).compile().as_text()
    assert "tpu_custom_call" in hlo
    name = ("paged_decode_attention" if T <= pallas_paged.DECODE_T_MAX
            else "paged_attention")
    assert f"%{name}" in hlo, "the trace readers find the kernel by name"


GLM5 = dict(H=64, W=640, R=512, DN=192, DR=64, DV=256)


@pytest.mark.parametrize("T,nb,masked", [
    (2048, 256, True), (2048, 32, False), (512, 32, False)],
    ids=["chunk2048-kv16384-marks", "chunk2048-kv2048", "chunk512"])
def test_expanded_latent_prefill_kernel_compiles(topo, T, nb, masked):
    """The prefill kernel's EXPANDED case at GLM-5's widths as the
    long-context cell runs it: one row of 2048 positions against the
    16 384 kv bucket under the selection's marks (and the 2048 bucket,
    where nothing selects; and the shortest chunk past the rule's cut):
    64 heads' queries of 256, W_kvb's halves in int8 with their
    scales, the whole latent pool [7, 2049, 1, 64, 640]. The operation
    keeps the name the trace readers know the prefill executables by."""
    import re
    g = GLM5
    assert pallas_paged.expanded_cheaper(T, g["W"], g["R"],
                                         (g["DN"], g["DR"], g["DV"]))
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def call(q, pool, tables, starts, layer, w_uk, w_uv, s_k, s_v, marks):
        return pallas_paged.paged_attention(
            q, pool, None, tables, starts, nb=nb, layer=layer,
            scale=256 ** -0.5, value_dim=g["R"],
            select=marks if masked else None,
            expand=(w_uk, w_uv, s_k, s_v))

    compiled = jax.jit(call).lower(
        shape((1, T, g["H"], g["DN"] + g["DR"]), jnp.bfloat16),
        shape((7, 2049, 1, BS, g["W"]), jnp.bfloat16),
        shape((1, 256), jnp.int32), shape((1,), jnp.int32),
        shape((), jnp.int32),
        shape((g["R"], g["H"], g["DN"]), jnp.int8),
        shape((g["R"], g["H"], g["DV"]), jnp.int8),
        shape((g["H"], g["DN"]), jnp.float32),
        shape((g["H"], g["DV"]), jnp.float32),
        shape((1, T, nb * BS), jnp.bfloat16)).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"%(paged_attention)[\w.\-]* = (\S+) custom-call\(",
                       hlo)
    assert len(calls) == 1, "the trace readers find the kernel by name"
    # one head's whole chunk a q block: [1, 64, T, 256] head-major
    assert calls[0][1].startswith(f"bf16[1,{g['H']},{T},{g['DV']}]")
    _fits(compiled, f"expanded prefill attention T={T} nb={nb}")


def test_latent_decode_kernel_copies_a_block_once(topo):
    """The latent decode kernel's module has ONE operand in HBM (the
    pool: no V pool beside it) and one set of VMEM slots, [2, R, 1, Bs,
    W]; bf16 panels go to the MXU as they lie."""
    import re
    text = _kernel_module(_lower_latent_attention(topo, B=16, T=1, nb=8))
    signature = text[:text.index("):")]
    assert re.findall(r"memref<([\dx]+)xbf16, #tpu.memory_space<hbm>>",
                      signature) == ["13x386x1x64x640"]
    assert signature.count("2x8x1x64x640xbf16") == 1
    matmuls = re.findall(
        r'tpu\.matmul"?\(.*?: \(vector<([\dx]+)x(\w+)>, '
        r'vector<([\dx]+)x(\w+)>, vector<[\dx]+xf32>\)', text)
    assert matmuls and {(a, b) for _, a, _, b in matmuls} == {
        ("bf16", "bf16")}, matmuls


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk",
                                     "prefill_chunk_1row"])
def test_latent_step_program_never_copies_the_pool(tpu_branches,
                                                   step_program, program):
    """never_copies_the_pool for the latent pool [3, 1201, 1, 64, 576]:
    the leading dense layer outside the scan and the scanned expert
    layers append to and read ONE carried buffer."""
    import re
    L, N = 3, POOL_BLOCKS
    if program == "decode_window":
        made = step_program("glm-4.7-flash", L, kv_blocks=N)
    else:
        made = step_program(
            "glm-4.7-flash", L, tokens=256, kv_blocks=N,
            rows={"prefill_chunk": 0, "prefill_chunk_1row": 1}[program])
    compiled, hlo = made.compiled, made.hlo
    assert "tpu_custom_call" in hlo
    pool_result = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:{},)?{},1,{},{}\]\S* ([\w\-]+)\("
        .format(L, N, BS, GLM["W"]))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pool_result.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= L * N * BS * GLM["W"] * 2)
    rewrites = _block_rewrites(hlo, f"{BS},{GLM['W']}")
    if program == "decode_window":      # the latents land as rows too
        assert not rewrites, rewrites
        assert "kv_append_rows" in hlo
    else:
        assert rewrites and "kv_append_rows" not in hlo


def test_latent_decode_window_makes_no_key_or_value_per_head(
        tpu_branches, step_program):
    """The decode executable of the latent model is absorbed: its
    attention is the paged decode kernel on the pool, the experts are
    the list path's call on the expert layers' stacks in place, and no
    instruction yields keys or values per head — an array whose minor
    dimensions are [20, 256] / [20, 192] / [20, 448] over the batch's
    16 rows and a context axis (>= one block of 64 tokens)."""
    import re
    hlo = step_program("glm-4.7-flash", 3, kv_blocks=POOL_BLOCKS).hlo
    assert "%paged_decode_attention" in hlo
    assert "moe_list_experts" in hlo
    def leading(m):
        return [int(n) for n in m.group(1).split(",") if n]
    # (W_kvb itself is [512, 20, 448]: a context axis comes with the
    # batch's 16 rows beside it)
    per_head = [m.group(0) for m in re.finditer(
        r"\w+\[((?:\d+,)*)20,(?:256|192|448)\]", hlo)
        if 16 in leading(m) and max(leading(m)) >= BS]
    assert not per_head, per_head[:5]
    stack = r"(?:(?:2|1),)?64,(?:2048,1536|1536,2048)"
    assert not _stack_makers(hlo, stack)


# ---------------------------------------------------------------------
# learned sparse attention and the chip's share of the experts (GLM-5 at
# its published widths: 64 heads on one cached vector of 512 + 64
# values, 32 index heads of 128 on the index pool, top-2048; experts of
# 6144 x 2048 read in two tiles, 16 held behind a router of 256)
# ---------------------------------------------------------------------

def _glm5_runner(topo, monkeypatch, layers=3):
    """The runner skeleton at the benchmark's GLM-5 file, cut to one
    dense and ``layers - 1`` expert layers, 8 slots of 16384 tokens
    over both pools (the cell's geometry)."""
    import dataclasses
    import json
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.ops.rope import rope_table
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "glm-5-int8-l7-e16.json")) as f:
        conf = json.load(f)
    mcfg = dataclasses.replace(model_config(conf, "glm-5-share"),
                               num_layers=layers)
    monkeypatch.setitem(model_configs.PRESETS, "glm-5-share", mcfg)
    runner, params, cache, rep = _runner_shapes(
        topo, 1, kv_blocks=2049, model="glm-5-share")
    runner.engine_cfg = EngineConfig(
        model="glm-5-share", quantization="int8", max_num_seqs=8,
        max_model_len=16384, kv_pool_tokens=131072, prefill_chunk=2048)
    runner.rope = rope_table(16384, mcfg.rope_dim_, mcfg.rope_theta)
    return runner, params, cache, rep


@pytest.mark.parametrize("program,kv_len", [
    ("decode_window", 16384), ("prefill_chunk", 16384),
    ("decode_window", 2048)])
def test_sparse_step_program_compiles_at_glm5_widths(
        topo, tpu_branches, monkeypatch, program, kv_len):
    """One decode window of 8 rows and one 2048-token prefill chunk of
    one row at the longest kv bucket, compiled whole for the described
    v5e: the indexer's scores and the selection are the two kernels of
    ops/dsa.py, the attention the paged kernels' sparse case, the
    experts the list and the grouped kernel in tiles; neither pool is
    copied or sliced; the program fits the chip. At the 2048 bucket
    nothing is selected: plain latent attention, no indexer kernel."""
    import re
    L, N = 3, 2049
    runner, params, cache, rep = _glm5_runner(topo, monkeypatch, L)
    B = runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    small = (a["sampling"], a["key"], a["guide_next"], a["guide_id"],
             a["guide_state"], a["counts"], a["seen"])
    if program == "decode_window":
        fn = jax.jit(partial(runner._decode_impl, steps=8, kv_len=kv_len,
                             greedy=True), donate_argnums=(1,))
        compiled = fn.lower(params, cache, a["tables"],
                            rep((B,), jnp.int32), rep((B,), jnp.int32),
                            *small).compile()
        want = {"paged_decode_attention", "moe_list_experts"}
    else:
        fn = jax.jit(partial(runner._prefill_impl, kv_len=kv_len),
                     donate_argnums=(1,))
        compiled = fn.lower(params, cache, a["tables"],
                            rep((1,), jnp.int32), rep((1, 2048), jnp.int32),
                            rep((1,), jnp.int32), rep((1,), jnp.int32),
                            *small).compile()
        want = {"paged_attention", "moe_grouped_experts", "moe_held_sum"}
    if runner.selects(kv_len):
        want |= {"dsa_index_scores", "dsa_select"}
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = \S+ custom-call\(", hlo)}
    assert {c for c in calls if c.startswith(("paged", "moe", "dsa"))} \
        == want
    assert (runner._attention_path(1 if program == "decode_window"
                                   else 2048, None, kv_len)
            .endswith("_sparse")) is runner.selects(kv_len)
    if program == "prefill_chunk":
        # the held experts' rounds (ops/moe._moe_grouped): nothing of a
        # chunk's 2048 x 8 assignments by the hidden width is built,
        # nor the buffer of 16 752 rows they were sorted into; a
        # round's buffer is 2048 + 16 x 15 + 128 rows
        from production_stack_tpu.ops import moe
        assert moe.held_block(2048, 8, 16, 256) == 2048
        big = re.findall(            # (int8 [16384, 6144]: o_proj)
            r"(?:bf16|f32)\[(?:\d+,)?(?:16384|16752|33504),6144\]", hlo)
        assert not big, big[:3]
        assert re.search(r"bf16\[4832,6144\]", hlo)   # two tiles' planes
        # and a round's sum by token is the kernel's one pass over the
        # block (``moe_held_sum``), no scatter into the float32 sum
        scattered = re.findall(r"= f32\[2048,6144\]\S* scatter\(", hlo)
        assert not scattered, scattered[:3]
    runner.params = params      # (_moe_path reads the stacks' dtype)
    assert runner._moe_path(8, 1) == "list_tiled2"
    assert runner._moe_path(1, 2048) == "grouped_tiled2"
    pools = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:{},)?{},1,{},(?:640|128)\]\S* "
        r"([\w\-]+)\(".format(L, N, BS))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pools.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= L * N * BS * (640 + 128) * 2)
    _fits(compiled, f"glm-5 share {program} kv={kv_len}")


@pytest.mark.slow
@pytest.mark.parametrize("tp", [1, 4])
def test_decode_window_compiles_at_mistral_7b(topo, tpu_branches, tp):
    compiled = _lower_decode_window(*_runner_shapes(topo, tp)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, f"decode window tp={tp}")


@pytest.mark.slow
@pytest.mark.parametrize("rows", [0, 1])
@pytest.mark.parametrize("tp", [1, 4])
def test_prefill_step_compiles_at_mistral_7b(topo, tpu_branches, tp,
                                             rows):
    compiled = _lower_prefill_chunk(*_runner_shapes(topo, tp), 512,
                                    rows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, f"prefill step tp={tp} rows={rows or 'all'}")


# ---------------------------------------------------------------------
# two kinds of mixer in one model, state pages beside the K/V pool
# (Qwen3-Next's share at its published widths: 32 state matrices of
# 128 x 128 a sequence and Gated DeltaNet layer, gated attention of 16
# query / 2 kv heads of 256, 64 of 512 experts of 2048 x 512 held)
# ---------------------------------------------------------------------

def _qwen3next_runner(topo, monkeypatch, periods=1):
    """The runner skeleton at the benchmark's Qwen3-Next file, cut to
    ``periods`` periods of four layers, 8 slots of 16384 tokens over
    the K/V pool and 9 state pages (the cell's geometry)."""
    import dataclasses
    import json
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.models.kv import cache_for
    from production_stack_tpu.ops.rope import rope_table
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "qwen3-next-80b-a3b-int8-l24-e64.json")) as f:
        conf = json.load(f)
    mcfg = dataclasses.replace(model_config(conf, "qwen3-next-share"),
                               num_layers=4 * periods)
    monkeypatch.setitem(model_configs.PRESETS, "qwen3-next-share", mcfg)
    runner, params, _, rep = _runner_shapes(
        topo, 1, kv_blocks=2049, model="qwen3-next-share")
    runner.engine_cfg = EngineConfig(
        model="qwen3-next-share", quantization="int8", max_num_seqs=8,
        max_model_len=16384, kv_pool_tokens=131072, prefill_chunk=2048)
    runner.rope = rope_table(16384, mcfg.rope_dim_, mcfg.rope_theta)
    cache = jax.tree.map(
        lambda x: rep(x.shape, x.dtype),
        jax.eval_shape(partial(cache_for, mcfg, 2049, 64, state_pages=9)))
    return runner, params, cache, rep


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk"])
def test_hybrid_step_program_compiles_at_qwen3next_widths(
        topo, tpu_branches, monkeypatch, program):
    """One decode window of 8 rows and one 2048-token prefill chunk of
    one row at the longest kv bucket, compiled whole for the described
    v5e: the delta rule is ops/gdn.py's kernel of the forward's kind,
    the attention the paged kernels' K/V case at 8 query heads a kv
    head of 256 (the chunk cut into q blocks), the experts the list and
    the grouped kernel; neither the K/V pool nor the state pool is
    copied or sliced (both aliased to the result); no period's slice
    of a parameter group is copied out before a sub-layer reads its
    row (as the scan's xs the three fused input projections of a
    period, 75 MB, were: PERF.md, PR 42); the program fits the
    chip."""
    import re
    P, N = 2, 2049
    runner, params, cache, rep = _qwen3next_runner(topo, monkeypatch, P)
    B = runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    tables = rep(runner.table_shape, jnp.int32)
    assert runner.table_shape == (8, 257)
    small = (a["sampling"], a["key"], a["guide_next"], a["guide_id"],
             a["guide_state"], a["counts"], a["seen"])
    if program == "decode_window":
        fn = jax.jit(partial(runner._decode_impl, steps=8, kv_len=16384,
                             greedy=True), donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables, rep((B,), jnp.int32),
                            rep((B,), jnp.int32), *small).compile()
        want = {"paged_decode_attention", "moe_list_experts",
                "gdn_recurrent_step"}
        path = "pallas_paged_decode"
    else:
        fn = jax.jit(partial(runner._prefill_impl, kv_len=16384),
                     donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables,
                            rep((1,), jnp.int32), rep((1, 2048), jnp.int32),
                            rep((1,), jnp.int32), rep((1,), jnp.int32),
                            *small).compile()
        want = {"paged_attention", "moe_grouped_experts", "moe_held_sum",
                "gdn_chunk_scan"}
        path = "pallas_paged"
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith(("paged", "moe", "gdn"))} \
        == want
    assert runner._attention_path(
        1 if program == "decode_window" else 2048, None, 16384) == path
    pools = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:{p},{n},2,{bs},256|{g},9,32,128,128)\]\S* "
        r"([\w\-]+)\(".format(p=P, n=N, bs=BS, g=3 * P))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pools.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    slices = re.findall(r"= s8\[3,2048,\d+\]\S* [\w\-]+\(", hlo)
    assert not slices, slices[:3]
    if program == "prefill_chunk":
        # the rule's substitution is the kernel's (ops/gdn._solve_stacked):
        # no XLA operation rewrites a layer's 4096 diagonal blocks a row
        # at a time (fifteen dynamic-update-slice a layer: PERF.md,
        # PR 49)
        rows = re.findall(
            r"= f32\[16,16,4096\]\S* dynamic-update-slice\(", hlo)
        assert not rows, rows[:3]
        # nor is any operand of the recurrence an array of the program
        # (PR 51: ``qg``, ``w``, ``kdT``, ``u``, ``attn`` [1, 32 heads,
        # 32 chunks, 64, .] are made in the kernel's VMEM)
        made = re.findall(r"(?:bf16|f32)\[1,32,32,(?:64|128),\d+\]", hlo)
        assert not made, made[:3]
        # the held experts' rounds (ops/moe._moe_grouped): nothing of a
        # chunk's 2048 x 10 assignments by the hidden width is built,
        # nor the buffer of 21 568 rows they were sorted into; a
        # round's buffer is 5120 + 64 x 15 + 128 rows
        from production_stack_tpu.ops import moe
        assert moe.held_block(2048, 10, 64, 512) == 5120
        big = re.findall(
            r"(?:bf16|f32)\[(?:\d+,)?(?:20480|21568),2048\]", hlo)
        assert not big, big[:3]
        assert re.search(r"bf16\[6208,2048\]", hlo)
        # and a round's sum by token is the kernel's one pass over the
        # block (``moe_held_sum``), no scatter into the float32 sum
        scattered = re.findall(r"= f32\[2048,2048\]\S* scatter\(", hlo)
        assert not scattered, scattered[:3]
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * P * N * 2 * BS * 256 * 2 + 3 * P * 9 * 32 * 128 * 128 * 4)
    _fits(compiled, f"qwen3-next share {program}")


@pytest.mark.parametrize("T,nb", [(2048, 256), (2048, 32), (512, 8)])
def test_kv_prefill_kernel_in_q_blocks_compiles_at_8_groups_of_256(
        topo, T, nb):
    """The prefill kernel's K/V case at 16 query / 2 kv heads of 256:
    a chunk whose whole q panel misses VMEM (paged_viable(2048, 8,
    256, 64) is false) is cut into q blocks of 512 against panels of
    512 keys (``prefill_tiles``) and compiles at the longest and a
    short kv bucket; attention_path keeps it on the kernel."""
    assert not pallas_paged.paged_viable(2048, 8, 256, 64)
    assert pallas_paged.paged_viable(256, 8, 256, 64)
    assert pallas_paged.prefill_tiles(T, 8, 256, nb, 64) == (512, 8)
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    pool = s((6, 2049, 2, 64, 256), jnp.bfloat16)
    was = pallas_paged._override
    pallas_paged._override = True
    try:
        assert pallas_paged.attention_path(T, 8, 256, 64) == "pallas_paged"
    finally:
        pallas_paged._override = was
    jax.jit(lambda q, k, v, t, st, lyr: pallas_paged.paged_attention(
        q, k, v, t, st, nb=nb, layer=lyr)).lower(
        s((1, T, 16, 256), jnp.bfloat16), pool, pool,
        s((1, 256), jnp.int32), s((1,), jnp.int32),
        s((), jnp.int32)).compile()


@pytest.mark.parametrize("T,hk,hv", [(2048, 16, 32), (512, 16, 32),
                                     (256, 16, 32), (320, 16, 32),
                                     (64, 2, 4), (640, 4, 4)])
def test_gdn_chunk_kernel_compiles_and_reads_in_place(topo, tpu_branches,
                                                      T, hk, hv):
    """The chunkwise rule of one row at Qwen3-Next's 16 key / 32 value
    heads of 128 (a 2048-token chunk: two grid steps of eight pairs of
    chunks a head; 512 and the probe's 256: four and two pairs; 320:
    padded to three) and at 4 heads (one pair; five with a key head a
    value head), compiled for the described v5e: ONE kernel,
    ``gdn_chunk_scan``, and around it neither a copy nor a transpose of
    q, k, v or o, which go in and come out as [1, T, heads x 128], the
    layout the projections leave and read: nothing of the transform
    (``qg``, ``w``, ``kdT``, ``u``, ``attn``, L, the decays) is an
    array of the program. The whole prefill executable:
    test_hybrid_step_program_compiles_at_qwen3next_widths."""
    import re
    from production_stack_tpu.ops import gdn
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def rule(q, k, v, g, beta, pool, ids, fresh):
        o, pool = gdn.mix(
            q.reshape(1, T, hk, 128), k.reshape(1, T, hk, 128),
            v.reshape(1, T, hv, 128), g, beta, pool, ids, jnp.int32(1),
            fresh)
        return o.reshape(1, T, hv * 128), pool
    hlo = jax.jit(rule, donate_argnums=5).lower(
        s((1, T, hk * 128)), s((1, T, hk * 128)), s((1, T, hv * 128)),
        s((1, T, hv), jnp.float32), s((1, T, hv), jnp.float32),
        s((2, 3, hv, 128, 128), jnp.float32), s((1,), jnp.int32),
        s((1,), jnp.bool_)).compile().as_text()
    calls = [m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)]
    assert calls == ["gdn_chunk_scan"]
    # g and beta [1, T, hv] may be fetched ahead (a copy between
    # memory spaces); a row that is no whole number of pairs is padded
    # (one fusion an operand), and nothing else is moved
    big = [m for m in re.findall(
        r"= (?:bf16|f32)\[1,\d+,\d+(?:,\d+)*\]\S* "
        r"(?:copy|transpose|fusion)\(", hlo)
        if not m.startswith(f"= f32[1,{T},{hv}]")]
    assert len(big) <= (5 if T % gdn._PAIR else 0), big


# ---------------------------------------------------------------------
# state pages alone (Brumby's cut at its published widths: 8 key-value
# heads of 128, five queries a head, a float32 state of 8256 monomials
# a head; no K/V pool)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("B,T,kernel", [
    (16, 1, "retention_recurrent_step"), (1, 256, "retention_chunk_scan"),
    (16, 256, "retention_chunk_scan"), (1, 2048, "retention_chunk_scan")])
def test_retention_kernels_compile_at_brumby_widths(topo, tpu_branches,
                                                    B, T, kernel):
    """ops/retention.py's two kernels at the cell's shapes (a decode
    step of 16 rows; a 256-token chunk of 1 and of 16 rows; the 2048
    tools/retention_chip_check.py runs) over the cell's pool of 17
    pages and 10 layers, compiled for the described v5e: the kernel of
    the forward's kind is in the program, both pools are aliased to the
    result (nothing of 5.8 GB is copied) and nothing of the monomials'
    size (``[tokens, 8256]``) is made in HBM."""
    import re
    from production_stack_tpu.ops import retention
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    L, P, H, G, D = 10, 17, 8, 5, 128
    F = retention.features(D)
    assert retention.retention_path(T) == kernel.rsplit("_", 1)[0]
    compiled = jax.jit(
        lambda q, k, v, g, state, norm, ids, fresh: retention.retain(
            q, k, v, g, state, norm, ids, 3, fresh),
        donate_argnums=(4, 5)).lower(
        s((B, T, H * G, D), jnp.bfloat16), s((B, T, H, D), jnp.bfloat16),
        s((B, T, H, D), jnp.bfloat16), s((B, T, H)),
        s((L, P, H, F, D)), s((L, P, F * H // D, D)),
        s((B,), jnp.int32), s((B,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith("retention")} == {kernel}
    pools = L * P * H * F * (D + 1) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= pools
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    assert not re.findall(r"\[(?:\d+,)*(?:8256|8320)(?:,\d+)*\]\S* "
                          r"(?:fusion|multiply|broadcast)\(", hlo)


@pytest.mark.parametrize("W", [4, 8])
def test_the_window_form_compiles_at_brumby_widths(topo, tpu_branches, W):
    """A decode window's form at the cell's shapes (16 rows, windows of
    4 and 8 steps, the pool of 17 pages and 10 layers), compiled for
    the described v5e: a step of one layer that only reads (under the
    recurrent step's name, no pool among its results) and the fold of
    every layer, the pools aliased to the result; nothing of the
    monomials' size is made in HBM, and the window's operands take
    what they hold (as ``[..., 1]`` a value took a tile: 671 MB)."""
    import re
    from production_stack_tpu.ops import retention
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    L, P, H, G, D, B = 10, 17, 8, 5, 128, 16
    F = retention.features(D)
    assert retention.retention_path(1, steps=W) == "retention_window"

    def window(q, k, v, g, state, norm, ids, fresh):
        win = retention.open_window(L, W, H, D, ids, fresh)
        y, *taken = retention.retain_in_window(
            q, k, v, g, win.k[3], win.v[3], win.G[3], win, state, norm, 3)
        win = win._replace(
            **{n: getattr(win, n).at[3].set(a) for n, a in zip("kvG", taken)},
            step=win.step + 1)
        return y, retention.fold_window(win, state, norm)
    compiled = jax.jit(window, donate_argnums=(4, 5)).lower(
        s((B, 1, H * G, D), jnp.bfloat16), s((B, 1, H, D), jnp.bfloat16),
        s((B, 1, H, D), jnp.bfloat16), s((B, 1, H)),
        s((L, P, H, F, D)), s((L, P, F * H // D, D)),
        s((B,), jnp.int32), s((B,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith("retention")} \
        == {"retention_recurrent_step", "retention_window_fold"}
    pools = L * P * H * F * (D + 1) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= pools
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26
    assert not re.findall(r"\[(?:\d+,)*(?:8256|8320)(?:,\d+)*\]\S* "
                          r"(?:fusion|multiply|broadcast|copy)\(", hlo)


@pytest.mark.parametrize("W", [8, 2])
def test_brumby_decode_window_compiles_with_state_pages_alone(
        topo, tpu_branches, monkeypatch, W):
    """One decode window of 16 rows at the benchmark's Brumby file cut
    to two layers, compiled whole for the described v5e as the runner
    lays such a model out: ONE table column (the page), one kv bucket,
    no K or V array among the program's arguments, the step kernel
    once a layer and step, the state pools aliased to the result and
    never copied. A window of 8 steps takes the window form (the steps
    read, ``retention_window_fold`` writes, once, after the scan); one
    of 2 the recurrent rule a step, as before."""
    import dataclasses
    import json
    import re
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.models import llama
    from production_stack_tpu.models.kv import cache_for
    from production_stack_tpu.ops.rope import rope_table
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "brumby-14b-int8-l10.json")) as f:
        conf = json.load(f)
    mcfg = dataclasses.replace(model_config(conf, "brumby-cut"),
                               num_layers=2)
    ecfg = EngineConfig(model="brumby-cut", quantization="int8",
                        max_num_seqs=16, max_model_len=32768,
                        prefill_chunk=256)
    # (ModelRunner.__init__ sets the page geometry; a skeleton has to)
    ecfg.kv_block_size, ecfg.kv_len_buckets = 32768, (32768,)
    runner = ModelRunner.__new__(ModelRunner)
    runner.model_cfg, runner.engine_cfg, runner.mesh = mcfg, ecfg, None
    runner._lora, runner._lora_scaling = None, 1.0
    runner.rope = rope_table(32768, mcfg.rope_dim_, mcfg.rope_theta)
    assert runner.table_shape == (16, 1) and ecfg.num_kv_blocks == 17
    one = SingleDeviceSharding(topo.devices[0])

    def rep(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def placed(tree):
        return jax.tree.map(lambda x: rep(x.shape, x.dtype), tree)
    params = placed(jax.eval_shape(
        partial(llama.init_params, mcfg, quantization="int8"),
        jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(partial(cache_for, mcfg, 17, 32768)))
    assert cache.k is None and cache.v is None
    a = _step_args(runner, rep, 16)
    fn = jax.jit(partial(runner._decode_impl, steps=W, kv_len=32768,
                         greedy=True), donate_argnums=(1,))
    compiled = fn.lower(
        params, cache, rep((16, 1), jnp.int32), rep((16,), jnp.int32),
        rep((16,), jnp.int32), a["sampling"], a["key"], a["guide_next"],
        a["guide_id"], a["guide_state"], a["counts"],
        a["seen"]).compile()
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith(("paged", "retention"))} \
        == ({"retention_recurrent_step", "retention_window_fold"}
            if W == 8 else {"retention_recurrent_step"})
    assert runner._mixer_path(1, W) == (
        "retention_window" if W == 8 else "retention_recurrent")
    assert runner._mixer_path(1) == "retention_recurrent"
    assert runner.state_pages_moved(W) == (10 if W == 8 else 4)
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * 17 * 8 * 8256 * 129 * 4)
    # the pool goes from the argument to the result through the scan of
    # read-only steps and the fold: no copy of its shape anywhere
    assert not re.findall(r"f32\[2,17,8,8256,128\]\S* copy\(", hlo)
    _fits(compiled, "brumby cut decode window")


def _phi4flash_runner(topo, monkeypatch):
    """The runner skeleton at the benchmark's Phi-4-mini-flash file,
    whole (three runs of periods: the scans make depth free), 8 slots
    of 16384 tokens over the nine-layer K/V pool and 9 state pages
    (the cell's geometry)."""
    import json
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.models.kv import cache_for
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "phi4-mini-flash-int8.json")) as f:
        conf = json.load(f)
    mcfg = model_config(conf, "phi4flash-whole")
    monkeypatch.setitem(model_configs.PRESETS, "phi4flash-whole", mcfg)
    monkeypatch.setattr(
        "production_stack_tpu.models.kv.cache_for",
        lambda cfg, n, bs, state_pages=0, **kw: cache_for(
            cfg, n, bs, state_pages=9, **kw))
    runner, params, _, rep = _runner_shapes(
        topo, 1, kv_blocks=2049, model="phi4flash-whole")
    runner.engine_cfg = EngineConfig(
        model="phi4flash-whole", quantization="int8", max_num_seqs=8,
        max_model_len=16384, kv_pool_tokens=131072, prefill_chunk=2048)
    cache = jax.tree.map(
        lambda x: rep(x.shape, x.dtype),
        jax.eval_shape(partial(cache_for, mcfg, 2049, 64, state_pages=9)))
    return runner, params, cache, rep


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk"])
def test_plan_step_program_compiles_at_phi4flash_widths(
        topo, tpu_branches, monkeypatch, program):
    """One decode window of 8 rows and one 2048-token prefill chunk of
    one row at the longest kv bucket of the WHOLE model (32 layers in
    three runs of periods), compiled for the described v5e: the scan is
    ops/mamba.py's kernel of the forward's kind, the attention the
    paged kernels' plain K/V case at 4 query heads a pool head of 128
    (differential attention's pairs: models/llama._diff_attention), a
    window of 512 in eight layers; a prefill chunk calls the DECODE
    kernel too, in its seven cross layers at one position a row;
    neither the nine-layer K/V pool nor the state pool is copied or
    sliced (both aliased to the result, through the ``finishing``
    conditional too); the program fits the chip beside 3.85 GB of
    weights and the 6 GB pool. ~40 s each: 32 layers' worth of three
    scan bodies at 2560 wide."""
    import re
    N = 2049
    runner, params, cache, rep = _phi4flash_runner(topo, monkeypatch)
    B = runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    tables = rep(runner.table_shape, jnp.int32)
    assert runner.table_shape == (8, 257)
    small = (a["sampling"], a["key"], a["guide_next"], a["guide_id"],
             a["guide_state"], a["counts"], a["seen"])
    if program == "decode_window":
        fn = jax.jit(partial(runner._decode_impl, steps=8, kv_len=16384,
                             greedy=True), donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables, rep((B,), jnp.int32),
                            rep((B,), jnp.int32), *small).compile()
        want = {"paged_decode_attention", "mamba_recurrent_step"}
    else:
        fn = jax.jit(partial(runner._prefill_impl, kv_len=16384),
                     donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables,
                            rep((1,), jnp.int32), rep((1, 2048), jnp.int32),
                            rep((1,), jnp.int32), rep((1,), jnp.int32),
                            *small, rep((), jnp.bool_)).compile()
        want = {"paged_attention", "paged_decode_attention",
                "mamba_chunk_scan"}
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)[\w.\-]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith(("paged", "mamba"))} == want
    assert runner._attention_path(
        1 if program == "decode_window" else 2048, None, 16384) \
        == ("pallas_paged_decode" if program == "decode_window"
            else "pallas_paged")
    pools = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:9,{n},10,{bs},128|9,9,16,5120)\]\S* "
        r"([\w\-]+)\(".format(n=N, bs=BS))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pools.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * 9 * N * 10 * BS * 128 * 2 + 9 * 9 * 16 * 5120 * 4)
    _fits(compiled, f"phi4flash whole {program}")


# the kernels of Phi-4-mini-flash's decode window at the parent of PR 58
# (commit 5c6c63b; sha256 of each body's MLIR without debug info, taken
# by _kernel_bodies on that tree): the Mamba step (two call sites), the
# decode kernel as the window layers call it and as the full layer and
# the cross readers call it. 473 962 characters of MLIR in four sites
PHI4_DECODE_BODIES = {
    "mamba_recurrent_step":
    "ab7863d1c195921b",
    "paged_decode_attention, a window of 512":
    "8ca008f073d5f507",
    "paged_decode_attention, full causal":
    "bf1c46efc272dc6c",
}
PHI4_DECODE_BODY_CHARS = 473962
PHI4_PREFILL_DIGEST = \
    "5dc59cff617ee7f71813daf3159058e598600cb03da2d5195dcf21429f38b057"


def test_phi4flash_decode_build_lowers_the_parents_kernels_and_two_writers(
        topo, tpu_branches, monkeypatch):
    """What refused PR 57, held without the chip. A Pallas kernel is
    lowered to Mosaic once a CALL SITE of every executable a start
    builds, warm or cold, and Phi-4-mini-flash's start builds nine
    decode windows: PR 57 wrote the K/V append into the decode kernel's
    body, unrolled over heads and blocks, each of the nine lowered 1.3 s
    longer and the cell's ``setup_s`` rose 13 % (PERF.md section 6).
    Here the decode window of the whole model (8 steps, the 16 384
    bucket) is lowered for the described v5e and its kernels are read:
    the parent's four call sites with the parent's bodies, bit for bit
    (a call that appends nothing, the cross readers', lowers what it
    lowered), and TWO more, the writer (ops/pallas_paged.append_rows)
    in the window layers and in the full layer, 47 064 characters of
    MLIR each: a fifth of the decode kernel's, whatever the heads, rows
    and blocks. And the 2048-token prefill chunk lowers to the parent's
    text byte for byte. A PR that means to change these kernels pins
    its own here and says so."""
    runner, params, cache, rep = _phi4flash_runner(topo, monkeypatch)
    B = runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    tables = rep(runner.table_shape, jnp.int32)
    small = (a["sampling"], a["key"], a["guide_next"], a["guide_id"],
             a["guide_state"], a["counts"], a["seen"])
    lowered = jax.jit(partial(runner._decode_impl, steps=8, kv_len=16384,
                              greedy=True), donate_argnums=(1,)).lower(
        params, cache, tables, rep((B,), jnp.int32), rep((B,), jnp.int32),
        *small)
    bodies = _kernel_bodies(lowered)
    parents = set(PHI4_DECODE_BODIES.values())
    shas = [sha[:16] for sha, _ in bodies]
    assert set(shas) >= parents, bodies
    writers = [sha for sha in shas if sha not in parents]
    assert len(bodies) == 6 and len(writers) == 2, bodies
    assert len(set(writers)) == 1, bodies
    assert sum(n for _, n in bodies) <= PHI4_DECODE_BODY_CHARS + 100_000
    prefill = jax.jit(partial(runner._prefill_impl, kv_len=16384),
                      donate_argnums=(1,)).lower(
        params, cache, tables, rep((1,), jnp.int32),
        rep((1, 2048), jnp.int32), rep((1,), jnp.int32),
        rep((1,), jnp.int32), *small, rep((), jnp.bool_))
    assert _program_digest(prefill) == PHI4_PREFILL_DIGEST


def _nemotron_runner(topo, monkeypatch):
    """The runner skeleton at the benchmark's Nemotron-3-Nano file,
    whole (52 blocks in four runs of periods), 8 slots of 16384 tokens
    over the six-layer K/V pool and 9 state pages (the cell's
    geometry)."""
    import json
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.models.kv import cache_for
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench", "configs",
            "nemotron-3-nano-30b-a3b-int8-e32.json")) as f:
        conf = json.load(f)
    mcfg = model_config(conf, "nemotron-share")
    monkeypatch.setitem(model_configs.PRESETS, "nemotron-share", mcfg)
    monkeypatch.setattr(
        "production_stack_tpu.models.kv.cache_for",
        lambda cfg, n, bs, state_pages=0, **kw: cache_for(
            cfg, n, bs, state_pages=9, **kw))
    runner, params, _, rep = _runner_shapes(
        topo, 1, kv_blocks=2049, model="nemotron-share")
    runner.engine_cfg = EngineConfig(
        model="nemotron-share", quantization="int8", max_num_seqs=8,
        max_model_len=16384, kv_pool_tokens=131072, prefill_chunk=2048)
    cache = jax.tree.map(
        lambda x: rep(x.shape, x.dtype),
        jax.eval_shape(partial(cache_for, mcfg, 2049, 64, state_pages=9)))
    return runner, params, cache, rep


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk"])
def test_sublayer_step_program_compiles_at_nemotron_widths(
        topo, tpu_branches, monkeypatch, program):
    """One decode window of 8 rows and one 2048-token prefill chunk of
    one row at the longest kv bucket of ALL 52 blocks (four runs of
    periods, 14 traced sublayers), compiled for the described v5e: the
    scan is ops/mamba2.py's kernel of the forward's kind (the chunked
    form's products on the matrix unit), the attention the paged
    kernels' plain K/V case at 16 query heads a pool head of 128, the
    experts WITHOUT a gate the list and the grouped kernel with the
    held share's rounds, their stacks (stored 1920 wide) read in place
    from inside the plan run; neither the six-layer K/V pool nor the
    state pool is copied or sliced (both aliased to the result), nor a
    layer of the expert stacks; the program fits the chip beside 9.1 GB
    of weights and the pools."""
    import re
    N = 2049
    runner, params, cache, rep = _nemotron_runner(topo, monkeypatch)
    B = runner.engine_cfg.max_num_seqs
    a = _step_args(runner, rep, B)
    tables = rep(runner.table_shape, jnp.int32)
    assert runner.table_shape == (8, 257)
    small = (a["sampling"], a["key"], a["guide_next"], a["guide_id"],
             a["guide_state"], a["counts"], a["seen"])
    if program == "decode_window":
        fn = jax.jit(partial(runner._decode_impl, steps=8, kv_len=16384,
                             greedy=True), donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables, rep((B,), jnp.int32),
                            rep((B,), jnp.int32), *small).compile()
        want = {"paged_decode_attention", "moe_list_experts",
                "mamba2_recurrent_step"}
        path, moe_path = "pallas_paged_decode", "list"
    else:
        fn = jax.jit(partial(runner._prefill_impl, kv_len=16384),
                     donate_argnums=(1,))
        compiled = fn.lower(params, cache, tables,
                            rep((1,), jnp.int32), rep((1, 2048), jnp.int32),
                            rep((1,), jnp.int32), rep((1,), jnp.int32),
                            *small).compile()
        want = {"paged_attention", "moe_grouped_experts", "moe_held_sum",
                "mamba2_chunk_scan"}
        path, moe_path = "pallas_paged", "grouped"
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_0-9]+?)[.\-\d]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls
            if c.startswith(("paged", "moe", "mamba"))} == want
    positions = 1 if program == "decode_window" else 2048
    assert runner._attention_path(positions, None, 16384) == path
    runner.params = params      # (_moe_path asks the stacks' dtype)
    assert runner._moe_path(8 if positions == 1 else 1, positions) \
        == moe_path
    assert runner._mixer_path(positions) == (
        "mamba2_recurrent_step" if positions == 1 else "mamba2_chunk_scan")
    pools = re.compile(
        r"([\w.\-]+) = \(?\w+\[(?:6,{n},2,{bs},128|23,9,128,4096|"
        r"23,32,2688,1920|23,32,1920,2688)\]\S* "
        r"([\w\-]+)\(".format(n=N, bs=BS))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pools.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    # no expert layer's slice of the stacks is copied out either
    slices = re.findall(r"= s8\[32,(?:2688,1920|1920,2688)\]\S* [\w\-]+\(",
                        hlo)
    assert not slices, slices[:3]
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * 6 * N * 2 * BS * 128 * 2 + 23 * 9 * 128 * 4096 * 4)
    _fits(compiled, f"nemotron share {program}")


@pytest.mark.parametrize("rows,positions,kernel", [
    (8, 1, "moe_list_experts"), (1, 2048, "moe_grouped_experts")])
def test_ungated_expert_kernels_at_nemotron_widths(
        topo, tpu_branches, monkeypatch, rows, positions, kernel):
    """The list and the grouped kernel on experts WITHOUT a gate (two
    matrices a slot, relu^2), a share of 32 behind a 128-wide sigmoid
    router, hidden 2688: STORED 1920 wide they compile for the described
    v5e; at the published 1856 = 14.5 x 128 the compiler refuses the
    expert's copy out of the stacks (an int8 array 1856 wide lies 1920
    wide in HBM's tiles anyway, so the stored width costs no byte
    more): why the stacks are stored padded (PERF.md, PR 54)."""
    from production_stack_tpu.ops import moe
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def lower(inter):
        up = {"w8": shaped((2, 32, 2688, inter), jnp.int8),
              "scale": shaped((2, 32, inter), jnp.float32)}
        down = {"w8": shaped((2, 32, inter, 2688), jnp.int8),
                "scale": shaped((2, 32, 2688), jnp.float32)}
        return jax.jit(lambda x, rw, bias, up, down, layer: moe.moe_mlp(
            x, rw, None, up, down, top_k=6, act=moe.relu2,
            exact=True if positions == 1 else None, layer=layer,
            positions=positions, router_score="sigmoid", router_bias=bias,
            routed_scale=2.5)).lower(
                shaped((rows * positions, 2688), jnp.bfloat16),
                shaped((2688, 128), jnp.bfloat16),
                shaped((128,), jnp.float32), up, down,
                shaped((), jnp.int32))

    hlo = lower(1920).compile().as_text()
    assert kernel in hlo
    assert moe.expert_tiles(2688, 1856, jnp.int8, jnp.bfloat16,
                            gated=False) == 0
    tiles = moe.expert_tiles
    monkeypatch.setattr(moe, "expert_tiles",
                        lambda h, i, *a, **k: 1 if i == 1856
                        else tiles(h, i, *a, **k))
    with pytest.raises(Exception, match="aligned to tiling"):
        lower(1856).compile()


# ---------------------------------------------------------------------
# a looped model (Ouro-2.6B whole: 48 weight layers run four times over
# 192 pool layers, 16 query heads on 16 pool heads of 128)
# ---------------------------------------------------------------------

def _ouro_runner(topo, monkeypatch):
    """The runner skeleton at the benchmark's Ouro-2.6B file, whole (48
    layers, four passes), 16 slots of 2048 tokens over a pool of 97
    blocks in 192 pool layers (the cell's geometry)."""
    import json
    from chipbench.engine_child import model_config
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as model_configs
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "ouro-2.6b-int8.json")) as f:
        conf = json.load(f)
    monkeypatch.setitem(model_configs.PRESETS, "ouro-whole",
                        model_config(conf, "ouro-whole"))
    runner, params, cache, rep = _runner_shapes(
        topo, 1, kv_blocks=98, model="ouro-whole")
    runner.engine_cfg = EngineConfig(
        model="ouro-whole", quantization="int8", max_num_seqs=16,
        max_model_len=2048, kv_pool_tokens=6208, prefill_chunk=256)
    return runner, params, cache, rep


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk"])
def test_looped_step_program_compiles_at_ouro_widths(
        topo, tpu_branches, monkeypatch, program):
    """One decode window of 16 rows and one 256-token prefill chunk of
    16 rows of ALL 48 layers in four passes (one traced layer body, one
    traced pass), compiled for the described v5e: the attention is the
    paged kernels' plain K/V case at one query head a pool head of 128
    with a pool-layer index up to 191; the 192-layer pool is neither
    copied nor sliced (aliased to the result); the passes and the exit
    gate carry their scopes; the program fits the chip beside 2.67 GB of
    weights and 9.9 GB of pool. (The prefill chunk takes 30-50 s: a whole
    step program of two nested scans through the TPU's compiler, as the
    other families' whole programs here do.)"""
    import re
    N = 98
    runner, params, cache, rep = _ouro_runner(topo, monkeypatch)
    assert cache.k.shape == (192, N, 16, BS, 128)
    if program == "decode_window":
        compiled = _lower_decode_window(runner, params, cache,
                                        rep).compile()
        want, path, positions = "paged_decode_attention", \
            "pallas_paged_decode", 1
    else:
        compiled = _lower_prefill_chunk(runner, params, cache, rep,
                                        256).compile()
        want, path, positions = "paged_attention", "pallas_paged", 256
    hlo = compiled.as_text()
    calls = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_0-9]+?)[.\-\d]* = [^=]*? custom-call\(", hlo)}
    assert {c for c in calls if c.startswith(("paged", "moe"))} == {want}
    assert runner._attention_path(positions, None, 512) == path
    # (a prefill chunk hands the gate's value to nobody, and the
    # compiler drops it: the decode windows count the passes)
    for scope in ("loop_pass", "final_norm", "lm_head") + (
            ("exit_gate",) if positions == 1 else ()):
        assert scope in hlo, scope
    pools = re.compile(
        r"([\w.\-]+) = \(?\w+\[192,{n},16,{bs},128\]\S* "
        r"([\w\-]+)\(".format(n=N, bs=BS))
    moved = [m.group(1) + ": " + m.group(2)
             for m in map(pools.search, hlo.splitlines()) if m
             and re.search(r"copy|dynamic.slice|dynamic.update.slice",
                           m.group(1) + " " + m.group(2))]
    assert not moved, moved
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * 192 * N * 16 * BS * 128 * 2)
    _fits(compiled, f"ouro whole {program}")
