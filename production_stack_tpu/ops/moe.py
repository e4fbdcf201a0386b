"""Mixture-of-experts MLP: top-k routing with static-shape dispatch.

TPU-first design (the reference stack has no model code — MoE models are
strings passed to ``vllm serve``, reference:
helm/templates/deployment-vllm-multi.yaml:57-64; expert parallelism is a
``--enable-expert-parallel``-style engine passthrough, SURVEY.md §2.9):

- Routing, dispatch and combine are all static-shape jnp — no
  data-dependent shapes, so the whole block lives inside the engine's
  jitted prefill/decode executables and XLA can schedule it.
- Four strategies, chosen at trace time from the shapes (rows and
  positions, so the token count N; experts E, top-k, the experts'
  widths) and the mesh; no option selects one. ``list_path`` and
  ``grouped_path`` are the rules for the two that read the experts in
  place (models/llama.forward asks them, to hand the stacks over
  whole); where both say no, ``moe_mlp`` chooses between the other two
  by the tokens and the capacity; ``moe_path`` names the outcome
  (engine/runner.py records it per executable: GET /debug/perf
  ``device.moe_paths``):

  **Exact (small N).** Every expert runs over all N tokens and results
  are combined with the routing weights ([N, E], zero for unselected
  experts). It is exact (no token is ever dropped) and streams every
  expert's weights once, whatever the rows chose. How much of that a
  perfect dispatch would read is the expected share of experts hit,
  1 - (1 - 1/E)^(N k) under even routing: 99 % for Mixtral's 8 experts
  top-2 at 16 rows (nothing to gain), 66 % for Qwen1.5-MoE's 60 top-4
  at 16 rows (a third of the bytes are multiplied by a combine weight
  of zero), 99 % at 64 rows.

  **List (a decode step whose experts fit VMEM).** The same sum over
  only the experts that a valid row chose: ``experts_hit`` compacts
  their ids, a Pallas kernel walks the list and copies those experts'
  weights alone out of the stacks, in place (``_moe_list``, further
  down). ``list_path`` is the rule: one position a row, at most
  DENSE_THRESHOLD rows, nothing sharded, two slots of an expert's
  matrices fit VMEM (Mixtral-8x7B's do not: the exact path), the
  kernels on (pallas_paged.flash_enabled: not on the CPU). As exact as
  the exact path: an expert no valid row chose contributes exactly
  zero there.

  **Grouped (a prefill chunk whose experts fit VMEM).** Every expert
  over the rows routed to it and no others: the N k assignments are
  laid out by expert in one buffer (each expert's segment starting at
  a multiple of the rows a copy is aligned to; the assignments of
  invalid tokens left out), one Pallas call a layer walks the experts
  that have a row, copying each one's weights as the list kernel does
  and multiplying its segment in passes of GROUPED_ROWS rows, and each
  assignment's row is gathered back, weighed and the k terms summed in
  float32 (``_moe_grouped``, further down). ``grouped_path`` is the
  rule: a prefill chunk (more positions a row than the decode
  attention kernel takes), and what the list path needs of the mesh,
  the widths, VMEM and the kernels. Nothing is dropped at any routing: the exact path's
  sum with the zeros left out, at 1/15 (Qwen1.5-MoE) to 1/16
  (GLM-4.7-Flash) of its arithmetic. Where the layer holds a SHARE of
  its router's experts (below) only the assignments that name a held
  expert are laid out at all: their list is compacted, and the
  grouping, the kernel and the sum run on a static block of it at a
  time, in as many rounds as the routing filled; a round's sum by
  token is a second Pallas call, one pass over the block
  (``_held_sum``).

  **Capacity dispatch (large N where the kernels are off or the mesh
  shards the experts).** The GShard/Switch
  pattern reshaped for scatter/gather instead of [N, E, C] one-hots:
  each (token, choice) assignment gets a rank within its expert (an
  O(N*k*E) cumsum — integers, negligible next to the FFN matmuls) and
  is scattered into a per-expert [capacity, h] buffer; experts run as
  one batched [E, C, h] matmul; results gather back and combine.
  Assignments ranked past capacity are dropped — their combine weight
  contributes nothing and the token rides the residual stream, the
  standard capacity-factor tradeoff. ``capacity_factor`` ≥ E/k makes
  dropping impossible (capacity = N) at dense-compute cost. Padding
  tokens (``valid`` mask: the engine's full-batch prefill pads idle
  rows and short chunks) are excluded from ranking entirely, so they
  can never crowd real tokens out of an expert.

- Expert weights are stacked [E, h, i] / [E, i, h]: under expert
  parallelism parallel/sharding.py shards the leading E axis over the
  mesh's 'ep' axis (and the i axis over 'tp'), so each device's FFN
  matmul touches only its resident experts and XLA inserts the
  dispatch/combine collectives from the sharding annotations.

- **The chip's share of the experts.** Where a deployment divides an
  expert layer over several chips, the router keeps its width and the
  stacks hold the experts of this chip alone: ``moe_mlp`` is told
  where they start (``expert_offset``), routes over all of the
  router's experts, and adds only what its own experts give for the
  tokens routed to them. An assignment to an expert held elsewhere
  keeps its place in the renormalised weights and contributes nothing
  here (what that chip would add is left out, as in the reference:
  no code stands in for it); the list of experts hit, the grouped rows
  and ``Work`` count held experts only. The grouped path sizes its
  buffer, its gathers and its sum by the assignments that landed here
  (``held_block``, ``_moe_grouped``), an eighth or a sixteenth of a
  chunk's in the deployments measured, not by all N k.
- **Experts read in tiles.** Where two slots of an expert's matrices
  miss the kernels' share of VMEM, the list and the grouped kernel
  take the expert in ``expert_tiles`` tiles of its intermediate width
  (SwiGLU is elementwise over it and ``down`` sums over it: a tile is
  a narrower expert with the same routing weight), decided from the
  shapes by the rule that decides whether the kernels run at all.

- **Experts without a gate.** ``gate`` None (static: decided by the
  parameters, before anything compiles): an expert is ``down(act(up(
  x)))``, two matrices (Nemotron-H's, with ``relu2``); every path, the
  scratch rule and the tiling count two matrices a slot then, and what
  they trace for an expert with a gate is as it was.

Routing follows Mixtral semantics: fp32 softmax over all experts, then
top-k, then renormalize the selected probabilities to sum to 1; Qwen's
(raw probabilities) and GLM-4.7-Flash's (sigmoid scores, a bias in the
selection alone, a routing scale) are ``route``'s arguments.
"""

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged


class Work(NamedTuple):
    """What a call's experts did (int32 scalars; models/llama.forward
    sums them over the layers). The last two count the rounds of the
    grouped path where the layer holds a share of its router's experts
    (``_moe_grouped``), and read 0 on every other path."""
    experts_read: jnp.ndarray       # experts whose weights were read
    expert_rows: jnp.ndarray        # rows the experts multiplied
    held_rows: jnp.ndarray          # assignments the rounds worked through
    rounds: jnp.ndarray             # rounds (blocks of assignments) run


def _work(experts_read, expert_rows, held_rows=0, rounds=0) -> Work:
    return Work(*(jnp.asarray(n, jnp.int32) for n in
                  (experts_read, expert_rows, held_rows, rounds)))


def capacity_for(n_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert token capacity: factor × the perfectly-balanced load,
    8-aligned (TPU sublane), clamped to [8, n_tokens]."""
    balanced = n_tokens * top_k / num_experts
    cap = int(-(-capacity_factor * balanced // 8) * 8)
    return max(8, min(cap, n_tokens))


def route(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
          renormalize: bool = True, score: str = "softmax",
          bias=None, scale: float = 1.0):
    """Top-k routing. x [N, h], router_w [h, E] ->
    (weights [N, k] fp32, expert ids [N, k] int32). renormalize=True is
    Mixtral semantics (selected weights re-sum to 1); False keeps the
    raw softmax probabilities (Qwen2-MoE's norm_topk_prob=False).

    GLM-4.7-Flash / DeepSeek-V3 (``noaux_tc``): score="sigmoid" scores
    each expert by itself; bias [E] (e_score_correction_bias) is added
    for the SELECTION alone, the weights are the chosen experts' scores
    without it; renormalised with the publication's 1e-20 in the
    denominator; then times ``scale`` (routed_scaling_factor)."""
    logits = jnp.einsum("nh,he->ne", x, router_w,
                        preferred_element_type=jnp.float32)
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"router score {score!r}")
    sigmoid = score == "sigmoid"
    probs = (jax.nn.sigmoid(logits) if sigmoid
             else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        top_p, top_i = jax.lax.top_k(probs, top_k)
    else:
        _, top_i = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    if renormalize:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / (total + 1e-20 if sigmoid else total)
    if scale != 1.0:
        top_p = top_p * scale
    return top_p, top_i.astype(jnp.int32)


def _quant():
    """models/quant.py, imported lazily: models/ imports ops/, so a
    top-level import here would cycle. By the first moe_mlp trace both
    packages are fully initialized."""
    from production_stack_tpu.models import quant
    return quant


def _wshape(w) -> tuple:
    """Shape of a raw or int8-quantized weight."""
    return (w["w8"] if _quant().is_quantized(w) else w).shape


def stored_dtype(w):
    """Dtype a raw or int8-quantized weight is stored in."""
    return (w["w8"] if _quant().is_quantized(w) else w).dtype


def _edot(xb: jnp.ndarray, w) -> jnp.ndarray:
    """einsum('ec?,e?o->eco') with weight-only int8 dequant applied in
    the epilogue (per-expert, per-output-channel scale)."""
    if _quant().is_quantized(w):
        y = jnp.einsum("eci,eio->eco", xb, w["w8"].astype(xb.dtype))
        return y * w["scale"].astype(xb.dtype)[:, None, :]
    return jnp.einsum("eci,eio->eco", xb, w)


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    """relu(x) squared: the activation of an expert without a gate
    (Nemotron-H's ``mlp_hidden_act`` "relu2")."""
    return jnp.square(jax.nn.relu(x))


def _expert_ffn(xb: jnp.ndarray, gate, up, down,
                act: Callable) -> jnp.ndarray:
    """Batched per-expert FFN. xb [E, C, h] -> [E, C, h]; ``gate``
    None: ``down(act(up(x)))``."""
    if gate is None:
        return _edot(act(_edot(xb, up)), down)
    g = _edot(xb, gate)
    u = _edot(xb, up)
    return _edot(act(g) * u, down)


def _moe_exact(x, top_p, top_i, gate, up, down, act):
    """All experts over all tokens, combined by routing weight."""
    N = x.shape[0]
    E = _wshape(up)[0]
    with jax.named_scope("moe_experts"):
        xb = jnp.broadcast_to(x, (E,) + x.shape)        # [E, N, h]
        y_e = _expert_ffn(xb, gate, up, down, act)      # [E, N, h]
    with jax.named_scope("moe_combine"):
        # combine [N, E]: routing weight where selected, else 0
        combine = jnp.zeros((N, E), jnp.float32)
        # (an expert held elsewhere is named E: out of range, dropped)
        combine = combine.at[
            jnp.arange(N)[:, None], top_i].set(top_p, mode="drop")
        return jnp.einsum("enh,ne->nh", y_e,
                          combine.astype(x.dtype))


# ---------------------------------------------------------------------
# the list path: a decode step reads only the experts its rows chose.
#
# ``experts_hit`` compacts the distinct expert ids that a valid row
# selected to the front of a static-length vector, with their count;
# ``_moe_list`` hands that list, the count and the layer index to one
# Pallas call as scalar-prefetched operands. The expert stacks
# [L, E, h, i] / [L, E, i, h] stay in HBM, whole (a custom call cannot
# fuse a slice of its operand: handed one layer it would be handed a
# copy of it, so models/llama.py closes over the stacks and passes the
# layer's index, as models/kv.py does with the KV pool). The kernel
# walks the list with a dynamic trip count; per listed expert it copies
# the three matrices into one of two VMEM slots (the next expert's
# copies run under this one's arithmetic), converts them to the
# activation dtype there, computes ``act(x @ gate) * (x @ up) @ down``
# for all N rows
# with the per-channel scales applied to the float32 products, weighs
# the rows by the expert's combine column (zero for a row that did not
# choose it) and accumulates [N, h] in float32. An expert off the list
# costs no copy and no arithmetic.
# ---------------------------------------------------------------------

# the decode batches the list path takes: the rows at which moe_mlp
# itself takes the exact path, whose sum the list path computes
DENSE_THRESHOLD = 64

# the grouped path (a prefill chunk's experts, further down): the rows
# of an expert's segment that one pass of its three matmuls takes (the
# MXU's 128 rows: a pass over fewer costs the same weight loads)
GROUPED_ROWS = 128

# the grouped path where the layer holds a share of its router's
# experts: the block of assignments one round takes, in even shares
# (the assignments of a chunk that land here when the routing is even:
# N k E / the router's experts); rounded up to GROUPED_ROWS. Two: one
# round at any routing near even, and the smallest block whose worst
# case (every assignment here, N k / block rounds that each read the
# hit experts again) stays under twice the time of the path sized by
# N k: 1.6 x at Qwen3-Next's share, 1.8 x at GLM-5's, where one share
# reads 2.2 x and 2.5 x and four cost 0.5-0.9 ms a layer more at even
# routing (tools/moe_prefill_table.py --held; PERF.md, PR 44)
HELD_BLOCK_SHARES = 2

# the share of pallas_paged.VMEM_LIMIT_BYTES the kernel's scratch may
# take; the rest is the compiler's (the float32 products, the rows).
# The largest compiled: Qwen1.5-MoE's experts in bfloat16, 0.33
_LIST_VMEM_SHARE = 0.5


def list_scratch_bytes(hidden: int, inter: int, weight_dtype,
                       act_dtype, gated: bool = True) -> int:
    """VMEM the list kernel holds for experts of [hidden, inter]: two
    slots of gate, up and down as stored (``gated`` False: of up and
    down), and one matrix converted to the activation dtype (none where
    the weights already are)."""
    stored = jnp.dtype(weight_dtype)
    act = jnp.dtype(act_dtype)
    converted = act.itemsize if stored != act else 0
    return hidden * inter * (2 * (3 if gated else 2) * stored.itemsize
                             + converted)


# the most tiles an expert is read in: beyond it a tile's copies are
# too short to hide what starting them costs (not measured; Mixtral's
# 4096 x 14336 would want 16)
_MAX_TILES = 8


def expert_tiles(hidden: int, inter: int, weight_dtype, act_dtype,
                 gated: bool = True) -> int:
    """In how many tiles of its intermediate width the kernels that
    read experts in place take one expert: the fewest equal tiles, a
    power of two of them and each a multiple of the 128 lanes wide, of
    which two slots fit the kernels' share of VMEM
    (``list_scratch_bytes`` of a tile). 1: the expert whole
    (Qwen1.5-MoE's 2048 x 1408 in int8, 23 MB; GLM-4.7-Flash's 25 MB);
    2: GLM-5's 6144 x 2048, 101 MB whole and 50 a half; 0: no such
    tiling within _MAX_TILES (Mixtral-8x7B's 4096 x 14336, 470 MB)."""
    if hidden % 128 or inter % 128:
        return 0
    tiles = 1
    while tiles <= _MAX_TILES:
        if inter % (tiles * 128) == 0 and list_scratch_bytes(
                hidden, inter // tiles, weight_dtype, act_dtype, gated
                ) <= _LIST_VMEM_SHARE * pallas_paged.VMEM_LIMIT_BYTES:
            return tiles
        tiles *= 2
    return 0


def _experts_in_vmem(hidden: int, inter: int, weight_dtype, act_dtype,
                     mesh, gated: bool = True) -> bool:
    """What both kernels that read experts in place need (the list
    path's and the grouped path's): the Pallas kernels run at all
    (pallas_paged.flash_enabled: compiled on a TPU, off on the CPU,
    interpret mode where a test forces it), no mesh axis shards
    anything, the widths are multiples of the 128 lanes, and two slots
    of one expert's matrices, or of one of its tiles, fit VMEM
    (``expert_tiles``)."""
    return (pallas_paged.flash_enabled()
            and (mesh is None
                 or all(size == 1 for size in mesh.shape.values()))
            and expert_tiles(hidden, inter, weight_dtype, act_dtype,
                             gated) > 0)


def list_path(rows: int, positions: int, hidden: int, inter: int,
              weight_dtype, act_dtype, mesh=None,
              gated: bool = True) -> bool:
    """Do the expert matmuls of a forward over ``positions`` tokens of
    each of ``rows`` rows walk the list of experts hit (``_moe_list``)?
    Decided here, from the shapes and the mesh, at trace time: a decode
    step (one position a row) of at most DENSE_THRESHOLD rows whose
    experts can be read in place (``_experts_in_vmem``). Elsewhere
    ``grouped_path`` is asked (prefill chunks), then today's paths.
    No expected share of experts hit enters: with every expert on the
    list the kernel takes at most 1 % longer than ``_moe_exact``
    (PERF.md, PR 34), with fewer it takes less. models/llama.forward
    asks both rules to know whether to hand the stacks over whole."""
    return (positions == 1 and rows <= DENSE_THRESHOLD
            and _experts_in_vmem(hidden, inter, weight_dtype, act_dtype,
                                 mesh, gated))


def grouped_path(rows: int, positions: int, hidden: int, inter: int,
                 weight_dtype, act_dtype, mesh=None,
                 gated: bool = True) -> bool:
    """Do the expert matmuls of that forward run grouped: every expert
    over the rows routed to it and no others (``_moe_grouped``)?
    Decided as ``list_path`` is: a prefill chunk, which is more
    positions a row than the decode attention kernel takes
    (pallas_paged.DECODE_T_MAX: a speculative window's draft + 1
    positions keep the exact path, and no cell runs one), and experts
    that can be read in place (``_experts_in_vmem``, the list kernel's
    clause: the grouped kernel holds the same two slots). No token
    count enters: at every chunk bucket from 16 tokens up it was
    level with the exact path or faster (PERF.md, PR 39). Nothing is
    dropped at any routing, so no capacity enters. Whether the layer
    holds all of its router's experts or a share does not enter
    either: the path then sizes its work by the assignments that land
    on the share, in rounds of ``held_block`` of them
    (``_moe_grouped``; PERF.md, PR 44), and is the same path."""
    return (positions > pallas_paged.DECODE_T_MAX
            and _experts_in_vmem(hidden, inter, weight_dtype, act_dtype,
                                 mesh, gated))


def moe_path(rows: int, positions: int, num_experts: int, top_k: int,
             hidden: int, inter: int, weight_dtype, act_dtype, mesh=None,
             capacity_factor: float = 2.0, capacity_tokens=None,
             gated: bool = True) -> str:
    """The strategy the experts of that forward take, as
    models/llama.py calls ``moe_mlp`` (a decode step exact): "list",
    "grouped", "exact" or "dispatch"; "list_tiled<n>" / "grouped_tiled<n>"
    where the kernel takes an expert in n tiles (``expert_tiles``).
    engine/runner.py keeps it per executable (``moe_paths``, GET
    /debug/perf ``device.moe_paths``)."""
    shape = (rows, positions, hidden, inter, weight_dtype, act_dtype, mesh,
             gated)
    tiles = expert_tiles(hidden, inter, weight_dtype, act_dtype, gated)
    tiled = f"_tiled{tiles}" if tiles > 1 else ""
    if list_path(*shape):
        return "list" + tiled
    if grouped_path(*shape):
        return "grouped" + tiled
    N = rows * positions
    covered = N <= DENSE_THRESHOLD or N <= capacity_for(
        capacity_tokens or N, num_experts, top_k, capacity_factor)
    return "exact" if positions == 1 or covered else "dispatch"


def experts_hit(top_i: jnp.ndarray, valid, num_experts: int):
    """top_i [N, k] int32, valid [N] bool (None: all) -> (ids [M]
    int32, count int32), M = min(E, N*k): the distinct experts that a
    valid row chose, ascending, compacted to the front (the tail holds
    zeros), and how many they are. A one-hot ``any``, a rank by an
    [E, E] compare and a [M, E] compare, all of which fuse: nothing
    sorted, nothing scattered, no shape depends on the data."""
    N, k = top_i.shape
    E = num_experts
    M = min(E, N * k)
    ids = jnp.arange(E, dtype=jnp.int32)
    chose = top_i[:, :, None] == ids                      # [N, k, E]
    if valid is not None:
        chose = chose & valid[:, None, None]
    hit = jnp.any(chose, axis=(0, 1))                     # [E]
    # an expert's rank among the hit: the hit experts up to it, less 1
    pos = jnp.sum(hit[None, :] & (ids[None, :] <= ids[:, None]),
                  axis=1, dtype=jnp.int32) - 1
    at = hit[None, :] & (pos[None, :]
                         == jnp.arange(M, dtype=jnp.int32)[:, None])
    return (jnp.sum(jnp.where(at, ids[None, :], 0), axis=1),
            jnp.sum(hit.astype(jnp.int32)))


def _rank_in_expert(top_i: jnp.ndarray, valid, num_experts: int,
                    held: bool = False):
    """top_i [N, k], valid [N] bool or None -> (flat_e [N k] int32, the
    assignments' experts, token-major; rank [N k] int32, how many
    earlier assignments of valid tokens chose the same expert (an
    O(N k E) cumsum of integers); rows [E] int32, the valid
    assignments of each expert; keep [N k] bool, the assignment's
    token is valid: None where valid is). ``held`` (static): top_i may
    name num_experts itself, an expert held on another chip
    (``moe_mlp``): such an assignment is kept out like an invalid
    token's, and its flat_e reads 0."""
    k = top_i.shape[1]
    flat_e = top_i.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    keep = None
    if valid is not None:
        keep = jnp.repeat(valid, k)
        onehot = onehot * keep.astype(jnp.int32)[:, None]
    if held:
        here = flat_e < num_experts
        keep = here if keep is None else keep & here
        flat_e = jnp.where(here, flat_e, 0)
    prior = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(prior, flat_e[:, None], axis=1)[:, 0]
    return flat_e, rank, jnp.sum(onehot, axis=0), keep


def _expert_copies(ids_ref, layer, hbms, gu_buf, d_buf, sems, tiles, c,
                   slot, tile=0):
    """The copies of listed expert c's gate, up and down out of the
    stacks in HBM into ``slot``: to start, or to wait for one by one
    (``hbms`` (up, down): an expert without a gate, two copies).
    ``tiles`` > 1 (static): of its tile ``tile`` (static), the columns
    of gate and up and the rows of down that one tile's width of the
    intermediate values spans."""
    e = ids_ref[c]
    bufs = [gu_buf.at[slot, o] for o in range(len(hbms) - 1)] \
        + [d_buf.at[slot]]
    if tiles == 1:
        srcs = [hbm.at[layer, e] for hbm in hbms]
    else:
        width = d_buf.shape[1]
        span = pl.ds(tile * width, width)
        srcs = [hbm.at[layer, e, :, span] for hbm in hbms[:-1]] \
            + [hbms[-1].at[layer, e, span, :]]
    return [pltpu.make_async_copy(src, buf, sems.at[slot, o])
            for o, (src, buf) in enumerate(zip(srcs, bufs))]


def _scale_row(ref, c):
    """Row c of a [M8, w] block as [1, w]. Mosaic loads a dynamic
    sublane only at a multiple of 8: take the aligned group of 8 rows
    and keep the one."""
    base = pl.multiple_of(jax.lax.div(c, 8) * 8, 8)
    rows = ref[pl.ds(base, 8), :]
    keep = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == c - base
    return jnp.sum(jnp.where(keep, rows, 0.0), axis=0, keepdims=True)


def _scaled_dot(a, w, scale):
    """a @ w in float32, w converted to a's dtype in VMEM and its
    per-channel scale (None: raw weights) applied to the products (the
    compiler tiles the width: panels of 128 to 512 channels cut by
    hand ran a layer within 1 % of this; PERF.md, PR 34)."""
    y = jnp.dot(a, w.astype(a.dtype), preferred_element_type=jnp.float32)
    return y if scale is None else y * scale


def _act_products(x, in_copies, gu_buf, slot, scales, act, wait=None):
    """act(x @ gate) * (x @ up), or act(x @ up) where the expert has no
    gate (one copy in ``in_copies``), float32; each matrix waited for
    where it is first read (``wait``: how, default at once)."""
    wait = wait or (lambda cp: cp.wait())
    wait(in_copies[0])
    first = _scaled_dot(x, gu_buf[slot, 0], scales[0])
    if len(in_copies) == 1:
        return act(first)
    wait(in_copies[1])
    return act(first) * _scaled_dot(x, gu_buf[slot, 1], scales[1])


def _moe_list_kernel(ids_ref, count_ref, layer_ref, x_ref, ti_ref, tp_ref,
                     *refs, act: Callable, quant: bool, tiles: int,
                     gated: bool = True):
    """Every listed expert over all N rows.

    ids_ref   (SMEM) [M]     the experts hit, compacted
    count_ref (SMEM) [1]     how many of them are live
    layer_ref (SMEM) [1]     the stacks' layer
    x_ref  [N, h]            the rows
    ti_ref [N, k] int32, tp_ref [N, k] fp32    routing (weights zeroed
                             on invalid rows)
    refs   gate, up (HBM) [L, E, h, i], down (HBM) [L, E, i, h];
           (quant only: the listed experts' scale rows, gate and up
           [M8, i], down [M8, h] fp32, M8 = M rounded up to 8;)
           out [N, h]; scratch: the gate/up slots [2, 2, h, i], the
           down slots [2, i, h], DMA semaphores [2 slots, 3 matrices],
           acc [N, h] fp32

    tiles > 1 (static): an expert comes in that many tiles of its
    intermediate width, one after the other through the two slots
    ([2, 2, h, i/tiles] and [2, i/tiles, h]); gate's and up's scale
    rows come a row a tile ([M8 x tiles, i/tiles], the expert's tiles
    in order), and every tile's products are weighed and added like an
    expert's.

    gated False (static): no gate among the refs, the scale rows or
    the slots ([2, 1, h, i]): the expert is ``act(x @ up) @ down``.
    """
    mats = 3 if gated else 2
    hbms, refs = refs[:mats], refs[mats:]
    if quant:
        scale_refs, refs = refs[:mats], refs[mats:]
    out_ref, gu_buf, d_buf, sems, acc_ref = refs
    layer = layer_ref[0]
    count = count_ref[0]
    cdt = x_ref.dtype                              # the dots' operands

    copies = functools.partial(_expert_copies, ids_ref, layer, hbms,
                               gu_buf, d_buf, sems, tiles)

    @pl.when(count > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    x = x_ref[...]

    def expert(c, carry):
        # an even number of tiles leaves every expert's first in slot 0
        slot = jax.lax.rem(c, 2) if tiles == 1 else 0
        e = ids_ref[c]
        for t in range(tiles):
            if t + 1 < tiles:
                for cp in copies(c, 1 - slot, t + 1):
                    cp.start()
            else:
                @pl.when(c + 1 < count)
                def _next():
                    for cp in copies(c + 1, 1 - slot):
                        cp.start()

            if t == 0:
                # this expert's combine column [N, 1]: its routing
                # weight on the rows that chose it, zero on the others
                comb = jnp.sum(
                    jnp.where(ti_ref[...] == e, tp_ref[...], 0.0),
                    axis=1, keepdims=True)
            if quant:
                part = c if tiles == 1 else c * tiles + t
                *s_in, sd = ([_scale_row(ref, part)
                              for ref in scale_refs[:-1]]
                             + [_scale_row(scale_refs[-1], c)])
            else:
                *s_in, sd = [None] * mats
            # each matrix is waited for where it is first read: gate's
            # products run under up's and down's copies
            *in_copies, down_copy = copies(c, slot, t)
            a = _act_products(x, in_copies, gu_buf, slot, s_in, act
                              ).astype(cdt)                     # [N, i]
            down_copy.wait()
            y = _scaled_dot(a, d_buf[slot], sd)
            acc_ref[...] += y * comb
            if tiles > 1:
                slot = 1 - slot
        return carry

    jax.lax.fori_loop(0, count, expert, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tile_scales(w, layer, listed, tiles: int):
    """The listed experts' scale rows of gate or up [M8, i], a row a
    tile where an expert comes in tiles: [M8 x tiles, i / tiles]."""
    sc = w["scale"][layer, listed]
    return sc if tiles == 1 else sc.reshape(sc.shape[0] * tiles, -1)


def _moe_list(x, top_p, top_i, gate, up, down, act, ids, count, layer):
    """The experts on the list over all tokens, combined by routing
    weight: ``_moe_exact``'s result (an expert no valid row chose
    contributes exactly zero there), reading ``count`` experts' weights
    where that reads all E. gate/up [L, E, h, i], down [L, E, i, h]
    (raw or int8-quantized), layer: int32 scalar, traced."""
    quant = _quant().is_quantized(up)
    N, h = x.shape
    k = top_i.shape[1]
    L, E, _, inter = _wshape(up)
    stacks = (up, down) if gate is None else (gate, up, down)
    mats = [w["w8"] if quant else w for w in stacks]
    tiles = expert_tiles(h, inter, mats[0].dtype, x.dtype, gate is not None)
    inter //= tiles

    def whole(*_):
        return (0, 0)

    in_specs = [pl.BlockSpec((N, h), whole),
                pl.BlockSpec((N, k), whole),
                pl.BlockSpec((N, k), whole)]
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * len(mats)
    operands = [x, top_i, top_p.astype(jnp.float32)] + mats
    if quant:
        # the listed experts' scale rows, gathered out of the stacks
        # (1.2 MB a layer beside the experts' 8.65 MB each)
        rows = jnp.pad(ids, (0, -ids.shape[0] % 8))
        for w in stacks:
            sc = (w["scale"][layer, rows] if w is down      # [M8, w]
                  else _tile_scales(w, layer, rows, tiles))
            in_specs.append(pl.BlockSpec(sc.shape, whole))
            operands.append(sc)
    with jax.named_scope("moe_experts"):
        return pl.pallas_call(
            functools.partial(_moe_list_kernel, act=act, quant=quant,
                              tiles=tiles, gated=gate is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(1,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((N, h), whole),
                scratch_shapes=[
                    pltpu.VMEM((2, len(mats) - 1, h, inter),
                               mats[0].dtype),
                    pltpu.VMEM((2, inter, h), mats[-1].dtype),
                    pltpu.SemaphoreType.DMA((2, len(mats))),
                    pltpu.VMEM((N, h), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((N, h), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name="moe_list_experts",
        )(ids, count.reshape(1),
          jnp.asarray(layer, jnp.int32).reshape(1), *operands)


# ---------------------------------------------------------------------
# the grouped path: a prefill chunk's experts multiply only the rows
# routed to them.
#
# ``_group_rows`` sorts the N k assignments by expert without sorting:
# an assignment's place is its expert's segment start plus its rank
# within the expert (the integer cumsum ``_moe_dispatch`` ranks by);
# a segment starts at a multiple of the row alignment the copies need
# (8 sublanes of 32 bits), so the buffer has N k + E (align - 1) rows,
# rounded up, whatever the routing, and one pass more of slack; the
# assignments of invalid tokens fall off its end and are never
# computed. ``_moe_grouped`` gathers the tokens' rows into it and makes
# one Pallas call a layer, built from the list path's: the stacks in
# HBM, whole, the layer's index and the list of experts that have a
# row scalar-prefetched, two VMEM slots, the next expert's three copies
# under this expert's arithmetic. Per listed expert it walks the
# segment in passes of GROUPED_ROWS rows (a dynamic trip count): a
# pass's rows come in by a copy started a pass ahead and its products
# [rows, h] leave by one that the next pass waits for. The last pass of
# a segment runs over into the next expert's rows and writes what that
# expert then overwrites (the experts are walked in the segments'
# order and a pass's write is awaited before the next starts). Outside,
# each assignment's row is gathered back, weighed and the k terms
# summed in float32: ``_moe_exact``'s sum with the zeros left out.
#
# A share of the router's experts (``router_experts`` wider than the
# stacks). Most assignments then name an expert on another chip, and
# all of the above sized by N k would sort, gather and sum them to
# multiply them by nothing. So the assignments that name a held expert
# of a valid token are COMPACTED first, by 1-D integer work alone (a
# prefix count over N k and one scatter of their indices), into a list
# in token order; ``held_block`` reads a block size B off the shapes
# (HELD_BLOCK_SHARES even shares of N k); and a loop with a traced trip
# count, ceil(kept / B), takes the list a block a round: the block's B
# (token, expert, weight) entries are grouped as B tokens of one choice
# each (rank and segments from a one-hot [B, E], a buffer of B + E
# (align - 1) + slack rows), the same Pallas call multiplies them, B
# rows are gathered back as they are, and ``_held_sum`` adds each,
# weighed in float32, to its token's row of the [N, h] float32 sum.
# The block is in token order, so the entries of a tile of 128 tokens
# are one contiguous window of it: a second Pallas call a round,
# ``moe_held_sum``, walks the block ONCE, slab of 128 entries after
# slab, and adds a slab to a tile's rows as the product of a [128
# tokens, 128 entries] matrix of the weights with it (no gather, no
# scatter; nothing past the live entries is read; to PR 52 a row
# scatter-add, 88 ns a row whatever was live). No capacity and no
# second path: with every assignment here the loop runs N k / B rounds,
# each reading the hit experts again (about 1.6-1.8 x the time of the
# path sized by N k at that routing: tools/moe_prefill_table.py
# --held); with none, no round, and zeros.
# ---------------------------------------------------------------------

def _row_align(dtype) -> int:
    """Rows a dynamic row offset into an array of ``dtype`` has to be
    a multiple of: a tile of 8 sublanes of 32 bits."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _group_rows(top_i: jnp.ndarray, valid, num_experts: int, align: int,
                slack: int, held: bool = False):
    """top_i [N, k], valid [N] bool or None -> (dest [N k] int32: the
    buffer row of each assignment, P where its token is invalid; rows
    [E] int32: the rows of each expert; seg [E] int32: where each
    expert's segment starts, a multiple of ``align``; P: the buffer's
    rows, static)."""
    N, k = top_i.shape
    E = num_experts
    flat_e, rank, rows, keep = _rank_in_expert(top_i, valid, E, held)
    padded = -(-rows // align) * align
    seg = jnp.cumsum(padded) - padded
    P = -(-(N * k + E * (align - 1)) // align) * align + slack
    dest = seg[flat_e] + rank
    if keep is not None:
        dest = jnp.where(keep, dest, P)
    return dest, rows, seg, P


def _moe_grouped_kernel(ids_ref, count_ref, layer_ref, seg_ref, passes_ref,
                        xs_hbm, *refs, act: Callable, quant: bool,
                        align: int, tiles: int, gated: bool = True):
    """Every listed expert over its own rows.

    ids_ref    (SMEM) [M]    the experts that have a row, compacted
    count_ref  (SMEM) [1]    how many of them are live
    layer_ref  (SMEM) [1]    the stacks' layer
    seg_ref    (SMEM) [M]    where each listed expert's segment starts
    passes_ref (SMEM) [M]    its passes: ceil(rows / R)
    xs_hbm (HBM) [P, h]      the tokens' rows, sorted by expert
    refs   gate, up (HBM) [L, E, h, i], down (HBM) [L, E, i, h];
           (quant only: the listed experts' scale rows, as the list
           kernel's;) out (HBM) [P, h]; scratch: the gate/up slots
           [2, 2, h, i], the down slots [2, i, h], their semaphores
           [2, 3], the rows' slots [2, R, h] in and [2, R, h] out,
           their semaphores [2] and [2]

    tiles > 1 (static): an expert comes in that many tiles of its
    intermediate width (slots and scale rows as the list kernel's),
    each walking the expert's segment again and writing its products
    to a plane of its own, out (HBM) [tiles x P, h]: tile t's at row
    t P + the segment's; the caller sums the planes.

    gated False (static): as the list kernel's.
    """
    mats = 3 if gated else 2
    hbms, refs = refs[:mats], refs[mats:]
    if quant:
        scale_refs, refs = refs[:mats], refs[mats:]
    out_hbm, gu_buf, d_buf, sems, x_buf, y_buf, x_sems, y_sems = refs
    layer = layer_ref[0]
    count = count_ref[0]
    cdt = x_buf.dtype                              # the dots' operands
    R = x_buf.shape[1]
    P = xs_hbm.shape[0]

    copies = functools.partial(_expert_copies, ids_ref, layer, hbms,
                               gu_buf, d_buf, sems, tiles)

    def rows_in(row, slot):
        return pltpu.make_async_copy(
            xs_hbm.at[pl.ds(pl.multiple_of(row, align), R)],
            x_buf.at[slot], x_sems.at[slot])

    def rows_out(row, slot):
        return pltpu.make_async_copy(
            y_buf.at[slot],
            out_hbm.at[pl.ds(pl.multiple_of(row, align), R)],
            y_sems.at[slot])

    @pl.when(count > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()
        rows_in(seg_ref[0], 0).start()

    def expert(c, done):
        slot = jax.lax.rem(c, 2) if tiles == 1 else 0
        more = c + 1 < count
        first = seg_ref[c]
        passes = passes_ref[c]
        # the segment after this one (the list's last: not read)
        after = seg_ref[jnp.minimum(c + 1, ids_ref.shape[0] - 1)]
        for t in range(tiles):
            last_tile = t + 1 == tiles
            if not last_tile:
                for cp in copies(c, 1 - slot, t + 1):
                    cp.start()
            else:
                @pl.when(more)
                def _next():
                    for cp in copies(c + 1, 1 - slot):
                        cp.start()

            if quant:
                part = c if tiles == 1 else c * tiles + t
                *s_in, sd = ([_scale_row(ref, part)
                              for ref in scale_refs[:-1]]
                             + [_scale_row(scale_refs[-1], c)])
            else:
                *s_in, sd = [None] * mats
            *in_copies, down_copy = copies(c, slot, t)
            # the rows walked after this tile's: the expert's own again
            # for its next tile, else the next expert's
            then = after if last_tile else first
            plane = t * P

            def one_pass(p, n, slot=slot, s_in=s_in, sd=sd,
                         in_copies=in_copies,
                         down_copy=down_copy, then=then, plane=plane,
                         last_tile=last_tile):
                """Rows first + p R .. + R of the buffer; n: the passes
                made so far, whose parity names the rows' slot."""
                rs = jax.lax.rem(n, 2)
                row = first + p * R
                inside = p + 1 < passes

                def _rows_ahead():
                    rows_in(jnp.where(inside, row + R, then),
                            1 - rs).start()

                if last_tile:
                    pl.when(inside | more)(_rows_ahead)
                else:
                    _rows_ahead()
                rows_in(row, rs).wait()
                x = x_buf[rs]
                # each matrix is waited for where it is first read
                a = _act_products(
                    x, in_copies, gu_buf, slot, s_in, act,
                    lambda cp: pl.when(p == 0)(cp.wait)).astype(cdt)
                pl.when(p == 0)(down_copy.wait)
                y_buf[rs] = _scaled_dot(a, d_buf[slot], sd
                                        ).astype(y_buf.dtype)
                # one write at a time, in the segments' order: what
                # this pass writes past its segment the next overwrites

                @pl.when(n > 0)
                def _written():
                    rows_out(row, 1 - rs).wait()

                rows_out(row + plane if plane else row, rs).start()
                return n + 1

            done = jax.lax.fori_loop(0, passes, one_pass, done)
            if tiles > 1:
                slot = 1 - slot
        return done

    done = jax.lax.fori_loop(0, count, expert, 0)

    @pl.when(done > 0)
    def _last():
        rows_out(0, jax.lax.rem(done - 1, 2)).wait()


def _grouped_products(x, top_i, tokens, gate, up, down, act, valid, layer,
                      held: bool):
    """What the experts give for each assignment, unweighed: top_i
    [M, c] names each assignment's expert, tokens [M c] the row of x
    it multiplies (assignment-major, as top_i.reshape(-1)). The
    assignments are laid out by expert (``_group_rows``), the rows
    gathered into the buffer, one Pallas call multiplies each expert's
    segment, and each assignment's row is gathered back: ([M c, h]
    float32, zeros where the assignment is left out: an invalid row of
    top_i or, ``held``, an expert named E; the experts that had a row;
    the rows the experts multiplied: passes x GROUPED_ROWS). ``held``
    (a round of the held experts, whose sum by token is a kernel's,
    ``_held_sum``): the rows come back as the kernel wrote them, a
    list of [M c, h] in x's dtype, one plane a tile of an expert
    (``expert_tiles``), whose float32 sum is the assignment's row; an
    assignment left out holds the buffer's row 0, some expert's
    product, which its weight of zero takes out of the sum (a select
    over the block here was a pass of its own, 35 us a layer in N)."""
    quant = _quant().is_quantized(up)
    h = x.shape[1]
    L, E, _, inter = _wshape(up)
    stacks = (up, down) if gate is None else (gate, up, down)
    mats = [w["w8"] if quant else w for w in stacks]
    tiles = expert_tiles(h, inter, mats[0].dtype, x.dtype, gate is not None)
    inter //= tiles
    R = GROUPED_ROWS
    align = _row_align(x.dtype)
    with jax.named_scope("moe_group"):
        dest, rows, seg, P = _group_rows(top_i, valid, E, align, R, held)
        # the buffer's rows by the token each holds (padding: token 0,
        # computed and never read back)
        src = jnp.zeros((P,), jnp.int32).at[dest].set(tokens, mode="drop")
        xs = x[src]                                       # [P, h]
        ids, count = experts_hit(top_i, valid, E)
        passes = -(-rows // R)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [hbm] * (1 + len(mats))
    operands = [xs] + mats
    if quant:
        listed = jnp.pad(ids, (0, -ids.shape[0] % 8))
        for w in stacks:
            sc = (w["scale"][layer, listed] if w is down    # [M8, w]
                  else _tile_scales(w, layer, listed, tiles))
            in_specs.append(pl.BlockSpec(sc.shape, lambda *_: (0, 0)))
            operands.append(sc)
    with jax.named_scope("moe_experts"):
        ys = pl.pallas_call(
            functools.partial(_moe_grouped_kernel, act=act, quant=quant,
                              align=align, tiles=tiles,
                              gated=gate is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(1,),
                in_specs=in_specs,
                out_specs=hbm,
                scratch_shapes=[
                    pltpu.VMEM((2, len(mats) - 1, h, inter),
                               mats[0].dtype),
                    pltpu.VMEM((2, inter, h), mats[-1].dtype),
                    pltpu.SemaphoreType.DMA((2, len(mats))),
                    pltpu.VMEM((2, R, h), x.dtype),
                    pltpu.VMEM((2, R, h), x.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((tiles * P, h), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name="moe_grouped_experts",
        )(ids, count.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
          seg[ids], passes[ids], *operands)
    with jax.named_scope("moe_combine"):
        # an assignment left out reads row 0, which may hold anything,
        # and counts as zero
        kept = dest < P
        back = jnp.where(kept, dest, 0)
        if held:        # an expert's tiles, a plane each, as they are
            y = [ys[back + t * P] for t in range(tiles)]
        else:
            y = ys[back].astype(jnp.float32)
            for t in range(1, tiles):
                y = y + ys[back + t * P].astype(jnp.float32)
            y = jnp.where(kept[:, None], y, 0.0)
    return y, count, jnp.sum(passes) * R


def held_block(tokens: int, top_k: int, num_experts: int,
               router_experts: int) -> int:
    """The assignments one round of the grouped path takes where the
    layer holds ``num_experts`` of its router's ``router_experts``:
    HELD_BLOCK_SHARES even shares of the chunk's ``tokens`` x top_k,
    rounded up to GROUPED_ROWS (Qwen3-Next's share, 64 of 512 top-10 at
    2048 tokens: 5120 of 20 480; GLM-5's, 16 of 256 top-8: 2048 of
    16 384), and no more than all of them. Read off shapes alone."""
    R = GROUPED_ROWS
    assignments = tokens * top_k
    even = -(-assignments * num_experts // router_experts)
    return min(-(-HELD_BLOCK_SHARES * even // R), -(-assignments // R)) * R


def _held_sum_tile(tokens: int) -> int:
    """The tokens a grid step of ``_held_sum`` takes: GROUPED_ROWS (the
    MXU's rows), or all of a shorter chunk in whole sublanes."""
    return min(GROUPED_ROWS, -(-tokens // 8) * 8)


def _held_sum_kernel(first_ref, acc_ref, tok_ref, weight_ref, *refs):
    """One tile of T tokens: its rows of the sum plus what the tile's
    window of the block gives them.

    first_ref  (SMEM) [tiles + 1]  where each tile's entries start in
                                   the block; the last: the live entries
    acc_ref    [T, h] fp32         the tile's rows of the sum so far
    tok_ref    [B / R, 1, R] int32 each entry's token, a slab a row
    weight_ref [B / R, 1, R] fp32  each entry's routing weight
    refs   the planes (HBM) [B, h] each; out [T, h] fp32 (acc's own
           buffer); scratch: the slabs' slots [2, planes, R, h], their
           semaphores [2, planes], slabs arrived (SMEM) [1]

    The block is walked ONCE over the whole grid: its slabs of R
    entries are copied in one after the other, slab s into slot s % 2
    while slab s - 1 is multiplied, and a slab that two tiles' windows
    share stays where it is for the second. A slab is added to the
    tile's rows as W @ slab with W[t, e] = the entry's weight where its
    token is the tile's row t, else 0: W enters the MXU as its three
    bfloat16 terms (a float32 exactly) against a bfloat16 slab, every
    product exact and summed in float32; float32 slabs (tests) multiply
    at full precision."""
    planes = len(refs) - 4
    hbm, (out_ref, buf, sems, arrived) = refs[:planes], refs[planes:]
    i = pl.program_id(0)
    T = acc_ref.shape[0]
    R = buf.shape[2]
    f32, bf16 = jnp.float32, jnp.bfloat16
    slabs = pl.cdiv(first_ref[pl.num_programs(0)], R)

    def copies(s):
        slot = jax.lax.rem(s, 2)
        return [pltpu.make_async_copy(
            hbm[p].at[pl.ds(pl.multiple_of(s * R, R), R)],
            buf.at[slot, p], sems.at[slot, p]) for p in range(planes)]

    @pl.when(i == 0)
    def _first():
        arrived[0] = 0

        @pl.when(slabs > 0)
        def _():
            for cp in copies(0):
                cp.start()

    out_ref[...] = acc_ref[...]
    lo, hi = first_ref[i], first_ref[i + 1]
    row = i * T + jax.lax.broadcasted_iota(jnp.int32, (T, R), 0)

    def one_slab(s, carry):
        @pl.when(s == arrived[0])
        def _arrive():
            for cp in copies(s):
                cp.wait()
            arrived[0] = s + 1

            @pl.when(s + 1 < slabs)
            def _ahead():
                for cp in copies(s + 1):
                    cp.start()

        slot = jax.lax.rem(s, 2)
        w = jnp.where(tok_ref[s] == row, weight_ref[s], 0.0)      # [T, R]
        terms, exact = [w], jax.lax.Precision.HIGHEST
        if buf.dtype == bf16:
            terms, exact = [], None
            for _ in range(3):
                terms.append(w.astype(bf16))
                w = w - terms[-1].astype(f32)
        for p in range(planes):
            slab = buf[slot, p]
            out_ref[...] += sum(
                jnp.dot(t, slab, precision=exact, preferred_element_type=f32)
                for t in terms)
        return carry

    jax.lax.fori_loop(lo // R, jnp.where(hi > lo, pl.cdiv(hi, R), lo // R),
                      one_slab, 0)


def _held_sum(acc, planes, tok, weight, tokens: int):
    """acc [Np, h] float32 (Np: ``tokens`` rounded up to whole tiles)
    plus a round's block by token: acc[tok[e]] += weight[e] x the sum
    of the planes' rows e ([B, h] each, in the activations' dtype, B a
    multiple of GROUPED_ROWS), for the entries whose tok is under
    ``tokens``. tok [B] is non-decreasing (the block is the compacted
    list in token order; the entries past its end name ``tokens``,
    weigh zero and hold finite rows: those of the last live slab are
    multiplied by their zero), so
    the entries of a tile of tokens are one contiguous window of the
    block: a prefix count at the tiles' boundaries gives the windows,
    and one Pallas call, ``moe_held_sum``, walks the block once
    (``_held_sum_kernel``): no gather, no scatter, nothing past the
    live entries read, acc updated in place."""
    Np, h = acc.shape
    B = tok.shape[0]
    R = GROUPED_ROWS
    T = _held_sum_tile(tokens)
    tiles = Np // T
    bounds = jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * T, tokens)
    first = jnp.sum(tok[None, :] < bounds[:, None], axis=1, dtype=jnp.int32)

    def whole(i, first):
        return (0, 0, 0)

    def tile(i, first):
        return (i, 0)

    return pl.pallas_call(
        _held_sum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((T, h), tile),
                      pl.BlockSpec((B // R, 1, R), whole),
                      pl.BlockSpec((B // R, 1, R), whole)]
            + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(planes),
            out_specs=pl.BlockSpec((T, h), tile),
            scratch_shapes=[
                pltpu.VMEM((2, len(planes), R, h), planes[0].dtype),
                pltpu.SemaphoreType.DMA((2, len(planes))),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="moe_held_sum",
    )(first, acc, tok.reshape(B // R, 1, R),
      weight.astype(jnp.float32).reshape(B // R, 1, R), *planes)


def _moe_grouped(x, top_p, top_i, gate, up, down, act, valid, layer,
                 router_experts: int = 0):
    """Every expert over the rows routed to it, combined by routing
    weight: ``_moe_exact``'s result with nothing multiplied by a
    weight of zero. gate/up [L, E, h, i], down [L, E, i, h] (raw or
    int8-quantized), layer: int32 scalar, traced. Returns ([N, h],
    ``Work``).

    router_experts (static; 0: the stacks' E): the experts the router
    scores where the stacks hold a share of them (``moe_mlp``); top_i
    then names E for an expert held elsewhere, and the path works on
    the COMPACTED list of the assignments that name a held expert of a
    valid token, ``held_block`` of them a round, in as many rounds as
    the routing filled (a traced trip count: one at any routing near
    even, N k / block with every assignment here, none and zeros out
    with none). A round runs the grouping and the kernel on its block
    as that many tokens of one choice each, and ``_held_sum`` weighs
    what comes back and adds it to its token's row of the [N, h]
    float32 sum in one pass over the block, which is in token order
    (the sum is the loop's carry, updated in place; what differs from
    a scatter-add of the weighed rows is the order of a token's terms,
    nothing else). Nothing of N k rows by E or by h is built."""
    N, h = x.shape
    k = top_i.shape[1]
    E = _wshape(up)[1]
    A = N * k
    if not router_experts or router_experts == E:
        y, count, multiplied = _grouped_products(
            x, top_i, jnp.arange(A, dtype=jnp.int32) // k, gate, up, down,
            act, valid, layer, False)
        with jax.named_scope("moe_combine"):
            y = jnp.sum((y * top_p.reshape(-1)[:, None]).reshape(N, k, h),
                        axis=1)
        return y.astype(x.dtype), _work(count, multiplied)

    B = held_block(N, k, E, router_experts)
    with jax.named_scope("moe_group"):
        flat_e = top_i.reshape(-1)
        flat_p = top_p.reshape(-1)
        keep = flat_e < E
        if valid is not None:
            keep = keep & jnp.repeat(valid, k)
        # the kept assignments' places in the compacted list, which is
        # in token order: a 1-D prefix count and a 1-D scatter
        upto = jnp.cumsum(keep.astype(jnp.int32))
        total = upto[-1]
        cap = -(-A // B) * B
        kept = jnp.zeros((cap,), jnp.int32).at[
            jnp.where(keep, upto - 1, cap)].set(
                jnp.arange(A, dtype=jnp.int32), mode="drop")
        rounds = -(-total // B)

    def one_round(r, carry):
        acc, read, multiplied = carry
        with jax.named_scope("moe_group"):
            a = jax.lax.dynamic_slice(kept, (r * B,), (B,))
            live = r * B + jnp.arange(B, dtype=jnp.int32) < total
            # past the list's end: no expert (E), and a token past the
            # last, so that the tokens stay in order for the sum
            tok = jnp.where(live, a // k, N)
            expert = jnp.where(live, flat_e[a], E)
            weight = jnp.where(live, flat_p[a], 0.0)
        planes, count, rows = _grouped_products(
            x, expert[:, None], tok, gate, up, down, act, None, layer, True)
        with jax.named_scope("moe_combine"):
            acc = _held_sum(acc, planes, tok, weight, N)
        return acc, read + count, multiplied + rows

    T = _held_sum_tile(N)
    acc, read, multiplied = jax.lax.fori_loop(
        0, rounds, one_round,
        (jnp.zeros((-(-N // T) * T, h), jnp.float32), jnp.int32(0),
         jnp.int32(0)))
    return acc[:N].astype(x.dtype), _work(read, multiplied, total, rounds)


def _moe_dispatch(x, top_p, top_i, gate, up, down, act, capacity,
                  valid=None, held: bool = False):
    """Scatter-based capacity dispatch (see module docstring)."""
    N, h = x.shape
    E = _wshape(up)[0]
    k = top_i.shape[1]

    # padding tokens must not compete for expert capacity: they are
    # left out of the rank count and the buffers (and the assignments
    # to experts held elsewhere)
    flat_e, rank, _, real = _rank_in_expert(top_i, valid, E, held)
    keep = rank < capacity
    if real is not None:
        keep = keep & real
    trash = E * capacity                                # overflow row
    dest = jnp.where(keep, flat_e * capacity + rank, trash)

    x_rep = jnp.repeat(x, k, axis=0)                    # [N*k, h]
    buf = jnp.zeros((E * capacity + 1, h), x.dtype).at[dest].set(x_rep)
    xb = buf[:-1].reshape(E, capacity, h)
    with jax.named_scope("moe_experts"):
        y_e = _expert_ffn(xb, gate, up, down, act)      # [E, C, h]
    with jax.named_scope("moe_combine"):
        y_flat = jnp.concatenate(
            [y_e.reshape(E * capacity, h), jnp.zeros((1, h), y_e.dtype)])
        y_rep = y_flat[dest]                            # dropped -> zeros
        w = top_p.reshape(-1)[:, None].astype(x.dtype)
        return jnp.sum((y_rep * w).reshape(N, k, h), axis=1)


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, gate: jnp.ndarray,
            up: jnp.ndarray, down: jnp.ndarray, *, top_k: int,
            capacity_factor: float = 2.0,
            dense_threshold: int = DENSE_THRESHOLD,
            act: Callable = jax.nn.silu, valid=None,
            exact=None, renormalize: bool = True,
            capacity_tokens=None, layer=None, positions: int = 1,
            router_score: str = "softmax", router_bias=None,
            routed_scale: float = 1.0, expert_offset: int = 0):
    """MoE feed-forward. x [N, h]; router_w [h, E]; gate/up [E, h, i];
    down [E, i, h]; gate None: experts without a gate, ``down(act(up(
    x)))``. Returns ([N, h] in x.dtype, ``Work``: the experts
    whose weights the call read and the rows they multiplied, int32
    scalars).

    The chip's share of the experts: where router_w scores more
    experts than the stacks hold, the stacks are those from
    ``expert_offset`` (static) on. The router picks among all of them
    and weighs as published; an assignment to an expert held elsewhere
    contributes nothing here (module text), and ``Work`` counts held
    experts only.

    valid [N] bool marks real tokens: padding rows contribute nothing
    and never consume expert capacity. exact=True forces the all-expert
    path regardless of N (the decode path passes it — decode must never
    drop a token); exact=None auto-selects it for N ≤ dense_threshold
    or whenever capacity covers every possible assignment.
    capacity_tokens: the token count the per-expert capacity is
    reckoned on (default N), clamped to N. The engine's prefill passes
    max_num_seqs x chunk bucket whatever rows it dispatches
    (runner._prefill_impl): a chunk alone in a one-row dispatch then
    holds as much per expert as it did among the parked rows of a full
    one, and where that covers its N tokens (Qwen1.5-MoE, 256 tokens:
    552) it takes the exact path and drops nothing.
    layer (int32 scalar, traced): gate/up/down are the whole stacks
    [L, E, ...] of which that layer is read in place, by the list path
    or the grouped path. Where ``list_path`` or ``grouped_path`` says
    so and nowhere else (models/llama.forward asks them, with the mesh,
    before it hands the stacks over; ``positions``, static, is the
    tokens a row of the N: 1 a decode step); both compute the exact
    path's sum, so ``exact`` False is refused and the capacity
    arguments do not apply.
    router_score, router_bias, routed_scale: ``route``'s score, bias
    and scale; every path takes the weights it gives unchanged.
    """
    N = x.shape[0]
    E = _wshape(up)[-3]
    with jax.named_scope("moe_router"):
        top_p, top_i = route(x, router_w, top_k, renormalize=renormalize,
                             score=router_score, bias=router_bias,
                             scale=routed_scale)
        if valid is not None:
            top_p = top_p * valid.astype(top_p.dtype)[:, None]
        held = router_w.shape[-1] != E
        if held:
            # experts held elsewhere: all named E, one past the stacks,
            # an id no kernel and no one-hot matches; weight zero
            here = (top_i >= expert_offset) & (top_i < expert_offset + E)
            top_i = jnp.where(here, top_i - expert_offset, E)
            top_p = jnp.where(here, top_p, 0.0)
    if layer is not None:
        h, inter = _wshape(up)[-2:]
        shape = (N // positions, positions, h, inter, stored_dtype(up),
                 x.dtype, None, gate is not None)
        assert exact is not False and (
            list_path(*shape) or grouped_path(*shape)), (
            "moe_mlp was handed whole stacks where neither list_path "
            f"nor grouped_path says so: {N} tokens, {positions} a row, "
            f"experts [{h}, {inter}], exact={exact}")
        if grouped_path(*shape):
            return _moe_grouped(x, top_p, top_i, gate, up, down, act,
                                valid, layer, router_w.shape[-1])
        with jax.named_scope("moe_list"):
            ids, count = experts_hit(top_i, valid, E)
        return _moe_list(x, top_p, top_i, gate, up, down, act, ids,
                         count, layer), _work(count, count * N)
    # (the balanced load an expert's capacity is reckoned on is that
    # of all the router's experts)
    capacity = min(N, capacity_for(capacity_tokens or N,
                                   router_w.shape[-1], top_k,
                                   capacity_factor))
    if exact is None:
        exact = N <= dense_threshold or capacity >= N
    if exact:
        y = _moe_exact(x, top_p, top_i, gate, up, down, act)
    else:
        y = _moe_dispatch(x, top_p, top_i, gate, up, down, act, capacity,
                          valid=valid, held=held)
    return y, _work(E, E * (N if exact else capacity))
