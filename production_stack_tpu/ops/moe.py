"""Mixture-of-experts MLP: top-k routing with static-shape dispatch.

TPU-first design (the reference stack has no model code — MoE models are
strings passed to ``vllm serve``, reference:
helm/templates/deployment-vllm-multi.yaml:57-64; expert parallelism is a
``--enable-expert-parallel``-style engine passthrough, SURVEY.md §2.9):

- Routing, dispatch and combine are all static-shape jnp — no
  data-dependent shapes, so the whole block lives inside the engine's
  jitted prefill/decode executables and XLA can schedule it.
- Two dispatch strategies, chosen at trace time by token count N:

  **Exact (small N, the decode path).** Every expert runs over all N
  tokens and results are combined with the routing weights ([N, E],
  zero for unselected experts). At decode sizes (N = batch ≤ ~tens)
  this is bandwidth-equivalent to "perfect" dispatch — with N*k
  assignments over E experts nearly every expert is touched anyway, so
  the step still streams every expert's weights once — and it is exact:
  no token is ever dropped.

  **Capacity dispatch (large N, the prefill path).** The GShard/Switch
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

Routing follows Mixtral semantics: fp32 softmax over all experts, then
top-k, then renormalize the selected probabilities to sum to 1.
"""

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def capacity_for(n_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert token capacity: factor × the perfectly-balanced load,
    8-aligned (TPU sublane), clamped to [8, n_tokens]."""
    balanced = n_tokens * top_k / num_experts
    cap = int(-(-capacity_factor * balanced // 8) * 8)
    return max(8, min(cap, n_tokens))


def route(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
          renormalize: bool = True):
    """Top-k routing. x [N, h], router_w [h, E] ->
    (weights [N, k] fp32, expert ids [N, k] int32). renormalize=True is
    Mixtral semantics (selected weights re-sum to 1); False keeps the
    raw softmax probabilities (Qwen2-MoE's norm_topk_prob=False)."""
    logits = jnp.einsum("nh,he->ne", x, router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
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


def _edot(xb: jnp.ndarray, w) -> jnp.ndarray:
    """einsum('ec?,e?o->eco') with weight-only int8 dequant applied in
    the epilogue (per-expert, per-output-channel scale)."""
    if _quant().is_quantized(w):
        y = jnp.einsum("eci,eio->eco", xb, w["w8"].astype(xb.dtype))
        return y * w["scale"].astype(xb.dtype)[:, None, :]
    return jnp.einsum("eci,eio->eco", xb, w)


def _expert_ffn(xb: jnp.ndarray, gate, up, down,
                act: Callable) -> jnp.ndarray:
    """Batched per-expert FFN. xb [E, C, h] -> [E, C, h]."""
    g = _edot(xb, gate)
    u = _edot(xb, up)
    return _edot(act(g) * u, down)


def _moe_exact(x, top_p, top_i, gate, up, down, act):
    """All experts over all tokens, combined by routing weight."""
    N = x.shape[0]
    E = _wshape(gate)[0]
    with jax.named_scope("moe_experts"):
        xb = jnp.broadcast_to(x, (E,) + x.shape)        # [E, N, h]
        y_e = _expert_ffn(xb, gate, up, down, act)      # [E, N, h]
    with jax.named_scope("moe_combine"):
        # combine [N, E]: routing weight where selected, else 0
        combine = jnp.zeros((N, E), jnp.float32)
        combine = combine.at[
            jnp.arange(N)[:, None], top_i].set(top_p)
        return jnp.einsum("enh,ne->nh", y_e,
                          combine.astype(x.dtype))


def _moe_dispatch(x, top_p, top_i, gate, up, down, act, capacity,
                  valid=None):
    """Scatter-based capacity dispatch (see module docstring)."""
    N, h = x.shape
    E = _wshape(gate)[0]
    k = top_i.shape[1]

    flat_e = top_i.reshape(-1)                          # [N*k] token-major
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    if valid is not None:
        # padding tokens must not compete for expert capacity: drop
        # their assignments from the rank count and the buffers
        valid_rep = jnp.repeat(valid.astype(jnp.int32), k)
        onehot = onehot * valid_rep[:, None]
    # rank of each assignment within its expert (how many earlier
    # assignments chose the same expert)
    prior = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(prior, flat_e[:, None], axis=1)[:, 0]
    keep = rank < capacity
    if valid is not None:
        keep = keep & (valid_rep > 0)
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
            capacity_factor: float = 2.0, dense_threshold: int = 64,
            act: Callable = jax.nn.silu, valid=None,
            exact=None, renormalize: bool = True,
            capacity_tokens=None) -> jnp.ndarray:
    """MoE feed-forward. x [N, h]; router_w [h, E]; gate/up [E, h, i];
    down [E, i, h]. Returns [N, h] in x.dtype.

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
    """
    N = x.shape[0]
    E = _wshape(gate)[0]
    with jax.named_scope("moe_router"):
        top_p, top_i = route(x, router_w, top_k, renormalize=renormalize)
        if valid is not None:
            top_p = top_p * valid.astype(top_p.dtype)[:, None]
    capacity = min(N, capacity_for(capacity_tokens or N, E, top_k,
                                   capacity_factor))
    if exact is None:
        exact = N <= dense_threshold or capacity >= N
    if exact:
        return _moe_exact(x, top_p, top_i, gate, up, down, act)
    return _moe_dispatch(x, top_p, top_i, gate, up, down, act, capacity,
                         valid=valid)
