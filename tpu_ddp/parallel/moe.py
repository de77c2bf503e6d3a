"""Expert parallelism: Switch-style mixture-of-experts over ``ep``.

No reference counterpart (the reference implements data parallelism only —
SURVEY.md §2 "Absent parallelism strategies"); included because multi-axis
model sharding is first-class in this framework. The layer is a top-k
routed MoE MLP — top-1 per Switch Transformers (Fedus et al.,
arXiv:2101.03961), top-2 per GShard (Lepikhin et al., arXiv:2006.16668);
both reimplemented from the papers' routing algebra, not from any code —
expressed the SPMD way:

- expert weights are STACKED on a leading expert axis and sharded over
  the ``ep`` mesh axis — each device hosts ``num_experts / ep`` experts;
- tokens are data-parallel over (dp × ep): every device routes its OWN
  tokens, builds a (tokens, experts, capacity) one-hot dispatch tensor,
  and two ``lax.all_to_all``s move token activations to their expert's
  host device and back — the ep-analogue of the pipeline's ppermute ring;
- capacity is static: ``C = ceil(T * capacity_factor * top_k / E)``
  slots per expert per source device, shared by a token's k choices.
  Assignments beyond an expert's capacity are dropped (that branch
  contributes zero; the residual stream still carries the token) — the
  standard static-shape trade XLA needs;
- the router is differentiable through the combine weights (the chosen
  expert's probability scales its output), and the Switch auxiliary
  load-balancing loss ``E * Σ_e f_e·P_e`` is returned alongside so the
  trainer can regularize routing collapse.

Gradient flow needs no custom rules: dispatch/combine are einsums against
a stop-gradient one-hot, and ``all_to_all`` transposes to the reverse
``all_to_all``. Exactness of the ep-sharded layer vs its single-device
execution is tested in tests/test_moe.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpu_ddp.parallel.mesh import EXPERT_AXIS


def topk_route(router_logits, num_experts: int, capacity: int,
               top_k: int = 1):
    """Top-k routing: (T, E) logits -> (dispatch, combine, aux).

    ``dispatch``: (T, E, C) one-hots of each kept token's (expert, slot)
    assignments — up to ``top_k`` per token. ``combine``: dispatch scaled
    by the router gates (the differentiable path into the router).
    ``aux``: load-balance loss over the FIRST choice (the Switch form).

    ``top_k == 1`` is Switch routing with the raw probability as gate;
    ``top_k > 1`` is the GShard scheme (arXiv:2006.16668 — reimplemented
    from the paper's algebra, not from any code): iterative argmax over
    masked probabilities, gates renormalized over the chosen experts,
    and later choices queue in an expert's capacity AFTER the slots the
    earlier choices kept (so slots never collide).
    """
    if not 1 <= top_k <= num_experts:
        raise ValueError(f"top_k={top_k} must be in [1, num_experts="
                         f"{num_experts}] (beyond E the argmax of the "
                         "fully-masked probabilities would silently "
                         "re-route everything to expert 0)")
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    remaining = probs
    onehots, gates = [], []
    for _ in range(top_k):
        expert = jnp.argmax(remaining, axis=-1)             # (T,)
        oh = jax.nn.one_hot(expert, num_experts,
                            dtype=jnp.float32)              # (T, E)
        onehots.append(oh)
        gates.append(jnp.sum(probs * oh, axis=-1))          # (T,)
        remaining = remaining * (1.0 - oh)
    if top_k == 1:
        weights = gates                                     # raw (Switch)
    else:
        denom = sum(gates) + 1e-9
        weights = [g / denom for g in gates]                # normalized

    base = jnp.zeros((num_experts,), jnp.float32)  # slots already taken
    dispatch = jnp.zeros((router_logits.shape[0], num_experts, capacity),
                         jnp.float32)
    combine = dispatch
    for oh, w in zip(onehots, weights):
        # Slot of each token within its expert's queue, in token order,
        # offset past the slots earlier choices kept.
        pos = (jnp.cumsum(oh, axis=0) - 1.0 + base[None, :]) * oh
        kept = oh * (pos < capacity)                        # (T, E)
        slot = jax.nn.one_hot(
            jnp.sum(pos * kept, axis=-1).astype(jnp.int32),
            capacity, dtype=jnp.float32)                    # (T, C)
        d = kept[:, :, None] * slot[:, None, :]             # (T, E, C)
        dispatch = dispatch + d
        combine = combine + lax.stop_gradient(d) * w[:, None, None]
        base = base + jnp.sum(kept, axis=0)
    # Load balance: fraction first-routed to e times mean prob of e.
    f = jnp.mean(onehots[0], axis=0)
    p = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(f * p)
    return lax.stop_gradient(dispatch), combine, aux


def switch_route(router_logits, num_experts: int, capacity: int):
    """Top-1 (Switch) routing — see :func:`topk_route`."""
    return topk_route(router_logits, num_experts, capacity, top_k=1)


def routing_stats(dispatch, top_k: int = 1):
    """Routing-health counters from a (T, E, C) dispatch tensor.

    ``dropped_frac``: fraction of the T*top_k routing assignments that
    found no capacity slot (those branches contribute zero; the token
    rides the residual stream). ``expert_load``: (E,) fraction of all
    assignments each expert kept — sums to ``1 - dropped_frac``.
    ``imbalance``: the hottest expert's load relative to the uniform
    share (1.0 = perfectly balanced; ``E`` = total collapse onto one
    expert). All float32, cheap enough to ride along every step.
    """
    t, e = dispatch.shape[0], dispatch.shape[1]
    per_expert = jnp.sum(dispatch, axis=(0, 2))             # (E,) kept
    total = jnp.float32(t * max(top_k, 1))
    load = per_expert / total
    return {"dropped_frac": 1.0 - jnp.sum(load),
            "expert_load": load,
            "imbalance": jnp.max(load) * e}


def moe_mlp(y, router_w, w1, w2, *, num_experts: int,
            capacity_factor: float = 1.25, top_k: int = 1,
            ep_axis: str = EXPERT_AXIS,
            ep_size: int = 1, activation=None,
            tp_in=None, tp_out=None, stats=None):
    """Top-k routed MoE MLP: (B, L, dm) -> ((B, L, dm), aux).

    ``w1``: (E_local, dm, dff_local), ``w2``: (E_local, dff_local, dm) —
    stacked expert weights, already sharded over ``ep`` (and optionally
    ``mp`` via the ``tp_in``/``tp_out`` Megatron hooks). Must run inside
    a shard_map over ``ep_axis`` when ``ep_size > 1``.

    ``stats``: optional mutable list; when given, this call appends its
    :func:`routing_stats` dict (per-shard numbers under ep — diagnostic
    callers run the dense configuration).
    """
    b, L, dm = y.shape
    T = b * L
    E = num_experts
    e_loc = w1.shape[0]
    if e_loc * max(ep_size, 1) != E:
        raise ValueError(f"{w1.shape[0]} local experts x ep={ep_size} "
                         f"!= num_experts={E}")
    # top_k choices per token share the capacity budget.
    cap = max(1, int(-(-T * capacity_factor * max(top_k, 1) // E)))
    act = activation or (lambda h: jax.nn.gelu(h.astype(jnp.float32)))
    cd = y.dtype

    x = y.reshape(T, dm)
    logits = jnp.dot(x, router_w.astype(cd),
                     preferred_element_type=jnp.float32)    # (T, E)
    dispatch, combine, aux = topk_route(logits, E, cap, top_k=top_k)
    if stats is not None:
        stats.append(routing_stats(dispatch, top_k=top_k))

    # (T, E, C) x (T, dm) -> (E, C, dm): gather each expert's slot queue.
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cd), x,
                           preferred_element_type=jnp.float32).astype(cd)
    if ep_size > 1:
        # Exchange: split the expert axis across ep peers, concatenate
        # the per-source queues -> (E_local, ep*C, dm) on each device.
        expert_in = lax.all_to_all(expert_in, ep_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
    h_in = tp_in(expert_in) if tp_in is not None else expert_in
    h = jnp.einsum("ecd,edf->ecf", h_in, w1.astype(cd),
                   preferred_element_type=jnp.float32)
    h = act(h).astype(cd)
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(cd),
                     preferred_element_type=jnp.float32)
    out = (tp_out(out) if tp_out is not None else out).astype(cd)
    if ep_size > 1:
        # Reverse exchange: every token's output returns to its source.
        out = lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)
    # (T, E, C) x (E, C, dm) -> (T, dm): weight by router prob; dropped
    # tokens (no slot) get zeros and ride the residual stream unchanged.
    y_out = jnp.einsum("tec,ecd->td", combine.astype(cd), out,
                       preferred_element_type=jnp.float32).astype(cd)
    return y_out.reshape(b, L, dm), aux


def grouped_dot(rows, w, sizes):
    """``rows`` (m, k) sorted by group times ``w`` (groups, k, n), group
    ``g`` being the next ``sizes[g]`` rows: (m, n) float32. The Pallas
    kernel (ops/pallas/grouped_matmul.py) where its predicate takes the
    shapes and dtypes, and ``lax.ragged_dot``, its definition, where it
    does not. The rows past the last group hold nothing a caller may
    use (the kernel writes zeros there, ``ragged_dot`` what it likes)."""
    from tpu_ddp.ops.pallas import grouped_matmul as kernel
    if kernel.supports(rows.shape[0], w.shape[1], w.shape[2], rows.dtype,
                       w.dtype):
        return kernel.grouped_matmul(rows, w, sizes)
    return lax.ragged_dot(rows, w, sizes,
                          preferred_element_type=jnp.float32)


def dropless_moe(x, router_w, w1, w2, *, top_k: int, held: tuple):
    """Dropless top-k routed gated MLP, for a chip that holds a share of
    the experts: (T, dm) -> (T, dm) float32.

    ``router_w`` (dm, E) routes over ALL ``E`` experts; ``held = (lo,
    hi)`` says which of them ``w1`` (hi-lo, dm, 2 ff) and ``w2`` (hi-lo,
    ff, dm) are. Each token takes its ``top_k`` largest router logits,
    gates ``g`` = softmax over those chosen logits in float32, and the
    result is the sum of ``g_e * (SiLU(u) * v) @ w2[e]``, ``[u, v] = x @
    w1[e]``, over the chosen experts THAT ARE HELD: what the absent
    experts would have added is left out (the other chips of the layer
    compute it; on one chip there is no exchange). Every assignment is
    computed, none dropped, and every shape is static whatever the
    routing: the ``T * top_k`` assignments are sorted by expert (those of
    absent experts last, in no group) and the two products are grouped
    ones (:func:`grouped_dot`) over the sorted rows, not a (T, E, C)
    dispatch tensor. The capacity form above stays for the models that
    train with it."""
    T = x.shape[0]
    lo, hi = held
    n = hi - lo
    cd = x.dtype
    with jax.named_scope("route"):
        logits = jnp.dot(x, router_w.astype(cd),
                         preferred_element_type=jnp.float32)    # (T, E)
        vals, idx = lax.top_k(logits, top_k)
        gates = jax.nn.softmax(vals, axis=-1)                   # (T, k)
        local = idx - lo
        mine = (local >= 0) & (local < n)
        group = jnp.where(mine, local, n).reshape(-1)           # (T*k,)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        weight = jnp.where(mine, gates, 0.0).reshape(-1)[order]
        # where each assignment went, to bring the results back
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
    with jax.named_scope("experts"):
        rows = x[order // top_k]                                # (T*k, dm)
        uv = grouped_dot(rows, w1.astype(cd), sizes)
        u, v = jnp.split(uv, 2, axis=-1)
        act = (jax.nn.silu(u) * v).astype(cd)
        out = grouped_dot(act, w2.astype(cd), sizes)
        # rows past the last group (absent experts) hold nothing defined
        out = jnp.where((weight > 0)[:, None], out * weight[:, None], 0.0)
        return out[back].reshape(T, top_k, -1).sum(axis=1)
