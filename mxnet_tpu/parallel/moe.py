"""Expert parallelism: mixture-of-experts FFN over an ``expert`` mesh axis.

The reference has no MoE (SURVEY.md §2.21 marks expert parallel absent);
this is the modern capability the TPU build adds on top of parity. The
design is the TPU-idiomatic dense-dispatch form (Switch Transformer /
GShard): routing builds dispatch/combine tensors, expert inputs are
gathered with an einsum, and ``with_sharding_constraint`` pins the expert
dimension to the ``expert`` mesh axis — XLA/GSPMD then lowers the two
dispatch einsums to ``all_to_all`` collectives over ICI. No hand-written
comms; everything stays differentiable and jit-compatible.

:func:`moe_share_apply` is the other form, for a chip that holds a share
of a wider layer's experts (serving): it is told which experts it holds,
routes over all of them, sorts the assignments by expert, runs grouped
products over the experts held and drops nothing.
"""
from __future__ import annotations

from typing import Optional

__all__ = ["moe_init", "moe_apply", "moe_share_apply", "route_sigmoid",
           "sharding_island"]


def sharding_island():
    """Canonical layout claims of the expert-parallel island (audited by
    ``analysis.sharding_passes.check_islands``): drawn from the unified
    SpecLayout — tokens arrive batch-sharded over ``(data, fsdp)`` like
    everywhere else, and the expert dimension rides the canonical ``tp``
    model axis (the all_to_all dispatch axis), so the audit reports zero
    cross-island disagreements."""
    from .layout import island_specs
    return "moe", island_specs("moe")


def moe_init(rng, d_model: int, d_hidden: int, n_experts: int, dtype=None):
    """Initialize router + expert FFN parameters.

    Returns {"router": (d, E), "wi": (E, d, h), "wo": (E, h, d)}.
    """
    import numpy as np
    dtype = dtype or np.float32
    s_in = 1.0 / np.sqrt(d_model)
    s_hid = 1.0 / np.sqrt(d_hidden)
    return {
        "router": (rng.normal(0, s_in, (d_model, n_experts))).astype(dtype),
        "wi": (rng.normal(0, s_in, (n_experts, d_model, d_hidden))
               ).astype(dtype),
        "wo": (rng.normal(0, s_hid, (n_experts, d_hidden, d_model))
               ).astype(dtype),
    }


def moe_apply(params, x, *, top_k: int = 2, capacity_factor: float = 1.25,
              mesh=None, axis: Optional[str] = None):
    """Apply the MoE FFN to tokens ``x`` of shape (tokens, d_model).

    Routing is top-``top_k`` softmax gating with per-expert capacity
    ``C = ceil(tokens * top_k * capacity_factor / E)``; tokens over
    capacity at an expert are dropped for that expert (standard Switch
    semantics — gate mass is renormalized over surviving assignments).

    Under ``jit`` with ``mesh``, the expert dimension of the dispatched
    activations is sharded over ``axis`` so each device runs only its
    experts; the surrounding einsums become all_to_all + local matmul.
    ``axis=None`` resolves to the legacy ``expert`` axis when the mesh
    carries it, else the unified SpecLayout's model axis (``tp``).
    Returns (tokens, d_model) combined outputs plus the load-balancing
    auxiliary loss (GShard aux: E * sum_e f_e * p_e).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        if axis is None:
            from .layout import resolve_model_axis
            axis = resolve_model_axis(mesh, "expert")
        elif axis not in mesh.axis_names:
            raise ValueError("mesh has no axis %r (axes: %s)"
                             % (axis, tuple(mesh.axis_names)))
    T, D = x.shape
    E = params["router"].shape[1]
    k = min(top_k, E)
    C = max(1, int(-(-T * k * capacity_factor // E)))  # ceil

    logits = x @ params["router"]                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)      # (T, k)

    # position of each (token, choice) in its expert's capacity buffer:
    # count prior assignments to the same expert in (token, choice) order.
    # Bookkeeping must stay int32: bf16 activations can't represent counts
    # above 256, which silently corrupts capacity slots for T*k > 256.
    choice_mask_i = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T, k, E)
    flat = choice_mask_i.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat              # (T*k, E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)   # (T, k)
    choice_mask = choice_mask_i.astype(x.dtype)
    keep = (pos < C).astype(x.dtype)
    gate_vals = gate_vals * keep
    denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
    gate_vals = gate_vals / jnp.maximum(denom, 1e-9)

    pos_oh = jax.nn.one_hot(pos, C, dtype=x.dtype)     # (T, k, C)
    # (T, E, C) combine weights; dispatch is its 0/1 support
    combine = jnp.einsum("tke,tk,tkc->tec", choice_mask, gate_vals, pos_oh)
    dispatch = jnp.einsum("tke,tk,tkc->tec", choice_mask, keep, pos_oh)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)  # (E, C, D)
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, jax.sharding.NamedSharding(mesh, P(axis, None, None)))
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, params["wi"]))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["wo"])
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, jax.sharding.NamedSharding(mesh, P(axis, None, None)))
    out = jnp.einsum("tec,ecd->td", combine, expert_out)

    # GShard load-balance aux loss: fraction routed vs mean gate prob
    frac = jnp.mean(choice_mask[:, 0, :], axis=0)      # top-1 routing share
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return out, aux


def route_sigmoid(x, router_weight, router_bias, *, top_k: int,
                  scaling: float):
    """Sigmoid routing with a selection bias (``noaux_tc``): scores ``s =
    sigmoid(W_r x)`` in float32; the ``top_k`` experts of largest ``s_e +
    b_e`` are chosen; their gates are ``scaling * s_e / sum_chosen s`` (the
    bias chooses, it does not weigh). Returns ``(experts (T, k) int32,
    gates (T, k) float32)``."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(jnp.float32),
        router_weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + router_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), gates


def moe_share_apply(x, experts, gates, w_gate, w_up, w_down, *,
                    first: int):
    """This chip's share of a routed SiLU-gated expert layer.

    ``x (T, D)``; ``experts``/``gates (T, k)`` as :func:`route_sigmoid`
    gives them, over ALL the layer's experts; the chip holds the experts
    ``first .. first + E`` as ``w_gate``/``w_up (E, D, F)`` and ``w_down
    (E, F, D)``, each expert's matrix ``(in, out)`` as the grouped product
    reads it. Every assignment to an expert held is computed, however
    many meet at one expert: the ``T * k`` assignments are sorted by
    expert (those to absent experts last, in a group no product runs
    over), the tokens' rows gathered in that order, and three grouped
    products (``lax.ragged_dot``) run over the groups. What the absent
    experts would add is left out.

    Returns ``(y (T, D) float32, counts (E,) int32)``: the share of the
    result, and how many assignments each expert held received.
    """
    import jax
    import jax.numpy as jnp
    t, k = experts.shape
    held = w_gate.shape[0]
    local = experts.reshape(-1) - first
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)            # absent experts last
    order = jnp.argsort(group, stable=True)
    counts = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    token = order // k
    rows = x[token]                                 # (T*k, D), by expert
    from .. import rtc

    def grouped(a, w):
        # (rows, in) x (E, in, out) -> (rows, out): the rows in the
        # weights' dtype, float32 accumulation
        a, w = rtc.product_operands(a, w)
        return jax.lax.ragged_dot(a, w, counts,
                                  preferred_element_type=jnp.float32)
    mid = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    out = grouped(mid, w_down)
    weight = jnp.where(here, gates.reshape(-1), 0.0)[order]
    # rows past the last group hold nothing that counts
    out = jnp.where(weight[:, None] != 0.0, out * weight[:, None], 0.0)
    y = jnp.zeros((t, x.shape[1]), jnp.float32).at[token].add(out)
    return y, counts
