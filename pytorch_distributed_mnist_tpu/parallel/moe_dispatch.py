"""Capacity-factor MoE dispatch: all_to_all token routing (GShard-style).

The dense-dispatch ``SwitchMoE`` (models/moe.py) runs every expert's FLOPs
on every token algebraically and lets EP sharding recover the per-device
FLOPs; that keeps the math layout-independent but moves the full (B, E, H)
activation through HBM. This module is the scale formulation the docstring
there promises: each token is physically dispatched to ONE expert's buffer,
bounded by a capacity factor, and tokens cross the ``expert`` mesh axis as
one ``lax.all_to_all`` each way — the XLA collective that rides ICI, the
TPU analog of the reference stack's NCCL alltoall in DeepSpeed-style MoE
(the reference itself has no experts at all:
``/root/reference/multi_proc_single_gpu.py:119-126``, SURVEY.md section 2c
EP ABSENT).

Shape walk (per device, inside shard_map over the ``expert`` axis):

    x_loc (Bg, M) --dispatch one-hot--> (E, Cap, M)        local einsum
      --all_to_all(expert)-->           (G, E_loc, Cap, M) tokens to owners
      --expert MLP (local weights)-->   (G, E_loc, Cap, M)
      --all_to_all back-->              (E, Cap, M)
      --combine one-hot * gate-->       (Bg, M)

Tokens beyond an expert's capacity ``ceil(Bg * cf / E)`` are dropped (their
combine weight is zero — the residual connection in ``MoEClassifier``
carries them through unchanged), the standard switch-transformer contract.
With no oversubscription the result equals dense dispatch exactly, which
is what tests/test_moe_dispatch.py pins.

Routing/dispatch tensors are built in f32 (top-1 is a discrete decision;
bf16 logit noise would make the routing layout-dependent).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "top1_mask_gate",
    "build_dispatch",
    "moe_capacity_forward",
    "load_balance_loss",
    "route_topk",
    "expert_load",
    "sequence_balance_loss",
    "held_experts_forward",
]


def top1_mask_gate(probs: jnp.ndarray):
    """(B, E) router probs -> (one-hot mask (B, E), routed prob gate (B,)).

    THE routing decision, shared by dense dispatch (models/moe.py),
    capacity dispatch, and the aux loss — one implementation so
    tie-breaking/dtype changes can never make them disagree (the
    dense == capacity equivalence tests assume identical routing).
    """
    e = probs.shape[-1]
    mask = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e, dtype=probs.dtype)
    gate = jnp.sum(probs * mask, axis=-1)
    return mask, gate


def build_dispatch(probs: jnp.ndarray, capacity: int):
    """(B, E) router probs -> one-hot dispatch/combine (B, E, Cap).

    Top-1 routing with in-order capacity assignment: the k-th token routed
    to expert e takes slot k; tokens with k >= capacity are dropped (both
    tensors zero for them).
    """
    mask, gate = top1_mask_gate(probs)
    # 0-indexed arrival position of each token within its expert's queue.
    pos = jnp.cumsum(mask, axis=0) * mask - mask
    keep = mask * (pos < capacity)
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=probs.dtype
    )  # (B, E, Cap)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def load_balance_loss(probs: jnp.ndarray) -> jnp.ndarray:
    """Switch-transformer auxiliary loss: ``E * sum_e f_e * p_e``.

    ``f_e`` = fraction of tokens top-1-routed to expert e, ``p_e`` = mean
    router probability of e. Equals 1.0 under perfectly uniform routing;
    grows as routing collapses onto few experts. Differentiable through
    ``p_e`` (the ``f_e`` factor is piecewise constant), which is exactly
    the gradient the switch paper uses to spread the router.
    """
    e = probs.shape[-1]
    mask, _ = top1_mask_gate(probs)
    f = jnp.mean(mask, axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)


def _expert_mlp(ei, w1, b1, w2, b2, compute_dtype):
    """(..., E, Cap, M) tokens through per-expert two-layer MLPs."""
    ei = ei.astype(compute_dtype)
    h = jax.nn.relu(
        jnp.einsum("...ecm,emh->...ech", ei, w1.astype(compute_dtype))
        + b1.astype(compute_dtype)[..., :, None, :]
    )
    return (
        jnp.einsum("...ech,ehm->...ecm", h, w2.astype(compute_dtype))
        + b2.astype(compute_dtype)[..., :, None, :]
    )


def moe_capacity_forward(
    x: jnp.ndarray,
    probs: jnp.ndarray,
    w1: jnp.ndarray,  # (E, M, H)
    b1: jnp.ndarray,  # (E, H)
    w2: jnp.ndarray,  # (E, H, M)
    b2: jnp.ndarray,  # (E, M)
    *,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    expert_axis: str = "expert",
    data_axis: Optional[str] = "data",
) -> jnp.ndarray:
    """Capacity-dispatched switch layer: (B, M) -> (B, M).

    Without a mesh (or with a 1-sized expert axis) this is the pure local
    program — same math, no collectives — used by tests as the oracle for
    the distributed path. With a mesh, tokens are grouped over
    ``(data_axis, expert_axis)`` and experts over ``expert_axis``; the two
    ``all_to_all``s exchange token buffers with expert owners.
    """
    e = w1.shape[0]

    def local_forward(x_loc, probs_loc, w1_l, b1_l, w2_l, b2_l, n_groups):
        bg = x_loc.shape[0]
        capacity = max(1, math.ceil(bg * capacity_factor / e))
        dispatch, combine = build_dispatch(probs_loc.astype(jnp.float32),
                                           capacity)
        ei = jnp.einsum("bec,bm->ecm", dispatch.astype(x_loc.dtype), x_loc)
        if n_groups == 1:
            y = _expert_mlp(ei, w1_l, b1_l, w2_l, b2_l, compute_dtype)
        else:
            e_loc = e // n_groups
            ei = ei.reshape((n_groups, e_loc) + ei.shape[1:])
            # (G, E_loc, Cap, M): dim 0 becomes the sender-group index.
            ei = lax.all_to_all(ei, expert_axis, split_axis=0, concat_axis=0)
            y = _expert_mlp(ei, w1_l, b1_l, w2_l, b2_l, compute_dtype)
            y = lax.all_to_all(y, expert_axis, split_axis=0, concat_axis=0)
            y = y.reshape((e,) + y.shape[2:])
        return jnp.einsum(
            "ecm,bec->bm", y.astype(jnp.float32), combine
        ).astype(x_loc.dtype)

    if mesh is None or mesh.shape.get(expert_axis, 1) == 1:
        return local_forward(x, probs, w1, b1, w2, b2, 1)

    n = mesh.shape[expert_axis]
    if e % n:
        raise ValueError(f"{e} experts not divisible by {expert_axis}={n}")
    token_axes = (
        (data_axis, expert_axis)
        if data_axis and mesh.shape.get(data_axis, 1) > 1
        else (expert_axis,)
    )
    n_groups = 1
    for a in token_axes:
        n_groups *= mesh.shape[a]
    if x.shape[0] % n_groups:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by the {n_groups} token "
            f"groups of mesh axes {token_axes} (capacity dispatch shards "
            f"tokens over them)"
        )
    tok = P(token_axes)
    ex = P(expert_axis)
    return jax.shard_map(
        lambda *a: local_forward(*a, n),
        mesh=mesh,
        in_specs=(tok, tok, ex, ex, ex, ex),
        out_specs=tok,
        check_vma=False,
    )(x, probs, w1, b1, w2, b2)


# --------------------------------------------------------------------------
# Top-k routing over all experts, computed for the experts held here
# --------------------------------------------------------------------------
#
# What expert parallelism asks of one chip, without its exchange: the router
# scores all ``E`` experts and picks ``k`` a token; this chip holds experts
# ``[first, first + count)`` and computes their part of the result for the
# (token, choice) pairs that name one of them. Nothing is dropped: the pairs
# are sorted by expert into a buffer of ``N * k`` rows, which no routing can
# overflow, and the three matmuls run grouped by expert over the rows that
# hold a pair (``jax.lax.ragged_dot``: on a TPU, XLA's own grouped-matmul
# kernel, whose grid follows the group sizes, so its cost follows the pairs
# that landed here, not ``N * k`` and not ``count * N``).


# The name of the chosen experts among a block's intermediate values, for a
# recomputing caller's policy (``jax.checkpoint_policies
# .save_only_these_names``): a choice is kept, never made again.
CHOICE_NAME = "expert_choice"


def route_topk(scores: jnp.ndarray, top_k: int, scale: float,
               bias: Optional[jnp.ndarray] = None):
    """(N, E) float32 router scores -> ``(idx, weight)``, both (N, k): the
    ``k`` largest a token and their weights ``scale * s_e / sum_chosen s``,
    normalised over all ``k`` chosen wherever their experts live. With a
    selection ``bias`` (E,) the ``k`` chosen are the largest of ``scores +
    bias`` and the weights still read the scores alone (arXiv:2412.19437,
    eq. 16: the bias steers the load and never enters a weight).

    The weights read the scores at ``idx`` and ``idx`` carries
    ``CHOICE_NAME``: under per-block recomputation the backward pass
    computes the scores again, and XLA rounds the recomputed block's
    bfloat16 values at other places than the forward's, so a token whose
    k-th and (k+1)-th scores are nearly tied would be given another expert
    in the backward pass than the one its forward result came from (0.3%
    of the pairs at 1,024 tokens; the routed leaves' gradients were 2-12%
    off for it). With the choice kept, both passes see one routing."""
    _, idx = lax.top_k(scores if bias is None else scores + bias, top_k)
    idx = checkpoint_name(idx.astype(jnp.int32), CHOICE_NAME)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    weight = vals / jnp.sum(vals, axis=-1, keepdims=True) * scale
    return idx, weight


def expert_load(idx: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """(..., T, k) chosen experts -> (..., E) float32: the (token, choice)
    pairs each of all ``num_experts`` was given, whoever holds it."""
    hit = idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype)
    return jnp.sum(hit, axis=(-3, -2), dtype=jnp.float32)


def sequence_balance_loss(scores: jnp.ndarray, load: jnp.ndarray,
                          top_k: int) -> jnp.ndarray:
    """The sequence-wise balance term of arXiv:2412.19437, eq. 17-20, without
    its weight: ``mean over sequences of sum_e f_e P_e`` with ``f_e = E / (k
    T) * load_e`` (``load`` (B, E): the pairs of one sequence, no gradient)
    and ``P_e`` the mean over the sequence's ``T`` tokens of ``s_e / sum_e'
    s_e'`` (``scores`` (B, T, E)). 1.0 under a uniform load."""
    t, e = scores.shape[-2:]
    f = lax.stop_gradient(load) * (e / (top_k * t))
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=-2)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def _sort_pairs(idx: jnp.ndarray, first: int, count: int):
    """Order the ``N * k`` (token, choice) pairs by held expert, the pairs
    of experts held elsewhere last. Returns ``order`` (slot -> pair),
    ``inv`` (pair -> slot), ``sizes`` (count,) rows per held expert and
    ``local`` (N, k) bool."""
    local = (idx >= first) & (idx < first + count)
    key = jnp.where(local, idx - first, count).reshape(-1)
    pairs = jnp.arange(key.shape[0], dtype=jnp.int32)
    sorted_key, order = lax.sort((key, pairs), num_keys=1, is_stable=True)
    _, inv = lax.sort((order, pairs), num_keys=1)
    starts = jnp.searchsorted(
        sorted_key, jnp.arange(count + 1, dtype=key.dtype), side="left")
    return order, inv, jnp.diff(starts).astype(jnp.int32), local


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_matmul(xs, w, sizes, valid, mask_out):
    """``ragged_dot`` over the sorted rows. A grouped kernel leaves the rows
    past the last group unwritten: ``mask_out`` zeroes them (``valid``
    (N * k, 1) marks the rows that hold a pair), and where the consumer
    selects for itself it is left off, which saves a pass over the result.
    Callers pass zero rows there. The transpose's rows are always zeroed:
    they meet activations in a product."""
    y = lax.ragged_dot(xs, w, sizes, preferred_element_type=xs.dtype)
    return jnp.where(valid, y, 0) if mask_out else y


def _grouped_fwd(xs, w, sizes, valid, mask_out):
    return (_grouped_matmul(xs, w, sizes, valid, mask_out),
            (xs, w, sizes, valid))


def _grouped_bwd(mask_out, res, dy):
    del mask_out
    xs, w, sizes, valid = res
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(
        a, b, sizes, preferred_element_type=xs.dtype), xs, w)
    dxs, dw = vjp(dy)
    return jnp.where(valid, dxs, 0), dw, None, None


_grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _to_slots(x, token):
    """Row ``token[j]`` of ``x`` (N, C) for every slot ``j``; slots that
    hold no local pair name row N, a row of zeros appended here, so the
    gather needs no select after it."""
    return jnp.concatenate([x, jnp.zeros_like(x[:1])])[token]


def _to_tokens(y, inv, local):
    """``out[n] = sum over the local pairs (n, c) of y[slot of (n, c)]``,
    float32. The select sits after the gather, inside the sum's fusion,
    because the slots of pairs held elsewhere hold undefined rows."""
    n, k = local.shape
    picked = y[inv].reshape(n, k, -1)
    return jnp.sum(jnp.where(local[..., None], picked, 0), axis=1,
                   dtype=jnp.float32)


@jax.custom_vjp
def _dispatch(x, token, inv, local):
    """``x`` (N, C) to the sorted slots, (N * k, C). Its transpose is a
    gather too, ``_to_tokens``: every pair of a token has one slot."""
    return _to_slots(x, token)


def _dispatch_fwd(x, token, inv, local):
    return _to_slots(x, token), (inv, local)


def _dispatch_bwd(res, dxs):
    inv, local = res
    return _to_tokens(dxs, inv, local).astype(dxs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, token, inv, local):
    """The sorted slots' rows ``y`` (N * k, C) summed back to their tokens,
    (N, C) float32: ``_dispatch``'s transpose, and the other way round."""
    return _to_tokens(y, inv, local)


def _combine_fwd(y, token, inv, local):
    return _to_tokens(y, inv, local), (token, jnp.zeros((), y.dtype))


def _combine_bwd(res, g):
    token, like = res
    return _to_slots(g.astype(like.dtype), token), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _sorted(values, order, inv):
    """``values[order]`` for a permutation ``order`` with inverse ``inv``:
    the transpose is the gather ``[inv]``, not a scatter-add."""
    return values[order]


_sorted.defvjp(lambda values, order, inv: (values[order], inv),
               lambda inv, g: (g[inv], None, None))


def _served(expert, token, inv, sizes):
    """(N, k) bool: the pairs whose result the combine reads from a row
    that the grouped matmuls computed from the pair's own token with the
    pair's own expert. Read off the arrays the three steps index by, as
    they do: the combine reads pair ``(n, c)`` from slot ``inv[n, c]``;
    the dispatch filled that slot from row ``token[slot]``; a grouped
    matmul gives row ``r`` the weights of the group ``g`` with ``sum
    sizes[:g] <= r < sum sizes[:g + 1]`` and leaves the rows past the last
    group unwritten. ``expert`` (N, k) counts from the first held one."""
    n, k = expert.shape
    slot = inv.reshape(n, k)
    group = jnp.searchsorted(jnp.cumsum(sizes), slot, side="right",
                             method="compare_all")
    own_row = token[slot] == jnp.arange(n, dtype=token.dtype)[:, None]
    return own_row & (group == expert)


def held_experts_forward(x, idx, weight, w_gate, w_up, w_down, *,
                         first: int):
    """The held experts' part of a top-k expert layer.

    ``x`` (N, C) tokens; ``idx``, ``weight`` (N, k) from :func:`route_topk`
    over all experts; ``w_gate``, ``w_up`` (count, C, F) and ``w_down``
    (count, F, C) the SwiGLU weights of experts ``first .. first + count``.
    Returns ``(out, counters)``: ``out`` (N, C) float32 is
    ``sum_{e chosen and held} weight_e * E_e(x)``; ``counters`` is the
    float32 vector ``[pairs landed here, pairs routed (N * k), pairs
    dropped, tokens of the fullest held expert over the mean, 1]``
    (summed over layers and steps by the caller; the last entry counts the
    summands). A pair that names a held expert is dropped unless it was
    served (:func:`_served`).

    A pair's weight multiplies its expert's hidden row (F wide) before the
    down projection, not the result (C wide): the same product, on a
    quarter of the bytes. Every move between tokens and slots, forward and
    backward, is a gather.
    """
    n, k = idx.shape
    count = w_gate.shape[0]
    with jax.named_scope("dispatch"):
        order, inv, sizes, local = _sort_pairs(idx, first, count)
        landed = jnp.sum(sizes)
        valid = jnp.arange(n * k, dtype=jnp.int32) < landed
        token = jnp.where(valid, order // k, n)
        xs = _dispatch(x, token, inv, local)
        w_sorted = _sorted(
            jnp.where(local, weight, 0.0).reshape(-1), order, inv)
        valid = valid[:, None]
    with jax.named_scope("experts"):
        gate = _grouped_matmul(xs, w_gate.astype(x.dtype), sizes, valid, True)
        up = _grouped_matmul(xs, w_up.astype(x.dtype), sizes, valid, True)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32) * w_sorted[:, None])
        y = _grouped_matmul(hidden.astype(x.dtype), w_down.astype(x.dtype),
                            sizes, valid, False)
    with jax.named_scope("combine"):
        out = _combine(y, token, inv, local)
    sizes_f = sizes.astype(jnp.float32)
    served = _served(idx - first, token, inv, sizes)
    counters = jnp.stack([
        landed.astype(jnp.float32),
        jnp.float32(n * k),
        jnp.sum((local & ~served).astype(jnp.float32)),
        jnp.max(sizes_f) / jnp.maximum(jnp.mean(sizes_f), 1e-9),
        jnp.float32(1.0),
    ])
    return out, counters
