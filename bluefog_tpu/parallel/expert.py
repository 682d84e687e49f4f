"""Expert parallelism: Switch-style mixture-of-experts over an ``ep`` axis.

No sibling in the reference (SURVEY.md §2.3: EP honestly absent upstream) —
the last of the composition bonuses (see :mod:`.tensor_parallel`,
:mod:`.pipeline`).  Experts shard over the ``ep`` mesh axis; tokens live
sharded over the same axis (each device routes its own token shard), and
dispatch/return ride a single ``lax.all_to_all`` pair — the canonical
TPU MoE wire pattern (Fedus et al., arXiv:2101.03961; Lepikhin et al.,
arXiv:2006.16668).

TPU-first choices: routing is the dense one-hot dispatch/combine einsum
formulation (everything stays MXU-shaped — no gather/scatter, no dynamic
shapes), capacity is static (``capacity_factor``), overflow tokens pass
through the residual untouched (standard Switch behavior).  Everything is
differentiable, including the router (gate probability scales the expert
output, the straight-through-free Switch estimator).

Beside it, the layer a chip's *share* of an expert-parallel deployment runs
(:func:`route_topk`, :func:`held_topk_experts`): it is told which experts
it holds, routes over all of them, keeps every assignment to an expert it
holds (no capacity, nothing dropped) and computes its own experts' part of
the result with a grouped matrix product over rows sorted by expert.  What
the experts held elsewhere would add is left out: on one chip the layer
runs without its exchange, and nothing stands in for the absent chips.
"""

from __future__ import annotations

import math
from fractions import Fraction
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.parallel._util import resolve_axis_size, vma_full
from bluefog_tpu.telemetry import registry as _telemetry

__all__ = ["switch_moe", "init_moe_params", "EP_AXIS", "route_topk",
           "held_topk_experts"]

EP_AXIS = "ep"


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32):
    """Full (unsharded) MoE params: router [d, E] (replicated), expert
    stacks wi [E, d, f] / wo [E, f, d] (shard axis 0 over ep: pass
    ``leaf.reshape(ep, E//ep, ...)`` stacked, or use ``in_specs
    P("ep")`` directly on the expert axis)."""
    kr, ki, ko = jax.random.split(key, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": (jax.random.normal(kr, (d_model, num_experts), jnp.float32)
                   * 0.02).astype(dtype),
        "wi": (jax.random.normal(ki, (num_experts, d_model, d_ff), jnp.float32)
               * scale_in).astype(dtype),
        "wo": (jax.random.normal(ko, (num_experts, d_ff, d_model), jnp.float32)
               * scale_out).astype(dtype),
    }


def switch_moe(
    x,
    params,
    axis_name: str = EP_AXIS,
    *,
    capacity_factor: float = 1.25,
    axis_size: Optional[int] = None,
    activation=jax.nn.gelu,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 (Switch) MoE layer; call inside ``shard_map``.

    ``x [T_local, d]`` — this device's token shard.  ``params``: ``router
    [d, E]`` replicated; ``wi [E_local, d, f]`` / ``wo [E_local, f, d]`` —
    this device's expert shard (``E = ep * E_local``).

    Returns ``(out [T_local, d], aux_loss)`` where ``aux_loss`` is the
    Switch load-balancing term (mean over devices), already ``pmean``-ed.
    """
    n = int(resolve_axis_size(axis_name, axis_size))
    e_local = params["wi"].shape[0]
    E = n * e_local
    if params["router"].shape[1] != E:
        raise ValueError(
            f"router is {params['router'].shape[1]} experts wide but "
            f"ep={n} x {e_local} local experts = {E}; pass this device's "
            f"[E/ep, ...] expert shard, not the full stack"
        )
    T = x.shape[0]
    # per-device, per-expert slot budget (ceil: capacity_factor headroom
    # must yield slots even when T/E is small)
    cap = max(1, math.ceil(capacity_factor * T / E))

    logits = jnp.einsum("td,de->te", x, params["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E] fp32
    expert = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.max(probs, axis=-1)  # [T]

    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's slots (this device's view)
    pos = (jnp.cumsum(onehot, axis=0) * onehot - 1.0).astype(jnp.int32)
    # one_hot zeroes out-of-range rows, so it IS the keep mask: pos == -1
    # (inactive pair) and pos >= cap (overflow) both yield all-zero slots
    dispatch = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # [T, E, cap] 0/1
    combine = dispatch * gate[:, None, None]  # gradient flows to the router

    wdt = x.dtype
    # gather tokens into expert slots: [E, cap, d]
    xin = jnp.einsum("td,tec->ecd", x, dispatch.astype(wdt))
    # ship slots to their expert's device: [E_local, n * cap, d]
    xin = lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=1, tiled=True)
    h = activation(jnp.einsum("ecd,edf->ecf", xin, params["wi"],
                              preferred_element_type=jnp.float32).astype(wdt))
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"],
                   preferred_element_type=jnp.float32).astype(wdt)
    # return slots to their source device: [E, cap, d]
    y = lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0, tiled=True)
    out = jnp.einsum("ecd,tec->td", y, combine.astype(wdt))

    # Switch aux loss: E * <fraction routed to e> . <mean router prob e>,
    # averaged over devices
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = lax.pmean(E * jnp.sum(frac * mean_prob), axis_name)
    return out, aux


# --------------------------------------------------------------------------
# top-k experts, told which experts they hold; nothing dropped
# --------------------------------------------------------------------------


def route_topk(x, router, top_k: int, scale: float = 1.0, *, score: str = "softmax",
               bias=None, groups: int = 1, groups_kept: Optional[int] = None,
               eps: float = 0.0):
    """Top-k routing over all the experts the router knows.

    ``x [T, d]`` is what the router reads; ``router [d, E]``.  Logits, top-k
    and the softmax over the k chosen logits run in float32 (softmax over
    all E, top-k, renormalised, is the same number), times ``scale`` where a
    model scales its routed experts.  Returns ``(experts [T, k] int32,
    weights [T, k] float32)``; the weights carry the router's gradient.

    With any of the keywords, DeepSeek-V3's router (arXiv:2412.19437 section
    2.1.2): ``score`` ``"sigmoid"`` scores every expert ``s = sigmoid(logit)``
    by itself (``"softmax"``: over all the experts); the choice is made on ``s
    + bias`` (``bias [E]``, which takes no gradient: a balancing update's to
    move, not the loss's); with ``groups`` > 1 the experts stand in that many
    equal groups, a group scores the sum of its two largest ``s + bias``, the
    ``groups_kept`` best groups stay and the ``top_k`` largest ``s + bias``
    among their experts are chosen; the weights are the chosen ``s`` **without
    the bias** over their sum, times ``scale``.  ``eps`` is added to that sum
    where a model's denominator has one (LFM2's ``1e-6``); at 0 the sum stands
    bare."""
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if score == "softmax" and bias is None and groups == 1 and not eps:
            top, experts = lax.top_k(logits, top_k)
            weights = jax.nn.softmax(top, axis=-1)
            return experts, weights if scale == 1.0 else weights * scale
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {score!r}: 'softmax' or 'sigmoid'")
        s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, -1)
        choice = lax.stop_gradient(s if bias is None else s + bias.astype(jnp.float32))
        if groups > 1:
            t, e = choice.shape
            if e % groups or not 0 < (groups_kept or groups) <= groups:
                raise ValueError(f"{e} experts in {groups} groups, {groups_kept} kept")
            by_group = choice.reshape(t, groups, e // groups)
            best = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)           # [T, groups]
            kept = lax.top_k(best, groups_kept or groups)[1]             # [T, kept]
            stays = jnp.zeros((t, groups), bool).at[
                jnp.arange(t)[:, None], kept].set(True)
            choice = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, e)
        experts = lax.top_k(choice, top_k)[1]
        chosen = jnp.take_along_axis(s, experts, axis=-1)
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        return experts, chosen * (scale / (total + eps if eps else total))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(x, w, group_sizes, dtype):
    """``y[r] = x[r] @ w[group of r]`` for rows sorted by group, as
    ``lax.ragged_dot``: operands in ``dtype``, accumulators and results
    float32, in both directions (the contract of
    ``models.transformer._bf16_matmul_f32_acc``): the cotangent is rounded
    to ``dtype`` and both transposes are grouped products again.  ``x [R,
    k]``, ``w [G, k, n]`` (any float type), ``group_sizes [G]``."""
    return _grouped_matmul_fwd(x, w, group_sizes, dtype)[0]


def _grouped_matmul_fwd(x, w, group_sizes, dtype):
    xb, wb = x.astype(dtype), w.astype(dtype)
    y = lax.ragged_dot(xb, wb, group_sizes, preferred_element_type=jnp.float32)
    # zero-size markers carry the primal types to the backward pass
    return y, (xb, wb, group_sizes, jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))


_ROWS_CONTRACTED = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_matmul_bwd(dtype, res, g):
    xb, wb, group_sizes, x_like, w_like = res
    gb = g.astype(dtype)
    dx = lax.ragged_dot(gb, wb.transpose(0, 2, 1), group_sizes,
                        preferred_element_type=jnp.float32)
    # dw[e] = x[rows of e]^T g[rows of e]: the rows are the ragged dimension
    dw = lax.ragged_dot_general(xb, gb, group_sizes, _ROWS_CONTRACTED,
                                preferred_element_type=jnp.float32)
    return dx.astype(x_like.dtype), dw.astype(w_like.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# the most rows of the sorted buffer one pass computes.  Every pass pays about
# 2.5 ms that its rows do not (twelve grouped products at 0.2 ms each before
# their first tile, three float32 sums of the stacks' gradients): read at
# 4096 rows a pass, where they were half of a pass (my chip runs, PR 29).
# 16,384 is a step's tokens in the SmallThinker cells: one pass at even
# routing (12,288 rows), six if every token picks six experts held here.
PASS_ROWS = 16384

# what a pass holds over the load the shapes promise (T k H / E rows at even
# routing), and the grain its row count is rounded up to.  Gather, mask,
# products and scatter-add cost what the buffer holds, valid rows or not:
# 0.33-0.6 ms a thousand rows, a layer forward and backward; a second pass
# costs 1.5 ms with 8 experts held, 4.4 with 16, 6.0 with 32 (the float32
# sums of the stacks' gradients), as much as 3,000 to 10,000 spare rows (my
# chip runs, PR 46).  So the buffer follows the load, with room to stay one
# pass: under seeded weights a layer read -15 to +16 % of the even load over
# a window, and +39 % in the one layer of Ling in which the router's bias
# favours the held experts' group (1,425 rows for 1,024: the grain's 2,048
# hold them).  4/3 is also what keeps the SmallThinker cells' 12,288 rows in
# their one pass of 16,384.  What is gathered or scattered is a pass's rows
# and never all T k assignments: a scalar looked up or added costs the chip
# 5-9 ns, 0.33-0.57 ms an op over Ling's 65,536 for 1,024 held, forward, again
# under a block's remat and transposed (my chip runs, PR 46; gone since PR 50).
HEADROOM = Fraction(4, 3)
ROW_GRAIN = 1024


def _pass_rows(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """The static row count of a pass: the even load ``tokens * top_k * held
    / num_experts`` times :data:`HEADROOM`, rounded up to :data:`ROW_GRAIN`;
    never more than :data:`PASS_ROWS`, nor than all the rows there can be."""
    worst = tokens * min(top_k, held)
    promised = math.ceil(HEADROOM * tokens * top_k * held / num_experts)
    return min(PASS_ROWS, -(-promised // ROW_GRAIN) * ROW_GRAIN, -(-worst // 8) * 8)


def _passes(ends, rows):
    """As many passes as the rows assigned here ask for (traced)."""
    return (ends[-1] + rows - 1) // rows


def _pass(i, rows, k, order, starts, ends):
    """Pass ``i`` covers the sorted rows ``[i * rows, (i + 1) * rows)``:
    their assignments (indices into the flat ``[T * k]``), their tokens,
    which of them are assigned to an expert held here, and how many rows of
    each expert the pass holds."""
    first = i * rows
    idx = lax.dynamic_slice_in_dim(order, first, rows)
    valid = (first + jnp.arange(rows)) < ends[-1]
    sizes = (jnp.clip(ends, first, first + rows)
             - jnp.clip(starts, first, first + rows)).astype(jnp.int32)
    return idx, idx // k, valid, sizes


def _pass_rows_out(xs, w_rows, wg, wu, wd, sizes, valid, dtype, activation):
    """The rows of one pass through their experts, weighted: ``[rows, d]``
    float32.  Rows past the last group belong to no expert: the grouped
    product leaves them undefined, so they are cut off on both sides and
    carry no cotangent."""
    xs = jnp.where(valid[:, None], xs, 0)
    hg = _grouped_matmul(xs, wg, sizes, dtype)
    hu = _grouped_matmul(xs, wu, sizes, dtype)
    y = _grouped_matmul((activation(hg) * hu).astype(dtype), wd, sizes, dtype)
    return jnp.where(valid[:, None], y, 0.0) * jnp.where(valid, w_rows, 0.0)[:, None]


def _by_lanes(shape):
    """``[T, d]`` as ``[T, d / 128, 128]`` where the lanes divide ``d``: a
    row is then a tile-aligned block of its own, and a scatter-add of rows
    into it read 3.6 ms where the flat form read 7.4 (12,288 rows of 2560,
    my chip runs, PR 29)."""
    T, d = shape
    return (T, d // 128, 128) if d % 128 == 0 else (T, d)


def _add_rows(acc, tok, rows):
    """``acc[tok] += rows`` with ``acc`` kept :func:`_by_lanes`."""
    return acc.at[tok].add(rows.astype(acc.dtype).reshape((-1,) + acc.shape[1:]))


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _held_passes(m, w, wg, wu, wd, order, starts, ends, k, rows, activation):
    """``out[t] = sum over t's assignments a to experts held of w[a]
    E(m[t])``, float32, computed in as many passes of ``rows`` sorted rows as
    there are assignments (a loop whose length is the load's, which reverse
    mode cannot differentiate: hence the rule below, which walks the same
    passes and lets ``jax.vjp`` differentiate each).  ``w [T * k]`` are the
    weights as the router gave them, one an assignment, unsorted: a pass
    gathers the ``rows`` it computes by its slice of ``order``, and in the
    backward pass adds their cotangents back at the same places (every
    assignment stands once in ``order``; the rows past the last one name
    assignment 0 and add zeros).  Its residuals are still its inputs: every
    pass is recomputed in the backward pass, and a block recomputed under
    remat gathers nothing for it."""
    return _held_passes_fwd(m, w, wg, wu, wd, order, starts, ends, k, rows,
                            activation)[0]


def _held_passes_fwd(m, w, wg, wu, wd, order, starts, ends, k, rows, activation):
    def body(i, out):
        idx, tok, valid, sizes = _pass(i, rows, k, order, starts, ends)
        y = _pass_rows_out(m[tok], w[idx], wg, wu, wd, sizes, valid, m.dtype,
                           activation)
        return _add_rows(out, tok, y)

    out = lax.fori_loop(0, _passes(ends, rows), body,
                        vma_full(m, _by_lanes(m.shape), jnp.float32))
    return out.reshape(m.shape), (m, w, wg, wu, wd, order, starts, ends)


def _held_passes_bwd(k, rows, activation, res, g):
    m, w, wg, wu, wd, order, starts, ends = res

    def body(i, acc):
        dm, dw, dwg, dwu, dwd = acc
        idx, tok, valid, sizes = _pass(i, rows, k, order, starts, ends)
        _, vjp = jax.vjp(
            lambda xs, w_rows, wg, wu, wd: _pass_rows_out(
                xs, w_rows, wg, wu, wd, sizes, valid, m.dtype, activation),
            m[tok], w[idx], wg, wu, wd)
        dxs, dw_rows, g1, g2, g3 = vjp(g[tok])
        return (_add_rows(dm, tok, dxs), dw.at[idx].add(dw_rows),
                dwg + g1, dwu + g2, dwd + g3)

    zeros = lambda shape: vma_full(g, shape, jnp.float32)  # typed as under shard_map
    dm, dw, dwg, dwu, dwd = lax.fori_loop(0, _passes(ends, rows), body, (
        zeros(_by_lanes(m.shape)), zeros(w.shape), zeros(wg.shape),
        zeros(wu.shape), zeros(wd.shape)))
    return (dm.reshape(m.shape).astype(m.dtype), dw.astype(w.dtype),
            dwg.astype(wg.dtype), dwu.astype(wu.dtype), dwd.astype(wd.dtype),
            None, None, None)


_held_passes.defvjp(_held_passes_fwd, _held_passes_bwd)


def held_topk_experts(m, experts, weights, params, held, num_experts: int,
                      *, rows: Optional[int] = None, activation=jax.nn.relu):
    """This share's part of a top-k expert layer, with no token dropped.

    ``m [T, d]``: what the experts read.  ``experts`` / ``weights``:
    :func:`route_topk`'s result, over all ``num_experts`` experts.  ``held``:
    the global ids of the experts this share holds, in the order of the
    leading axis of ``params``: ``wg``, ``wu`` ``[H, d, f]`` and ``wd``
    ``[H, f, d]``, a gated product: ``E_e(m) = (activation(m wg) * (m wu))
    wd``, ``activation`` ReLU unless given.

    Returns ``[T, d]``: for every token the sum, over those of its top-k
    experts that are held here, of ``w_e E_e(m)``; the other shares'
    terms are left out.

    How: the ``T * k`` assignments are sorted by expert held (those to
    experts held elsewhere last), and the sorted rows that are assigned
    here are computed in passes of ``rows`` rows (default
    :func:`_pass_rows`: the load the shapes promise, ``T k H / num_experts``
    rows at even routing, and a third, up to :data:`PASS_ROWS`): gather,
    three grouped products (``lax.ragged_dot`` with the pass's own group
    sizes; operands in ``m``'s type, float32 accumulators), scatter-add by
    token.  There are as many passes as the load asks for, ``ceil(assigned
    / rows)``, so the layer's cost follows the load of the experts held:
    routing that tilts past the buffer costs a second, equally small pass,
    and a pile-up on one expert costs time and never a token.

    Before the passes nothing is looked up or scattered over the ``T * k``
    assignments, of which a share holds 2-12 %: ``held`` is static, so an
    assignment's place among the experts held and the count of each
    expert's rows are comparisons against ``held`` and sums (one fused
    pass over ``[T * k, H]``; written for ``H`` well under ``num_experts``,
    and still right with every expert held); the one op over all the
    assignments is the stable sort.  The weights stay as the router gave
    them: a pass gathers the ``rows`` it computes, and the backward pass
    adds their cotangents back a pass at a time (:func:`_held_passes`)."""
    T, d = m.shape
    k = experts.shape[1]
    held = tuple(int(e) for e in held)
    H = len(held)
    total = int(num_experts)
    if not held or min(held) < 0 or max(held) >= total or len(set(held)) != H:
        raise ValueError(
            f"held={held} must be distinct expert ids in [0, {total})")
    if params["wg"].shape[0] != H:
        raise ValueError(
            f"params hold {params['wg'].shape[0]} experts, held names {H}")
    if rows is None:
        rows = _pass_rows(T, k, H, total)
    A = T * k
    reg = _telemetry.get_registry()
    if reg.enabled:
        reg.gauge("moe.experts_held").set(H)
        reg.gauge("moe.experts_total").set(total)
        reg.gauge("moe.top_k").set(k)
        reg.gauge("moe.buffer_rows").set(rows)
        reg.gauge("moe.rows_expected").set(A * H / total)
        reg.gauge("moe.assignments").set(A)

    with jax.named_scope("moe_experts"):
        # an assignment's place among the experts held, H for "held elsewhere":
        # `held` is static, so both are comparisons and sums, nothing looked up
        hit = experts.reshape(A, 1) == jnp.asarray(held, experts.dtype)  # [A, H]
        group = jnp.min(jnp.where(hit, jnp.arange(H, dtype=jnp.int32), H), axis=-1)
        order = jnp.argsort(group, stable=True)               # held ones first
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        # the last pass may reach past the A assignments: never past this
        most = -(-T * min(k, H) // rows) * rows
        if most > A:  # the padding names assignment 0; its rows are not valid
            order = jnp.concatenate([order, jnp.zeros((most - A,), order.dtype)])
        out = _held_passes(m, weights.reshape(A), params["wg"], params["wu"],
                           params["wd"], order, ends - sizes, ends, k, rows,
                           activation)
        return out.astype(m.dtype)
