"""A routed expert layer that is told which experts it holds.

The router scores every token over ALL ``n_experts`` (the published width);
this chip holds the experts ``[first, first + held)`` (``held`` is the
leading axis of the expert weights) and adds only their terms to the result:
what the absent experts would add lies on other chips (expert parallelism;
the exchange between chips is not in this file, and on one chip the layer runs
without it). Nothing stands in for the absent chips.

Three routing rules, all in float32, all through ``choose`` (``route`` is
``choose`` of ``x router_w``). ``sigmoid`` (DeepSeek-V3's, as ``nemotron_h``
uses it): ``s = sigmoid(logits)``, the ``top_k`` largest of ``s +
correction_bias`` choose. ``softmax`` over a matrix's logits (as ``laguna``
uses it): ``s = softmax(logits)`` over all the experts, the ``top_k`` largest
choose, no correction bias. ``softmax`` over logits that were computed
elsewhere (``zaya``: an MLP whose state is handed from layer to layer,
``models/zaya.py``), with a correction bias, ``top_k`` 1 and ``norm_topk``
off: the weight is the chosen probability itself, the only way a gradient
reaches that router. Under each the weights are ``s`` of the chosen, divided
by their sum when ``norm_topk`` is set, times ``scale``.

**The skip.** A router may have more outputs than the layer has experts
(``zaya``: one more, chosen by the tokens that pass the layer by).
``held_experts``'s ``n_experts`` is the router's width, the expected load is
counted over it, and a slot whose choice no chip holds sorts with the absent
experts' and adds nothing anywhere.

Experts come with or without a shared expert beside them
(``models/decoder.py ExpertLayer``: ``shared_width`` 0 builds none); this file
holds the routed ones alone.

Two forms of expert (``held_experts``'s ``gated``). Plain: ``w_down
activation(w_up x)``, two products. Gated: ``w_up`` is ``[E, H, 2F]``, the
gate's columns first and the up projection's after them, ONE product for the
two, and the expert is ``w_down (activation(gate) * up)``: three products'
work in two calls. Both go through the same pieces.

No token-slot is ever dropped. The ``tokens x top_k`` slots are sorted so
that the slots of held experts come first, expert by expert; a token picks
distinct experts, so at most all of them are local. The grouped products run
over those sorted rows with the experts' true group sizes and visit only the
row tiles the groups fill, so the matmul work follows the slots really routed
here: on a TPU the grouped matmul JAX ships (``megablox`` ``gmm``, with its
own backward products) at 512-row tiles, which at this layer's shapes runs
the two products forward and backward in a third of the time of
``jax.lax.ragged_dot`` (3.5 against 11.1 ms at 3200 live rows, PERF.md 6);
``ragged_dot`` elsewhere (the CPU tests). The sorted rows are worked through
in PIECES of a static size (twice the expected local load), and only the
pieces that hold a local slot are run: a loop of ``ceil(local slots / rows)``
trips, in the forward pass and in the backward pass alike (``_held_sum``, a
``jax.custom_vjp``: a loop with a traced trip count cannot be differentiated
by JAX, so the backward pass is written here). Each trip adds into sums that
the loop carries. So gather, activation and scatter-add follow the routed
slots too, a piece at a time; no buffer is ever sized for the worst case (a
49152-row branch that is never taken cost the step 1 GB of the chip: PERF.md
6), and a piece past the last local slot costs nothing: as ``lax.cond``s,
each skipped piece wrote zeros for its term, for its residuals and for the
cotangents of x and of both weight tensors, 584 MB a skipped piece over the
three passes (PERF.md 5).

Scopes (``pretrain.CAUSAL_LM_SCOPES``): ``moe_route``, ``moe_dispatch``,
``moe_experts``, ``moe_combine`` (a router of its own opens ``moe_route``
itself: ``pretrain.ZAYA_SCOPES``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bert_pytorch_tpu.ops.pallas.common import interpret_mode

GMM_TILE_ROWS = 512


def grouped_dot(rows, weights, sizes):
    """rows [M, K] @ weights[g] [K, N] for the rows of group g (consecutive,
    ``sizes`` [G] of them each); rows past the groups are left undefined."""
    if interpret_mode() or rows.shape[0] % GMM_TILE_ROWS:
        return jax.lax.ragged_dot(rows, weights, sizes,
                                  preferred_element_type=rows.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tiling = (GMM_TILE_ROWS, min(896, rows.shape[1]), min(640, weights.shape[2]))
    return megablox.gmm(rows, weights, sizes, rows.dtype, tiling)


def route(x, router_w, correction_bias, top_k: int, scale: float,
          norm_topk: bool = True, score: str = "sigmoid"):
    """x [T, H] -> (expert ids [T, k] int32, weights [T, k] float32).
    ``score``: ``sigmoid`` (with its ``correction_bias`` [experts]) or
    ``softmax`` (``correction_bias`` None): :func:`choose` of the matrix's
    logits."""
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                            precision="highest")
        return choose(logits, correction_bias, top_k, scale, norm_topk, score)


def choose(logits, correction_bias, top_k: int, scale: float,
           norm_topk: bool = True, score: str = "sigmoid"):
    """Router logits [T, outputs] float32, wherever they were computed ->
    (ids [T, k] int32, weights [T, k] float32). ``correction_bias``
    [outputs] or None is added to the scores for the choice alone, outside
    the gradient. Under the caller's scope."""
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score must be sigmoid|softmax, got {score!r}")
    _, chosen = jax.lax.top_k(
        scores if correction_bias is None
        else scores + jax.lax.stop_gradient(correction_bias), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def chunk_rows(tokens: int, top_k: int, n_experts: int, held: int,
               multiple: int = GMM_TILE_ROWS) -> int:
    """Rows the held experts' slots are worked through at a time: twice the
    expected local load, rounded up to ``multiple`` rows, at most every slot
    there is."""
    most = tokens * top_k
    rows = -(-2 * most * held // n_experts // multiple) * multiple
    return min(rows, -(-most // multiple) * multiple)


def _held_sum(rows: int, top_k: int, activation, gated: bool = False):
    """``total(x, slot_weights, w_up, w_down, order, sizes, ends, trips) ->
    [T, H] float32``: the held experts' terms, summed over the first ``trips``
    pieces of ``rows`` sorted slots (those that hold a local slot), with a
    backward pass of its own.

    Both passes are a loop whose trip count is the number of such pieces (a
    traced value; JAX cannot differentiate such a loop, hence the two rules).
    The forward pass scatter-adds each piece's rows into ONE carried sum and
    keeps only its arguments. The backward pass makes each piece's forward
    again, takes its ``jax.vjp`` inside the loop's body, and adds into carried
    sums for the cotangents of ``x``, the slot weights and both weight
    tensors (in their own dtypes, as a sum of per-piece cotangents would be).
    """

    def piece(index, x, slot_weights, order, sizes, ends):
        """Piece ``index``: its slots and their tokens [rows], which rows
        hold a local slot [rows, 1], its share of each group [E], and its
        slots' rows of ``x`` [rows, H] and weights [rows]."""
        lo = index * rows
        slots = jax.lax.dynamic_slice(order, (lo,), (rows,))
        token = slots // top_k
        # Rows past the last local slot belong to no group: the grouped
        # product leaves them undefined, so every value that goes into or
        # comes out of one passes a select (in the backward pass too: the
        # select's transpose is a select).
        live = (lo + jnp.arange(rows) < ends[-1])[:, None]
        mine = jnp.clip(ends - lo, 0, rows) - jnp.clip(
            ends - sizes - lo, 0, rows)
        with jax.named_scope("moe_dispatch"):
            rows_in = x[token]
        with jax.named_scope("moe_combine"):
            slot_w = slot_weights[slots]
        return slots, token, live, mine, rows_in, slot_w

    def experts(live, mine, rows_in, slot_w, w_up, w_down):
        """A piece's gathered rows [rows, H] and slot weights [rows] -> its
        weighted outputs [rows, H] in float32."""
        with jax.named_scope("moe_dispatch"):
            rows_in = jnp.where(live, rows_in, 0)
        with jax.named_scope("moe_experts"):
            mid = jnp.where(live, grouped_dot(rows_in, w_up, mine), 0)
            if gated:
                gate, up = jnp.split(mid, 2, axis=-1)
                mid = activation(gate) * up
            else:
                mid = activation(mid)
            rows_out = grouped_dot(mid, w_down, mine)
        with jax.named_scope("moe_combine"):
            return jnp.where(live, rows_out, 0).astype(
                jnp.float32) * slot_w[:, None]

    def forward(x, slot_weights, w_up, w_down, order, sizes, ends, trips):
        def add_piece(index, total):
            _, token, live, mine, rows_in, slot_w = piece(
                index, x, slot_weights, order, sizes, ends)
            rows_out = experts(live, mine, rows_in, slot_w, w_up, w_down)
            with jax.named_scope("moe_combine"):
                return total.at[token].add(rows_out)

        total = jax.lax.fori_loop(0, trips, add_piece,
                                  jnp.zeros(x.shape, jnp.float32))
        return total, (x, slot_weights, w_up, w_down, order, sizes, ends,
                       trips)

    def backward(kept, d_total):
        x, slot_weights, w_up, w_down, order, sizes, ends, trips = kept

        def cotangents(index):
            slots, token, live, mine, rows_in, slot_w = piece(
                index, x, slot_weights, order, sizes, ends)
            _, pull = jax.vjp(functools.partial(experts, live, mine),
                              rows_in, slot_w, w_up, w_down)
            with jax.named_scope("moe_combine"):
                d_rows_out = d_total[token]
            return slots, token, pull(d_rows_out)

        def add_piece(index, sums):
            d_x, d_slot_weights, d_up, d_down = sums
            slots, token, (d_rows, d_slot, d_up_piece, d_down_piece) = (
                cotangents(index))
            with jax.named_scope("moe_dispatch"):
                d_x = d_x.at[token].add(d_rows)
            with jax.named_scope("moe_combine"):
                d_slot_weights = d_slot_weights.at[slots].add(d_slot)
            with jax.named_scope("moe_experts"):
                return (d_x, d_slot_weights, d_up + d_up_piece,
                        d_down + d_down_piece)

        # Piece 0 outside the loop: the weights' sums start as its cotangents
        # and are never filled with zeros (without a local slot every row's
        # select yields zeros, so its results are the zeros wanted).
        slots, token, (d_rows, d_slot, d_up, d_down) = cotangents(0)
        with jax.named_scope("moe_dispatch"):
            d_x = jnp.zeros_like(x).at[token].add(d_rows)
        with jax.named_scope("moe_combine"):
            d_slot_weights = jnp.zeros_like(slot_weights).at[slots].add(d_slot)
        sums = jax.lax.fori_loop(1, trips, add_piece,
                                 (d_x, d_slot_weights, d_up, d_down))
        return (*sums, None, None, None, None)

    total = jax.custom_vjp(lambda *args: forward(*args)[0])
    total.defvjp(forward, backward)
    return total


def held_experts(x, chosen, weights, w_up, w_down, first: int,
                 n_experts: int, activation, multiple: int = GMM_TILE_ROWS,
                 gated: bool = False):
    """The held experts' part of the layer's output, and the counters.

    x [T, H]; chosen / weights [T, k] from :func:`route`; w_up [E, H, F]
    ([E, H, 2F] when ``gated``: gate columns, then up columns), w_down
    [E, F, H] for the E experts ``first .. first + E``. Returns
    (out [T, H] in x's dtype, counters): ``local_slots`` (slots routed to held
    experts), ``load_max_over_mean`` (largest group over the mean group),
    ``dropped_slots`` (local slots that no piece reached: 0, since the pieces
    cover every slot there is) and ``pieces_run`` (the pieces that hold a
    local slot: the trips of the loop over them).
    """
    tokens, top_k = chosen.shape
    held = w_up.shape[0]
    rows = chunk_rows(tokens, top_k, n_experts, held, multiple)
    pieces = -(-tokens * top_k // rows)
    with jax.named_scope("moe_dispatch"):
        local = chosen - first                                # [T, k]
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)        # absent: last
        order = jnp.argsort(key, stable=True)                 # held first
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                        axis=0)[:held]
        n_local = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)
        pieces_run = (n_local + rows - 1) // rows
        order = jnp.pad(order, (0, pieces * rows - tokens * top_k))
    w_up, w_down = w_up.astype(x.dtype), w_down.astype(x.dtype)
    out = _held_sum(rows, top_k, activation, gated)(
        x, weights.reshape(-1), w_up, w_down, order, sizes, ends, pieces_run)
    counters = {
        "local_slots": n_local.astype(jnp.float32),
        "load_max_over_mean": jnp.max(sizes).astype(jnp.float32) * held
        / jnp.maximum(n_local, 1).astype(jnp.float32),
        "dropped_slots": jnp.maximum(
            n_local - pieces * rows, 0).astype(jnp.float32),
        "pieces_run": pieces_run.astype(jnp.float32),
    }
    return out.astype(x.dtype), counters
