"""A routed expert layer that is told which experts it holds.

The router scores every token over ALL ``n_experts`` (the published width);
this chip holds the experts ``[first, first + held)`` (``held`` is the
leading axis of the expert weights) and adds only their terms to the result:
what the absent experts would add lies on other chips (expert parallelism:
``held_experts``, one chip's share, with no exchange). Nothing stands in for
the absent chips. Where the chips are there (a mesh with an ``expert`` axis),
``exchanged_experts`` computes the WHOLE layer: each chip routes its own
tokens, the slots cross to the chips that hold their experts and the terms
come back (the last section of this file).

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
here: on a TPU megablox's grouped kernels (``gmm`` for the product and for the
rows' cotangent, ``tgmm`` for the weights'), under this file's own
``jax.custom_vjp`` (``_tiled_grouped_dot``) so that EACH of the three gets
tiles chosen for its own (m, k, n) and for the rows a group is expected to
hold (``gmm_tiles``). Megablox rounds k and n up to whole tiles and computes
whole every row tile a group touches, and its own ``custom_vjp`` hands one
triple to all three products: under PR 27's (512, 896, 640), fitted to the
hybrid decoder's widths, the kernels multiplied 2.3 to 4 times the live work.
By the sweep of the three cells' six products alone on a v5e (PERF.md 6, PR
40; ms a call, that triple -> the rule's tiles): 2048 x 4096 at 964 rows a
group 1.79 -> 1.01 forward, 1.66 -> 1.01 rows' cotangent, 1.91 -> 1.18
weights'; 3072 x 2048 at 320 rows 0.73 -> 0.43, 0.71 -> 0.41, 0.85 -> 0.48;
1856 x 2688 at 384 rows 0.81 -> 0.42, 0.51 -> 0.41, 0.90 -> 0.48 (and a third
of ``jax.lax.ragged_dot``'s time before that: 3.5 against 11.1 ms, PR 27);
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
itself: ``pretrain.ZAYA_SCOPES``); the exchange adds ``moe_exchange_out``
(counts, rows and, in the backward pass, the sums' cotangents on their way to
the experts) and ``moe_exchange_back`` (what returns: terms, and the rows' and
weights' cotangents): ``pretrain.MELLUM_SCOPES``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bert_pytorch_tpu.ops.pallas.common import interpret_mode
from bert_pytorch_tpu.utils import trace_parts

GMM_TILE_ROWS = 512
LANES = 128
# What one grid step of a grouped product may hold by ``gmm_vmem_bytes``'s
# count: megablox sets no ``vmem_limit_bytes``, so Mosaic compiles it under a
# v5e's 16 MiB of scoped VMEM. Of the sweep's 440 candidates every one under
# 15.8 MiB by that count compiled and the four refused stood at 16.9 and over
# (PERF.md 6, PR 40).
GMM_VMEM_BYTES = 15 * 2 ** 20


def _widths(size: int):
    """Tile widths for one dimension, widest first: the multiples of 128 that
    cover ``size`` in equal tiles with the least padding there is (none where
    ``size`` is a multiple of 128; 1856 -> 1920, 640, 384, 128)."""
    lanes = -(-size // LANES)
    return [lanes // parts * LANES for parts in range(1, lanes + 1)
            if lanes % parts == 0]


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int,
                   weights_out: bool = False) -> int:
    """Bytes a grid step of megablox's ``gmm`` (``tgmm``: ``weights_out``)
    holds at these tiles: both operands' blocks and the result's, each twice
    (the pipeline fetches the next while this one is worked on), the float32
    accumulator, and what the body keeps of its operands beside the blocks:
    ``gmm`` the rows' block once more, ``tgmm`` a float32 copy of each (it
    masks them as float32)."""
    if weights_out:
        return (2 * tm * (tk + tn) * itemsize + 2 * tk * tn * itemsize
                + 4 * tk * tn + 4 * tm * (tk + tn))
    return (2 * (tm * tk + tk * tn + tm * tn) * itemsize + 4 * tm * tn
            + tm * tk * itemsize)


def gmm_tiles(k: int, n: int, group_rows: int, itemsize: int,
              weights_out: bool = False):
    """(tm, tk, tn) for ONE grouped product of sorted rows in groups of about
    ``group_rows``: rows [m, k] x weights[g] [k, n] -> [m, n], or, with
    ``weights_out``, rows^T [k, m] x rows' [m, n] -> weights [g, k, n]
    (``tgmm``); m a multiple of ``GMM_TILE_ROWS``. From shapes alone; the
    constants are the sweep's (PERF.md 6, PR 40).

    ``tm``: every group visits ``group_rows / tm + 1`` row tiles and each is
    computed whole, so 256 rows where a group holds two such tiles and 128
    under that (512 was never faster, and costs the VMEM ``tn`` uses better).
    ``tk``, ``tn``: widths that waste the least of k and n (``_widths``),
    a grid step under ``GMM_VMEM_BYTES``. The row product takes the pair that
    moves the fewest bytes a FLOP: the rows' block is read again for every n
    tile (1 / tn), and the weights' block once a group where k is ONE tile
    (it then stays in VMEM across the group's row tiles: 1 / group_rows) and
    once a row tile where it is not (1 / tm); so k whole where that leaves a
    wide ``tn``. The weights' product accumulates a [tk, tn] block over a
    group's rows and pays its masks and the accumulator's read and write by
    the grid step: the largest block that fits, the squarer the better.
    """
    tm = 256 if group_rows >= 512 else LANES
    pairs = [(tk, tn) for tk in _widths(k) for tn in _widths(n)
             if gmm_vmem_bytes(tm, tk, tn, itemsize, weights_out)
             <= GMM_VMEM_BYTES]
    if weights_out:
        best = max(pairs, key=lambda p: (p[0] * p[1], -abs(p[0] - p[1])))
    else:
        best = min(pairs, key=lambda p: (
            1 / p[1] + 1 / (group_rows if p[0] >= k else tm), -p[0]))
    return (tm, *best)


def grouped_dot(rows, weights, sizes, group_rows: int):
    """rows [M, K] @ weights[g] [K, N] for the rows of group g (consecutive,
    ``sizes`` [G] of them each, about ``group_rows`` expected); rows past the
    groups are left undefined."""
    if interpret_mode() or rows.shape[0] % GMM_TILE_ROWS:
        return jax.lax.ragged_dot(rows, weights, sizes,
                                  preferred_element_type=rows.dtype)
    return _tiled_grouped_dot(rows, weights, sizes, group_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tiled_grouped_dot(rows, weights, sizes, group_rows):
    """:func:`grouped_dot` through megablox's kernels, each of the three
    products (this one, the rows' cotangent, the weights') at tiles of its
    own (:func:`gmm_tiles`). Called on the CPU (the tests do) it runs them in
    the Pallas interpreter."""
    return _tiled_forward(rows, weights, sizes, group_rows)[0]


def _tiled_forward(rows, weights, sizes, group_rows):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend as megablox

    k, n = weights.shape[1:]
    with trace_parts.kernel_build("gmm"):
        out = megablox.gmm(
            rows, weights, sizes, rows.dtype,
            gmm_tiles(k, n, group_rows, rows.dtype.itemsize),
            interpret=interpret_mode())
    return out, (rows, weights, sizes)


def _tiled_backward(group_rows, kept, d_out):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend as megablox

    rows, weights, sizes = kept
    (k, n), itemsize = weights.shape[1:], rows.dtype.itemsize
    # d_out [m, n] x weights[g]^T [n, k]: n is the contraction now
    with trace_parts.kernel_build("gmm"):
        d_rows = megablox.gmm(
            d_out, weights, sizes, rows.dtype,
            gmm_tiles(n, k, group_rows, itemsize),
            transpose_rhs=True, interpret=interpret_mode())
    # (tgmm takes rows^T and turns it back itself: XLA drops the pair)
    with trace_parts.kernel_build("tgmm"):
        d_weights = megablox.tgmm(
            rows.swapaxes(0, 1), d_out, sizes, weights.dtype,
            gmm_tiles(k, n, group_rows, itemsize, weights_out=True),
            num_actual_groups=weights.shape[0], interpret=interpret_mode())
    return d_rows, d_weights, None


_tiled_grouped_dot.defvjp(_tiled_forward, _tiled_backward)


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


def _piece_terms(activation, gated: bool, group_rows: int, live, mine,
                 rows_in, slot_w, w_up, w_down):
    """A piece's gathered rows [rows, H], sorted by expert in groups of
    ``mine`` [E] rows (``live`` [rows, 1]: which rows belong to a group), and
    their slot weights [rows] -> its weighted outputs [rows, H] in float32."""
    with jax.named_scope("moe_dispatch"):
        rows_in = jnp.where(live, rows_in, 0)
    with jax.named_scope("moe_experts"):
        mid = jnp.where(
            live, grouped_dot(rows_in, w_up, mine, group_rows), 0)
        if gated:
            gate, up = jnp.split(mid, 2, axis=-1)
            mid = activation(gate) * up
        else:
            mid = activation(mid)
        rows_out = grouped_dot(mid, w_down, mine, group_rows)
    with jax.named_scope("moe_combine"):
        return jnp.where(live, rows_out, 0).astype(
            jnp.float32) * slot_w[:, None]


def _held_sum(rows: int, top_k: int, activation, gated: bool,
              group_rows: int):
    """``total(x, slot_weights, w_up, w_down, order, sizes, ends, trips) ->
    [T, H] float32``: the held experts' terms, summed over the first ``trips``
    pieces of ``rows`` sorted slots (those that hold a local slot), with a
    backward pass of its own. ``group_rows``: the rows an expert expects of a
    call, for the grouped products' tiles.

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

    experts = functools.partial(_piece_terms, activation, gated, group_rows)

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


def _tile_fill(start, stop, ends, group_rows: int, products, itemsize: int):
    """Live work over the work of the tiles visited, for the forward products
    ``products`` ((k, n) each) over pieces of sorted slots in which group e
    lies in rows ``start[p, e] .. stop[p, e]`` of piece p ([pieces, E];
    ``ends``: the groups' running ends, its last the live slots): a group
    visits, in each piece it reaches, every row tile it touches, and a
    visited tile is computed whole over k and n rounded up to their tiles."""
    live = visited = jnp.zeros((), jnp.float32)
    for k, n in products:
        tm, tk, tn = gmm_tiles(k, n, group_rows, itemsize)
        tiles = jnp.sum(jnp.where(
            stop > start, -(-stop // tm) - start // tm, 0))
        live += ends[-1].astype(jnp.float32) * float(k * n)
        visited += tiles.astype(jnp.float32) * float(
            tm * (-(-k // tk) * tk) * (-(-n // tn) * tn))
    return live / jnp.maximum(visited, 1.0)


def _in_piece(ends, sizes, lo, rows: int):
    """Of runs that end at ``ends`` and hold ``sizes``, where the part inside
    the piece (or round) of ``rows`` places from ``lo`` on starts and stops,
    counted from the piece's first place."""
    start = jnp.clip(ends - sizes - lo, 0, rows)
    stop = jnp.clip(ends - lo, 0, rows)
    return start, stop


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
    cover every slot there is), ``pieces_run`` (the pieces that hold a
    local slot: the trips of the loop over them) and ``tile_fill`` (the two
    forward products' live rows x k x n over the rows x k x n, both rounded up
    to their tiles, of the tiles :func:`gmm_tiles` has the kernels visit: 1
    would be no padding; 0 without a local slot).
    """
    tokens, top_k = chosen.shape
    held = w_up.shape[0]
    rows = chunk_rows(tokens, top_k, n_experts, held, multiple)
    pieces = -(-tokens * top_k // rows)
    group_rows = max(tokens * top_k // n_experts, 1)
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
    out = _held_sum(rows, top_k, activation, gated, group_rows)(
        x, weights.reshape(-1), w_up, w_down, order, sizes, ends, pieces_run)
    counters = {
        "local_slots": n_local.astype(jnp.float32),
        "load_max_over_mean": jnp.max(sizes).astype(jnp.float32) * held
        / jnp.maximum(n_local, 1).astype(jnp.float32),
        "dropped_slots": jnp.maximum(
            n_local - pieces * rows, 0).astype(jnp.float32),
        "pieces_run": pieces_run.astype(jnp.float32),
        "tile_fill": _tile_fill(
            *_in_piece(ends, sizes, jnp.arange(pieces)[:, None] * rows, rows),
            ends, group_rows, (w_up.shape[1:], w_down.shape[1:]),
            x.dtype.itemsize),
    }
    return out.astype(x.dtype), counters


# ------------------------------------------------- the exchange between chips

def exchange_rows(tokens: int, top_k: int, chips: int,
                  multiple: int = GMM_TILE_ROWS) -> int:
    """Rows one chip sends one chip in one round of the exchange: half of
    what a chip expects to send another (``tokens x top_k / chips``), rounded
    up to ``multiple`` rows. An even routing takes two full rounds and a third
    for what lies over the mean; an uneven one takes more rounds."""
    return max(-(-tokens * top_k // (2 * chips * multiple)), 1) * multiple


def _dealt(axis: str, chips: int, t):
    """``t`` [T, ...] dealt round the ``chips`` of ``axis``: token i of every
    chip goes to chip ``i mod chips``, and the tokens chip r sends lie where
    they lay on r (its token ``i`` at ``i - i mod chips + r``). Dealt twice,
    every token is home again: the deal is its own inverse, and its own
    transpose. A row of the batch prefers some experts to others all along
    (its tokens share what attention averaged over them), so the pair of the
    chip that holds the row and the chip that holds those experts would carry
    far more than a pair's share and every chip would wait for its rounds;
    after the deal each chip holds every ``chips``-th token of every row, and
    each pair carries the holder's load over ``chips``: the rounds follow the
    fullest CHIP and not the fullest pair. ``t`` as it is where the tokens do
    not divide by the chips."""
    if t.shape[0] % chips:
        return t
    dealt = jax.lax.all_to_all(
        t.reshape(t.shape[0] // chips, chips, *t.shape[1:]), axis, 1, 1)
    return dealt.reshape(t.shape)


def _in_round(ends, sizes, lo, rows: int):
    """How much of each run lies in the round of ``rows`` places from ``lo``
    on (:func:`_in_piece`)."""
    start, stop = _in_piece(ends, sizes, lo, rows)
    return stop - start


def _exchanged_sum(axis: str, chips: int, rows: int, top_k: int, activation,
                   gated: bool, group_rows: int):
    """``total(x, slot_weights, w_up, w_down, order, starts, to_chip, arrived,
    rounds) -> [T, H] float32``: every slot's term, computed by the chip that
    holds its expert and summed into its token here, in ``rounds`` rounds of
    ``rows`` slots a pair of chips. Inside a ``shard_map`` manual over
    ``axis`` (``chips`` wide).

    ``order`` [T k]: this chip's slots sorted by expert, so by the chip that
    holds it; ``starts`` / ``to_chip`` [chips]: where each chip's run begins
    in ``order`` and how many slots it holds; ``arrived`` [chips, E]: the
    slots each chip sends this one, by local expert (the counts, exchanged
    first); ``rounds``: the most rounds any pair needs, the same on every
    chip (the collectives inside the loops need every chip in every trip).

    A round: each chip gathers the next ``rows`` slots' rows for every chip
    (``moe_dispatch``), the rows cross (``moe_exchange_out``), the arrivals
    are sorted by local expert (each sender's run already is, and the counts
    say where its groups end, so no expert id travels) and go through ONE
    piece of :func:`_held_sum`'s kind (:func:`_piece_terms`: the grouped
    products over the groups' true sizes, ``moe_experts``), the outputs
    return to their places and cross back (``moe_exchange_back``), and each
    is added to its token with the router's weight (``moe_combine``): the
    weights stay with their tokens. Like :func:`_held_sum` the forward pass
    keeps only its arguments and the backward pass makes each round again:
    the rows cross once more and, beside them, the cotangents of the tokens'
    sums and the weights (float32, a number a slot); the rows' cotangents and
    the weights' come back: the same exchange turned round. No buffer grows
    with the imbalance: a chip that draws more slots costs more rounds.
    """
    experts = functools.partial(_piece_terms, activation, gated, group_rows)
    swap = lambda t: jax.lax.all_to_all(t, axis, 0, 0)

    def outbound(index, order, starts, to_chip):
        """Round ``index`` at the sender: the slot [chips, rows] each place of
        the send buffer carries and which places carry one."""
        place = index * rows + jnp.arange(rows)[None, :]
        live = place < to_chip[:, None]
        slots = order[jnp.clip(starts[:, None] + place, 0, order.shape[0] - 1)]
        return slots, live

    def inbound(index, arrived):
        """Round ``index`` at the holder: the order that sorts the
        [chips x rows] arrivals by local expert, the groups' sizes [E] and
        which sorted rows belong to a group [chips x rows, 1]."""
        ends = jnp.cumsum(arrived, axis=1)                     # [chips, E]
        place = index * rows + jnp.arange(rows)                # [rows]
        expert = jnp.sum(place[None, :, None] >= ends[:, None, :], axis=-1)
        order = jnp.argsort(expert.reshape(-1), stable=True)   # absent: last
        mine = jnp.sum(_in_round(ends, arrived, index * rows, rows), axis=0)
        live = (jnp.arange(chips * rows) < jnp.sum(mine))[:, None]
        return order, mine, live

    def send_rows(x, slots, live):
        with jax.named_scope("moe_dispatch"):
            rows_out = jnp.where(live[..., None], x[slots // top_k], 0)
        with jax.named_scope("moe_exchange_out"):
            return swap(rows_out)

    def forward(x, slot_weights, w_up, w_down, order, starts, to_chip,
                arrived, rounds):
        ones = jnp.ones((chips * rows,), jnp.float32)

        def add_round(index, total):
            slots, live_out = outbound(index, order, starts, to_chip)
            got = send_rows(x, slots, live_out).reshape(chips * rows, -1)
            by_expert, mine, live = inbound(index, arrived)
            with jax.named_scope("moe_dispatch"):
                rows_in = got[by_expert]
            terms = experts(live, mine, rows_in, ones, w_up, w_down)
            with jax.named_scope("moe_combine"):
                back = jnp.zeros_like(got).at[by_expert].set(
                    terms.astype(x.dtype)).reshape(chips, rows, -1)
            with jax.named_scope("moe_exchange_back"):
                back = swap(back)
            with jax.named_scope("moe_combine"):
                weight = jnp.where(live_out, slot_weights[slots], 0.0)
                return total.at[(slots // top_k).reshape(-1)].add(
                    (back.astype(jnp.float32) * weight[..., None]).reshape(
                        chips * rows, -1))

        total = jax.lax.fori_loop(0, rounds, add_round,
                                  jnp.zeros(x.shape, jnp.float32))
        return total, (x, slot_weights, w_up, w_down, order, starts, to_chip,
                       arrived, rounds)

    def backward(kept, d_total):
        (x, slot_weights, w_up, w_down, order, starts, to_chip, arrived,
         rounds) = kept

        def cotangents(index):
            slots, live_out = outbound(index, order, starts, to_chip)
            token = slots // top_k
            got = send_rows(x, slots, live_out).reshape(chips * rows, -1)
            with jax.named_scope("moe_combine"):
                weight = jnp.where(live_out, slot_weights[slots], 0.0)
                # (the sums' cotangent is a cast of x's dtype: exact there)
                d_sums = jnp.where(live_out[..., None], d_total[token], 0
                                   ).astype(x.dtype)
            with jax.named_scope("moe_exchange_out"):
                d_got = swap(d_sums).reshape(chips * rows, -1)
                weight_got = swap(weight).reshape(-1)
            by_expert, mine, live = inbound(index, arrived)
            with jax.named_scope("moe_dispatch"):
                rows_in = got[by_expert]
            _, pull = jax.vjp(functools.partial(experts, live, mine),
                              rows_in, weight_got[by_expert], w_up, w_down)
            with jax.named_scope("moe_combine"):
                d_terms = d_got[by_expert].astype(jnp.float32)
            d_rows, d_weight, d_up, d_down = pull(d_terms)
            with jax.named_scope("moe_dispatch"):
                d_rows = jnp.zeros_like(got).at[by_expert].set(
                    d_rows).reshape(chips, rows, -1)
                d_weight = jnp.zeros_like(weight_got).at[by_expert].set(
                    d_weight).reshape(chips, rows)
            with jax.named_scope("moe_exchange_back"):
                d_rows, d_weight = swap(d_rows), swap(d_weight)
            return (slots.reshape(-1), token.reshape(-1),
                    d_rows.reshape(chips * rows, -1), d_weight.reshape(-1),
                    d_up, d_down)

        def add_round(index, sums):
            d_x, d_slot_weights, d_up, d_down = sums
            slots, token, d_rows, d_weight, d_up_round, d_down_round = (
                cotangents(index))
            with jax.named_scope("moe_dispatch"):
                d_x = d_x.at[token].add(d_rows)
            with jax.named_scope("moe_combine"):
                d_slot_weights = d_slot_weights.at[slots].add(d_weight)
            with jax.named_scope("moe_experts"):
                return (d_x, d_slot_weights, d_up + d_up_round,
                        d_down + d_down_round)

        # Round 0 outside the loop, as _held_sum's piece 0 (the weights' sums
        # start as its cotangents; a place that carries no slot is zero).
        slots, token, d_rows, d_weight, d_up, d_down = cotangents(0)
        with jax.named_scope("moe_dispatch"):
            d_x = jnp.zeros_like(x).at[token].add(d_rows)
        with jax.named_scope("moe_combine"):
            d_slot_weights = jnp.zeros_like(slot_weights).at[slots].add(
                d_weight)
        sums = jax.lax.fori_loop(1, rounds, add_round,
                                 (d_x, d_slot_weights, d_up, d_down))
        return (*sums, None, None, None, None, None)

    total = jax.custom_vjp(lambda *args: forward(*args)[0])
    total.defvjp(forward, backward)
    return total


def exchanged_experts(x, chosen, weights, w_up, w_down, n_experts: int,
                      activation, axis: str, multiple: int = GMM_TILE_ROWS,
                      gated: bool = False):
    """The WHOLE layer's routed output for this chip's tokens, with the
    experts divided over the mesh axis ``axis``: called inside a
    ``shard_map`` manual over it, every chip with its own tokens ``x`` [T, H],
    their routing over all ``n_experts`` (``chosen`` / ``weights`` [T, k] from
    :func:`route`) and the stacked weights of the experts it holds (chip r of
    c: experts ``r E / c .. (r + 1) E / c - 1``; ``w_up`` [E / c, H, F or
    2F], ``w_down`` [E / c, F, H]).

    The order of operations: DEAL the routed tokens round the chips
    (:func:`_dealt`: rows, choices and weights, so that no pair of chips
    carries one row's preference); sort the slots by expert (so by chip);
    exchange the COUNTS (one small all-to-all: every chip learns how many
    slots each chip sends each of its experts, and with one maximum over the
    axis how many rounds the fullest pair needs); then the rounds of
    :func:`_exchanged_sum`; deal the sums home. No slot is dropped under any
    routing and nothing is sized for the worst case or by a capacity factor.

    Counters, this chip's (``models/decoder.py`` and
    ``pretrain.make_train_step`` add them up over layers, micro-batches and
    chips): ``local_slots`` (slots that arrived here, this chip's own among
    them), ``exchange_slots_out`` / ``exchange_slots_in`` (slots sent to and
    received from OTHER chips: over all chips the two sums are equal),
    ``exchange_bytes_out`` (those slots' rows, one crossing: slots x H x the
    item size), ``chip_load_max_over_mean`` (the fullest chip's arrivals
    over the mean chip's) and ``load_max_over_mean``
    (the fullest expert's over the mean expert's, of the whole layer),
    ``dropped_slots`` (slots of this chip that no round carried: 0, since the
    rounds cover the fullest pair), ``pieces_run`` (the rounds: every chip
    runs one piece a round) and ``tile_fill`` (:func:`held_experts`'s, over
    the rounds' groups).
    """
    tokens, top_k = chosen.shape
    held, hidden = w_up.shape[0], x.shape[-1]
    chips = n_experts // held
    rows = exchange_rows(tokens, top_k, chips, multiple)
    group_rows = max(chips * rows // held, 1)   # of one round's arrivals
    me = jax.lax.axis_index(axis)
    deal = functools.partial(_dealt, axis, chips)
    with jax.named_scope("moe_exchange_out"):
        x, chosen, weights = deal(x), deal(chosen), deal(weights)
    with jax.named_scope("moe_dispatch"):
        key = chosen.reshape(-1)
        order = jnp.argsort(key, stable=True)
        counts = jnp.sum(jax.nn.one_hot(key, n_experts, dtype=jnp.int32),
                         axis=0).reshape(chips, held)
        to_chip = jnp.sum(counts, axis=1)
        starts = jnp.cumsum(to_chip) - to_chip
    with jax.named_scope("moe_exchange_out"):
        arrived = jax.lax.all_to_all(counts, axis, 0, 0)    # [sender, E/c]
        rounds = jax.lax.pmax(jnp.max(-(-to_chip // rows)), axis)
    w_up, w_down = w_up.astype(x.dtype), w_down.astype(x.dtype)
    out = _exchanged_sum(axis, chips, rows, top_k, activation, gated,
                         group_rows)(
        x, weights.reshape(-1), w_up, w_down, order, starts, to_chip, arrived,
        rounds)
    with jax.named_scope("moe_dispatch"):
        sizes = jnp.sum(arrived, axis=0)                     # by local expert
        n_here = jnp.sum(sizes)
        remote = jnp.arange(chips) != me
        sent = jnp.sum(jnp.where(remote, to_chip, 0))
        total = jax.lax.psum(n_here, axis)
        as_float = lambda v: v.astype(jnp.float32)
        mean = jnp.maximum(as_float(total), 1.0)
        # the rounds' groups, laid end to end: what the kernels' tiles see
        ends = jnp.cumsum(arrived, axis=1)
        lo = jnp.arange(-(-tokens * top_k // rows))[:, None, None] * rows
        by_round = jnp.sum(_in_round(ends, arrived, lo, rows), axis=1)
        stop = jnp.cumsum(by_round, axis=1)   # a round's groups start anew
        fill = _tile_fill(stop - by_round, stop, jnp.cumsum(sizes),
                          group_rows, (w_up.shape[1:], w_down.shape[1:]),
                          x.dtype.itemsize)
    counters = {
        "local_slots": as_float(n_here),
        "exchange_slots_out": as_float(sent),
        "exchange_slots_in": as_float(
            jnp.sum(jnp.where(remote[:, None], arrived, 0))),
        "exchange_bytes_out": as_float(sent) * float(
            hidden * x.dtype.itemsize),
        "chip_load_max_over_mean": as_float(
            jax.lax.pmax(n_here, axis)) * chips / mean,
        "load_max_over_mean": as_float(
            jax.lax.pmax(jnp.max(sizes), axis)) * n_experts / mean,
        "dropped_slots": as_float(
            jnp.sum(jnp.maximum(to_chip - rounds * rows, 0))),
        "pieces_run": as_float(rounds),
        "tile_fill": fill,
    }
    with jax.named_scope("moe_exchange_back"):
        return deal(out.astype(x.dtype)), counters
