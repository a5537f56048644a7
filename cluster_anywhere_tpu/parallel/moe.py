"""Mixture-of-experts FFNs: two paths, told apart by where the experts live.

`routed_ffn` is the dropless top-k path of one device, which serving
(models/generate.py: prefill and every decode step) and the one-device forward
and loss (models/transformer.py) go through: a softmax router in float32, the
k largest probabilities of each token (optionally renormalised), every
(token, expert) assignment sorted by expert, one grouped matmul a projection
over the sorted rows (`lax.ragged_dot`: on a TPU a kernel that reads the
weights of the experts that have rows and of no other), the results weighted,
returned to token order and summed over k.  No capacity and no drop, whatever
the imbalance.  Rows marked not live (the empty slots of a decode batch, the
left padding of a prompt) take no expert.  Experts are gated
(`silu(x w_gate) * (x w_up)) w_down`, OLMoE's kind) or ungated
(`silu(x w_in) w_out`), and the activation may be relu(x)^2 in silu's place
(ACTIVATIONS).  The scores may be a sigmoid's in place of the
softmax's, and the k weights scaled.  A device that holds a share of the
experts (`held`: expert parallelism's share, here without the exchange) is
told which: the router stays as wide as the model's experts and a token takes
its k of all of them; the assignments that fall on the experts held are
computed, the others sort behind the last group with the rows that are not
live and add nothing.  What the other devices' experts would add is theirs to
compute: nothing here stands in for it.

A held share's layer has two branches, and the rows decide between them as
the program runs.  The assignments that fall on the share are the head of the
sorted rows, about N x k x held / routed of N x k.  Where that leaves a buffer
of at most half the rows (`compact_buffer_rows`: a prefill's bucket and a
decode step's slots alike, by their static row count) the first C sorted rows
alone, a static COMPACT_SHARE times that even share, are gathered, go through
the same three grouped matmuls and are weighted and summed into their tokens'
rows in float32 (`_combine_compact`: each token's k places looked up among the
C rows), so nothing of the size of N x k rows is read, written or sorted back.
`lax.cond(rows in groups <= C, compact, every row)`: a router that sends the
share more than C rows takes the branch over all N x k rows, which is the path
of a device that holds every expert, so no assignment is dropped and the two
branches give the same sums up to float32 reassociation.
`RoutedOutput.compact` says which ran.  The compact buffer is also what the
exchange over 'ep' will fill: the rows a chip receives from the all-to-all are
exactly the sorted head that it holds experts for.

A held share given few rows (FEW_ROWS: a decode step's slots, and a prefill's
smaller buckets) takes neither: it loops over the experts that were given a
row, as many turns as there are, and each turn reads that expert's matrices
(two, or a gated expert's three) where they lie in the stack and takes every
row through them, weighted by what the row gives that expert (0 for most).  A
step's 8 live rows give a share of 16 of 128 experts six assignments a layer
on some 2 experts.  For each expert that has a row, however few, the grouped
matmul takes 0.17 ms over ungated experts of 20 MB (a seventh of the chip's
bandwidth) and the loop the time to read it once; gated experts the grouped
matmul reads nearly as fast as the loop, and what the loop saves there is a
layer's fixed part: the conditional, the compact rows' gathers and the kernel's
metadata, 22 to 50 us a layer (FEW_ROWS has the readings).  A loop of as many
turns as the rows decide is one jax does not differentiate: a gradient asked
through it is the grouped matmul's over all N x k rows, which are the same sums.

`moe_ffn` is expert parallelism for training over an 'ep' mesh axis:
switch-style top-1 routing with a capacity, tokens exchanged with
`lax.all_to_all`, ungated experts.  Its dispatch/combine use STATIC-SHAPE
scatter/gather on flat slot indices (token n -> slot expert_idx[n] * capacity
+ position-within-expert), with dropped tokens routed to one overflow row
that is sliced away.  The classic one-hot-einsum formulation ("nxc,ne->xce")
is O(N*X*C*E) — at N=8k tokens, 4 experts, capacity 2.5k it spends ~2.5x the
expert FFN's FLOPs on routing alone and materialises [N, X, C] dispatch
tensors (measured 3.4 s/step vs 0.1 s dense on v5e); the scatter form is
O(N*E) with the same static shapes, gradients, and all_to_all layout.
Experts' weights are sharded over 'ep'; tokens travel to their expert's
device via `lax.all_to_all`.  More than one expert a token, or gated experts,
over 'ep' is not built yet: models/transformer.py refuses the combination.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# the experts' matrices among a block's weights (init_moe_params), each [X, ...]
EXPERT_MATRICES = ("w_gate", "w_up", "w_down", "w_in", "w_out")
# the chip's lanes.  An ungated expert's first matrix whose width F is over one tile of lanes and no multiple
# of it is stored [X, E, F up to whole tiles], the columns past F zeros (`init_moe_params`): act(0) = 0 and
# `routed_ffn` hands the second matrix the first F columns, so the expert is F wide.  The chip lays an array
# [.., 2688, 1856] out with 2688 innermost (1,856 is 14.5 tiles), the grouped matmul's kernel takes [X, E, F]
# with F innermost, and the compiler then copied every layer's experts from the one layout into the other at
# every call: 3.4 GB twice at Nemotron-H's 23 layers of 16 (its own report, compiled for a described v5e;
# tests/test_chip_compile.py holds the decode step to no such copy).  With F innermost the chip pads 1,856 to
# 1,920 itself: stored at 1,920 the matrix takes the bytes it would take, and is read where it lies.
LANES = 128


# an expert's activation, by the configuration's name for it (`TransformerConfig.moe_act`)
ACTIVATIONS = {"silu": jax.nn.silu, "relu2": lambda h: jnp.square(jax.nn.relu(h))}


# A held share's compact buffer (`routed_ffn`, module docstring) is this many
# times the even share of the assignments, N * k * held / routed.  Measured on
# the chip (scripts/moe_compact_sweep.py `held`, PERF.md section 6, PR 45): of
# 1,134 (prompt, expert layer) pairs of `kexaone-longrag-closed6`'s own seeded
# models (9 seeds; 8 of 128 held) 87.8% were given at most 2 x the even share
# of their bucket, 95.9% at most 3 x, 98.7% at most 4 x, 99.8% at most 6 x (the
# most 6.08 x: a seeded router is not balanced); of 972 of `axk1-rag-closed6`
# (12 of 192) all at most 2 x.  At 3 x and 4 x the layer costs the same (11.1
# and 11.6 ms at 8,192 rows, all N x k rows 21.1), so the larger holds.
COMPACT_SHARE = 4
# The compact combine looks its rows up in column blocks of at most this many
# bytes of the computed rows.  The chip's compiler keeps a source of up to 117
# MB (8,192 x 7,168 bf16) in fast memory for the k gathers that read it and
# leaves a larger one in HBM, where a gather of 8,192 rows is 0.77 ms in place
# of 0.16: at 8,192 rows x 8 of width 6,144 (C = 16,384, 201 MB) the layer
# reads 13.4 ms with one block and 11.6 with two (my chip runs, PR 45).
COMPACT_LOOKUP_BYTES = 96 * 2 ** 20


# A held share given at most this many rows loops over its touched experts (`routed_ffn`, module docstring) in
# place of the grouped matmul.  Measured on the chip (scripts/moe_few_rows_sweep.py), microseconds a layer,
# grouped matmul -> loop.
#
# Ungated, Nemotron-3-Nano's widths (an expert 2,688 x 1,856 stored 1,920 wide, 20.3 MB; 16 held of 128, six a
# token; PERF.md section 6, PR 50).  A decode step's 32 rows, 8 live, all sending t assignments to the same t held
# experts: t = 1: 217 -> 58, 2: 390 -> 79, 3: 564 -> 112, 4: 737 -> 146, 6: 1,085 -> 213; spread over 8 experts
# 1,430 -> 280, over all 16: 2,817 -> 548.  The grouped matmul is 44 + 173 a touched expert (117 GB/s), the loop
# 12 + 33.5 (606 GB/s of the chip's 819).  Every row live, a random router: 32 rows 2,470 -> 481, 64: 2,822 -> 531,
# 128: 3,352 -> 576, 256: 3,550 -> 665, 512: 3,883 -> 1,098, 1,024: 4,212 -> 2,010 (PR 61; all 16 touched: the
# loop takes every row through every touched expert, 11 GFLOP an expert at 512 rows).
#
# Gated (PERF.md section 6, PR 61; the compact buffer behind its conditional -> the loop).  The grouped matmul
# reads gated experts at 500-670 GB/s, not at the ungated kind's 117: what the loop takes out is the fixed part
# of a layer (the conditional, the gathers of the compact rows, the kernel's metadata) and a tenth of the read.
#   Keye-VL 2,048 x 768, 9.4 MB, 16 of 128, a step's 4 rows: 35 + 17.3 t -> 13 + 17.2 t (t = 1: 53 -> 30, 3: 88 -> 63,
#     8: 171 -> 150); all live, 64 rows 436 -> 336, 256: 661 -> 422, 512: 678 -> 573, 1,024: 842 -> 975.
#   Kimi-Linear 2,304 x 1,024, 14.2 MB, 16 of 256, 32 rows of which 8 live: 44 + 28.2 t -> 12.5 + 26.4 t (t = 1:
#     73 -> 39, 3: 129 -> 92, 8: 269 -> 224); 64 rows 511 -> 430, 256: 918 -> 550, 512: 993 -> 828, 1,024: 1,115 -> 1,411.
#   K-EXAONE 6,144 x 2,048, 75.5 MB, 8 of 128, 32 rows of which 6 live: 55 + 112 t -> 12 + 108 t (t = 1: 167 -> 120,
#     3: 391 -> 336, 8: 952 -> 876); 64 rows 1,077 -> 879, 256: 2,005 -> 1,019, 512: 2,155 -> 1,789, 1,024: 2,408 -> 3,449.
#   A.X-K1 7,168 x 2,048, 88 MB, 12 of 192, 32 rows of which 6 live: 57 + 132 t -> 13 + 124.5 t (t = 1: 191 -> 138,
#     3: 455 -> 387, 8: 1,113 -> 1,009); 64 rows 1,548 -> 1,265, 256: 3,460 -> 1,745, 512: 3,565 -> 3,031, 1,024:
#     3,843 -> 5,955.
# All four cross between 512 and 1,024 rows, whatever the expert's width: past some 250 rows a turn is bound by its
# products (every row through every touched expert: rows x the expert's parameters x 2 operations at the matrix
# peak) and the grouped path by reading the held experts at about 300 GB/s, and both grow with the expert's size
# alike.  So one constant serves both forms and every width; a bucket of 1,024 keeps the compact grouped path.
FEW_ROWS = 512


def takes_loop(n: int, held) -> bool:
    """Whether `routed_ffn` given `n` rows and this `held` loops over the experts touched (FEW_ROWS).  Known as
    the program is traced, so its text holds one path, and the batcher knows which without asking the device."""
    return held is not None and n <= FEW_ROWS


def compact_buffer_rows(n: int, k: int, held: int, routed: int) -> int:
    """The sorted rows a share of `held` of `routed` experts computes of N x k
    assignments when no more than that many fall on it: COMPACT_SHARE times
    the even share, in whole sublanes.  0 where the layer keeps all N x k: a
    share so large, or rows so few, that the buffer is over half of them."""
    c = -(-COMPACT_SHARE * n * k * held // (8 * routed)) * 8
    return c if 2 * c <= n * k else 0


class MoEOutput(NamedTuple):
    out: jax.Array
    aux_loss: jax.Array  # load-balancing loss (Switch Transformer style)


class RoutedOutput(NamedTuple):
    out: jax.Array  # [N, E], x's dtype
    aux_loss: jax.Array  # load-balancing loss, as moe_ffn's
    experts_touched: jax.Array  # int32: experts (of those held) that were given at least one row
    assignments: jax.Array  # int32: (row, expert) pairs that were computed: live rows x k, of which on experts held
    compact: jax.Array  # int32: 1 where a held share's rows went through the compact buffer (module docstring), else 0


def _sort_by_expert(expert, n_experts: int):
    """`expert` [N * k]: each assignment's group, `n_experts` for one that has none here.  Returns (sorted row ->
    assignment, each group's rows [n_experts], the sorted rows that belong to a group: the sorted head,
    assignment -> sorted row)."""
    order = jnp.argsort(expert, stable=True)
    group_sizes = jnp.sum(expert[:, None] == jnp.arange(n_experts), axis=0, dtype=jnp.int32)
    in_groups = jnp.sum(group_sizes)
    back = jnp.zeros(expert.shape, jnp.int32).at[order].set(jnp.arange(expert.shape[0], dtype=jnp.int32))
    return order, group_sizes, in_groups, back


def _combine_every_row(out, gate, order, back, in_groups):
    """All N x k sorted rows `out` [N * k, E], each weighted by its gate [N, k]
    in float32, returned to token order and summed over k: [N, E] in out's dtype."""
    n, k = gate.shape
    with jax.named_scope("moe.combine"):
        # rows past the last group belong to no expert: their product is not defined
        weighted = jnp.where((jnp.arange(n * k) < in_groups)[:, None], out.astype(jnp.float32), 0.0)
        weighted = weighted * gate.reshape(n * k)[order][:, None]
        return jnp.sum(weighted[back].reshape(n, k, -1), axis=1).astype(out.dtype)


def _combine_compact(out, gate, back, in_groups):
    """The same sums from the sorted head alone, `out` [C, E] with every
    group's rows in it (`in_groups <= C`): each token's k places are looked up
    in the C computed rows, an assignment that was computed elsewhere (or by
    nobody: a row not live) at weight 0, one [N, .] gather a place, weighted
    and added in float32 in the places' order.  Linear in C and N x k; no
    [N * k, E] array is made.  The C rows are looked up a block of columns at
    a time, each block a value of its own (the barrier), of at most
    COMPACT_LOOKUP_BYTES: the chip's compiler keeps a block of that size in
    fast memory for its k gathers, and one that is larger in HBM."""
    n, k = gate.shape
    c, e = out.shape
    blocks = -(-c * e * out.dtype.itemsize // COMPACT_LOOKUP_BYTES)
    width = -(-e // (128 * blocks)) * 128
    with jax.named_scope("moe.combine"):
        at = back.reshape(n, k)
        weight = jnp.where(at < in_groups, gate, 0.0)
        at = jnp.minimum(at, c - 1)
        computed = (jnp.arange(c) < in_groups)[:, None]
        sums = []
        for lo in range(0, e, width):
            block = jnp.where(computed, out[:, lo:lo + width], 0)
            if blocks > 1:
                block = lax.optimization_barrier(block)
            total = jnp.zeros((n, block.shape[-1]), jnp.float32)
            for j in range(k):
                total = total + block[at[:, j]].astype(jnp.float32) * weight[:, j, None]
            sums.append(total.astype(out.dtype))
        return sums[0] if len(sums) == 1 else jnp.concatenate(sums, axis=-1)


def routed_ffn(
    x: jax.Array,  # [N, E]
    router: jax.Array,  # [E, X], this layer's
    experts,  # name -> the experts' matrices of every layer, stacked: [L, X, ...]
    layer=0,  # which of the L this call is: an int, or a scan's index
    *,
    k: int = 1,
    renormalize: bool = False,  # the k probabilities divided by their sum
    live: Optional[jax.Array] = None,  # [N] bool; None = every row
    scoring: str = "softmax",  # or "sigmoid": the scores the k largest are taken of
    scale: float = 1.0,  # what the k weights are multiplied by
    held: Optional[Tuple[int, int]] = None,  # (first, count) of the router's experts in `experts`; None = all
    act: str = "silu",  # the experts' activation: ACTIVATIONS' key
) -> RoutedOutput:
    """The dropless routed expert FFN of one device (module docstring).  Every
    live row's k assignments are computed; a row that is not live is given to
    no expert, adds to no group and comes back as zeros.  The experts are
    gated if `experts` holds w_gate, w_up [L, X, E, F] and w_down [L, X, F, E],
    else ungated: w_in [L, X, E, F] (or wider by columns of zeros: LANES) and
    w_out [L, X, F, E]; `act` is their activation, silu or relu(x)^2.

    The experts come as every layer's, with the layer's index, because inside
    a scan over the layers they are best not sliced.  The grouped matmul is a
    kernel, a kernel's operand has to be a buffer of its own, and a layer's
    slice of the stacked [L, X, E, F] is then a copy of all X experts at every
    step (0.7 ms a matrix and layer at OLMoE's sizes: PERF.md section 6, PR
    27).  So the matmul runs over L * X groups of which only this layer's X
    have rows, and reads the stack in place.  A caller that holds one layer's
    matrices hands them over as a stack of one (`w[None]`, layer 0): the same
    matmul over X groups.

    `held` = (first, count): `experts` holds experts first .. first + count - 1
    of the router's X, as [L, count, ...]; the groups are those count.  The
    load-balance loss is then the held experts' terms of the sum."""
    n, _ = x.shape
    dt = x.dtype
    n_routed = router.shape[-1]
    first, n_experts = (0, n_routed) if held is None else held  # n_experts: those with a group here
    gated = "w_gate" in experts
    activation = ACTIVATIONS[act]
    with jax.named_scope("moe.router"):
        logits = jnp.dot(x, router.astype(dt), preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            gate, idx = lax.top_k(probs, k)  # [N, k] each, float32 / int32
            if renormalize:
                gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            gate, idx = lax.top_k(scores, k)
            if renormalize:
                gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)  # the load-balance loss's
        else:
            raise ValueError(f"scoring is 'softmax' or 'sigmoid', not {scoring!r}")
        if scale != 1.0:
            gate = gate * scale
    with jax.named_scope("moe.dispatch"):
        expert = idx.reshape(n * k)
        if held is not None:
            # an assignment to an expert that lives elsewhere sorts behind the last group
            expert = jnp.where((expert >= first) & (expert < first + n_experts), expert - first, n_experts)
            probs = probs[:, first:first + n_experts]
        if live is not None:
            # a row that takes no expert sorts behind the last group
            expert = jnp.where(jnp.repeat(live, k), expert, n_experts)
        order, group_sizes, in_groups, back = _sort_by_expert(expert, n_experts)
    n_layers = experts["w_down" if gated else "w_out"].shape[0]
    of_layer = lambda group_sizes, layer: jnp.zeros((n_layers, n_experts), jnp.int32).at[layer].set(group_sizes).reshape(-1)
    every = of_layer(group_sizes, layer)  # every layer's groups: this layer's alone have rows

    def through_experts(c: int, x, experts, order, every):
        """The first `c` sorted rows gathered, through their experts: [c, E] in
        x's dtype.  Rows past the last group belong to no expert: their
        product is not defined, and `moe.combine` masks them."""
        grouped = lambda a, w: lax.ragged_dot(a, w.reshape(-1, *w.shape[2:]).astype(dt), every)
        with jax.named_scope("moe.dispatch"):
            rows = x[order[:c] // k]  # [c, E], expert by expert
        with jax.named_scope("moe.experts"):
            if gated:
                hidden = activation(grouped(rows, experts["w_gate"])) * grouped(rows, experts["w_up"])
                return grouped(hidden, experts["w_down"])
            hidden = activation(grouped(rows, experts["w_in"]))
            return grouped(hidden[:, :experts["w_out"].shape[-2]], experts["w_out"])  # without w_in's columns of zeros

    def every_row(x=x, gate=gate, experts=experts, order=order, in_groups=in_groups, back=back, every=every):
        return _combine_every_row(through_experts(n * k, x, experts, order, every), gate, order, back, in_groups)

    def compact_rows(c: int):
        return _combine_compact(through_experts(c, x, experts, order, every), gate, back, in_groups)

    def touched_experts(x, gate, experts, expert, group_sizes, layer):
        """Every row through each held expert that was given one, an expert a
        turn, weighted by what the row gives it and summed in float32: [N, E]."""
        flat = {name: experts[name].reshape(-1, *experts[name].shape[2:]) for name in EXPERT_MATRICES if name in experts}
        with jax.named_scope("moe.dispatch"):
            held_idx = expert.reshape(n, k)[:, :, None] == jnp.arange(n_experts)  # [N, k, X]
            weight = jnp.sum(jnp.where(held_idx, gate[:, :, None], 0.0), axis=1)  # [N, X]: 0 where the row takes none of it
            touched = jnp.argsort(group_sizes == 0, stable=True)  # the experts given a row first

        def turn(i, total):
            with jax.named_scope("moe.experts"):
                at = layer * n_experts + touched[i]
                through = lambda a, name: a @ lax.dynamic_index_in_dim(flat[name], at, keepdims=False).astype(dt)
                if gated:
                    out = through(activation(through(x, "w_gate")) * through(x, "w_up"), "w_down")
                else:
                    out = through(activation(through(x, "w_in"))[:, :flat["w_out"].shape[-2]], "w_out")
            with jax.named_scope("moe.combine"):
                return total + out.astype(jnp.float32) * lax.dynamic_index_in_dim(weight, touched[i], 1)

        total = lax.fori_loop(0, jnp.sum(group_sizes > 0), turn, jnp.zeros(x.shape, jnp.float32))
        return total.astype(dt)

    def touched_experts_bwd(given, g):
        """The gradient of all N x k rows through the grouped matmul, which are the loop's sums: jax
        differentiates no loop of as many turns as the rows decide.  The rows are sorted anew from `expert`."""
        *floats, expert, _, layer = given
        order, group_sizes, in_groups, back = _sort_by_expert(expert, n_experts)
        pulled = jax.vjp(lambda *floats: every_row(*floats, order, in_groups, back, of_layer(group_sizes, layer)), *floats)[1](g)
        return (*pulled, None, None, None)

    c = 0 if held is None else compact_buffer_rows(n, k, n_experts, n_routed)
    if takes_loop(n, held):
        loop = jax.custom_vjp(touched_experts)
        loop.defvjp(lambda *given: (touched_experts(*given), given), touched_experts_bwd)
        compact, out = jnp.zeros((), bool), loop(x, gate, experts, expert, group_sizes, layer)
    elif c:
        compact = in_groups <= c
        out = lax.cond(compact, lambda: compact_rows(c), every_row)
    else:
        compact, out = jnp.zeros((), bool), every_row()
    # load-balance aux loss over the live rows: share of the assignments an
    # expert was given times its mean probability, summed over experts
    rows_live = jnp.ones((n,), jnp.float32) if live is None else live.astype(jnp.float32)
    n_live = jnp.maximum(jnp.sum(rows_live), 1.0)
    frac = group_sizes.astype(jnp.float32) / (n_live * k)
    mean_prob = jnp.sum(probs * rows_live[:, None], axis=0) / n_live
    aux = jnp.sum(frac * mean_prob) * n_routed
    return RoutedOutput(out, aux, jnp.sum(group_sizes > 0).astype(jnp.int32), in_groups, compact.astype(jnp.int32))


def moe_ffn(
    x: jax.Array,  # [N_local_tokens, E]
    router_w: jax.Array,  # [E, n_experts] (replicated)
    w_in: jax.Array,  # [local_experts, E, F]
    w_out: jax.Array,  # [local_experts, F, E]
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    act: str = "silu",  # the experts' activation: ACTIVATIONS' key
) -> MoEOutput:
    """Call inside shard_map (manual over `axis_name`).  `w_in` may be wider than
    `w_out` is tall by columns of zeros (LANES)."""
    ep = lax.psum(1, axis_name)
    n_local, e_model = x.shape
    local_experts = w_in.shape[0]
    n_experts = ep * local_experts

    logits = x @ router_w  # [N, n_experts]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # top-1
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]  # [N]

    capacity = int(max(1, (n_local * capacity_factor) // n_experts + 1))
    # position of each token within its expert's queue (cumulative count of
    # same-expert tokens before it); int path — no [N, X, C] one-hots
    onehot_i = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [N, X]
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot_i, axis=0) - 1, expert_idx[:, None], axis=-1
    )[:, 0]  # [N]
    keep = pos < capacity
    # flat slot: expert * capacity + position; dropped tokens go to the one
    # overflow row (X*C) that both sides discard
    slot = jnp.where(keep, expert_idx * capacity + pos, n_experts * capacity)
    expert_in = jnp.zeros((n_experts * capacity + 1, e_model), x.dtype)
    expert_in = expert_in.at[slot].set(x)  # unique slots: set, not add
    expert_in = expert_in[: n_experts * capacity]
    expert_in = expert_in.reshape(ep, local_experts, capacity, e_model)
    # each device receives, for its local experts, the token slots from every
    # source device: [ep_src, local_experts, C, E]
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0, concat_axis=0, tiled=False)
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        local_experts, ep * capacity, e_model
    )

    h = ACTIVATIONS[act](jnp.einsum("xne,xef->xnf", expert_in, w_in))
    expert_out = jnp.einsum("xnf,xfe->xne", h[..., :w_out.shape[-2]], w_out)

    # route back
    expert_out = expert_out.reshape(local_experts, ep, capacity, e_model).transpose(
        1, 0, 2, 3
    )
    expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0, concat_axis=0, tiled=False)
    expert_out = expert_out.reshape(n_experts * capacity, e_model)
    # combine: gather each token's slot back and gate it; dropped tokens
    # contribute zero (residual connection carries them unchanged upstream)
    out = jnp.take(expert_out, jnp.minimum(slot, n_experts * capacity - 1), axis=0)
    out = out * (gate * keep.astype(gate.dtype))[:, None]

    # load-balance aux loss: fraction routed * mean prob, summed over experts
    frac = jnp.mean(onehot_i.astype(probs.dtype), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac * mean_prob) * n_experts
    return MoEOutput(out, aux)


def init_moe_params(key, e_model: int, f_hidden: int, n_experts: int, dtype=jnp.float32,
                    gated: bool = False, held: Optional[int] = None):
    """A router over n_experts and the experts' matrices: all n_experts of them,
    or the `held` that this device keeps.  An ungated expert's first matrix of
    a width over LANES that is no multiple of it is made with columns of zeros up
    to whole tiles (LANES says why)."""
    n_routed, n_experts = n_experts, held or n_experts
    if gated:
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "router": jax.random.normal(k1, (e_model, n_routed), dtype) * 0.02,
            "w_gate": jax.random.normal(k2, (n_experts, e_model, f_hidden), dtype) * e_model ** -0.5,
            "w_up": jax.random.normal(k3, (n_experts, e_model, f_hidden), dtype) * e_model ** -0.5,
            "w_down": jax.random.normal(k4, (n_experts, f_hidden, e_model), dtype) * f_hidden ** -0.5,
        }
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = (2.0 / e_model) ** 0.5
    scale_out = (2.0 / f_hidden) ** 0.5
    w_in = jax.random.normal(k2, (n_experts, e_model, f_hidden), dtype) * scale_in
    if f_hidden > LANES and f_hidden % LANES:
        w_in = jnp.pad(w_in, ((0, 0), (0, 0), (0, -f_hidden % LANES)))
    return {
        "router": jax.random.normal(k1, (e_model, n_routed), dtype) * 0.02,
        "w_in": w_in,
        "w_out": jax.random.normal(k3, (n_experts, f_hidden, e_model), dtype) * scale_out,
    }
