"""Expert parallelism — switch-style top-1 MoE over a mesh axis.

The last of the five parallelism axes (dp/sp/tp/pp/ep). Beyond the
reference (its CNNs have no expert structure), included because the mesh
design claims multi-axis readiness and MoE is the standard way conditional
compute scales on TPU pods (Switch Transformer, Fedus et al. 2021,
arxiv 2101.03961; the dispatch/combine-as-einsum formulation is the
Mesh-TensorFlow idiom — dense one-hot contractions on the MXU, no
data-dependent scatters, static shapes throughout).

Layout: E experts on an ``expert`` mesh axis of size E — device e holds
expert e's parameters AND a 1/E shard of the tokens. Per step:

1. gate: top-1 expert per local token (f32 softmax);
2. capacity: each source device may send at most C tokens to each expert
   (position = running count of earlier local tokens choosing the same
   expert; overflow tokens are DROPPED — their combine weight is zero, the
   caller's residual connection carries them, exactly Switch semantics);
3. dispatch: one-hot einsum packs tokens into a ``[E, C, D]`` buffer, one
   `lax.all_to_all` routes slice e to device e;
4. each device runs ITS expert once over the ``[E·C, D]`` received batch
   (every expert is busy every step — the whole point of the layout);
5. the inverse all_to_all brings results home; the transposed one-hot
   einsum scatters them back to token order, scaled by the gate prob.

Everything is differentiable end to end (all_to_all transposes to the
inverse all_to_all; the one-hot contractions transpose to each other), so
gate and expert gradients need no custom rules. Exactness (fwd + grad)
against a dense single-program oracle with the identical drop rule is
pinned in tests/test_moe.py.

The one-hot dispatch/combine contractions have a fused alternative: the
Pallas kernels in `ops/moe_kernel.py` keep the ``[n, E, C]`` mask VMEM-
resident per token tile instead of materializing it in HBM twice per step
(``fused=True``; oracle-equal fwd + grad in the interpreter, pinned in
tests/test_moe_kernel.py; Mosaic has refused them at every shape so far,
tests/test_chip_compile.py).

Returns the combined output plus the switch load-balancing auxiliary loss
``E · Σ_e f_e · P_e`` computed on the LOCAL token shard (the standard
per-core practice — average it with the task loss through the ordinary
data-parallel machinery): add ``aux_weight · aux`` (paper default 1e-2) to
the training loss to keep routing balanced.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops.grouped import grouped_product, grouped_product_fuses
from distribuuuu_tpu.ops.interpret import pallas_interpret


def token_slot_positions(onehot_e: jnp.ndarray) -> jnp.ndarray:
    """Per-token position in its chosen expert's send buffer, as **int32**.

    ``onehot_e`` is the float one-hot expert choice ``[n, E]``; the result
    ``[n]`` is the running count of earlier local tokens that chose the same
    expert. The cumsum runs over the *cast* int32 one-hot, not the float
    one: a float32 cumsum stops counting exactly at 2^24 (16.8M — real for
    long-sequence shards), silently freezing every later token's slot at
    the same position, so capacity assignment would overwrite slots and
    corrupt the dispatch without any error. Int32 counts exactly to 2^31.
    """
    oh = onehot_e.astype(jnp.int32)
    return jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)


def switch_moe(
    x: jnp.ndarray,
    gate_kernel: jnp.ndarray,
    expert_params: Any,
    expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    *,
    capacity: int,
    axis_name: str = "expert",
    fused: bool = False,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 mixture-of-experts over ``axis_name``.

    Args:
      x: local token shard ``[n, D]`` (tokens sharded over the expert axis).
      gate_kernel: ``[D, E]`` router weights (replicated).
      expert_params: THIS device's expert parameters.
      expert_fn: ``(params, tokens [m, D]) -> [m, D]``, shape-preserving.
      capacity: C, max tokens each source device may send to each expert.
        Size it ``ceil(n / E) · capacity_factor`` with factor 1.25–2.
      fused: route dispatch/combine through the Pallas kernels in
        `ops/moe_kernel.py` (the ``[n, E, C]`` one-hot mask stays VMEM-
        resident instead of round-tripping HBM twice); oracle equality
        (fwd + grad, incl. the capacity-drop boundary) is pinned in
        tests/test_moe_kernel.py.
      interpret: run the fused kernels in the Pallas interpreter (CPU
        tests); ignored on the einsum path.

    Returns ``(combined [n, D], aux_loss scalar)``; dropped tokens come
    back as zeros (wrap with a residual: ``x + switch_moe(...)[0]``).

    Gradient contract (pinned vs a dense oracle in tests/test_moe.py):
    compute ``loss_local = task_loss(out) + aux_weight · aux`` on the
    local shard and differentiate inside `shard_map`; then, as for any
    mixed replicated/sharded parameterization, average the REPLICATED
    params' grads over the axis (``lax.pmean`` for gate_kernel and
    anything upstream of x) and divide the per-device EXPERT params'
    grads by the axis size (their cotangents arrive summed over source
    shards, while the global loss is the mean over shards).
    """
    n, d = x.shape
    e = lax.axis_size(axis_name)
    if gate_kernel.shape[-1] != e:
        raise ValueError(
            f"gate_kernel routes to {gate_kernel.shape[-1]} experts but the "
            f"'{axis_name}' axis has {e} devices (one expert per device); "
            "tokens routed past the axis would be silently dropped"
        )
    if fused:
        from distribuuuu_tpu.ops.moe_kernel import (
            fused_moe_dispatch,
            fused_moe_combine,
        )

        from distribuuuu_tpu.ops.interpret import pallas_interpret

        # asked for by the caller or process-wide (ops/interpret.py), never
        # picked from the platform
        interpret = interpret or pallas_interpret()

        send, top, pos, w, fp_sum = fused_moe_dispatch(
            x, gate_kernel, capacity=capacity, interpret=interpret
        )
        recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
        y = expert_fn(expert_params, recv.reshape(e * capacity, d).astype(x.dtype))
        y = y.reshape(e, capacity, d).astype(jnp.float32)
        back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0, tiled=True)
        out = fused_moe_combine(back, top, pos, w, interpret=interpret).astype(x.dtype)
        f_e = fp_sum[0] / n
        p_e = fp_sum[1] / n
        aux = e * jnp.sum(f_e * p_e)
        return out, aux

    probs = jax.nn.softmax((x.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)), axis=-1)
    top = jnp.argmax(probs, axis=-1)  # [n]
    top_p = jnp.take_along_axis(probs, top[:, None], axis=-1)[:, 0]  # [n]

    onehot_e = jax.nn.one_hot(top, e, dtype=jnp.float32)  # [n, E]
    # position of each token within its expert's send buffer (source-local):
    # the running count of earlier local tokens that chose the same expert.
    # Counted in int32 — a float32 cumsum silently saturates at 2^24 tokens
    # per expert and would corrupt slot assignment past it (see
    # token_slot_positions).
    pos = token_slot_positions(onehot_e)  # [n] int32
    keep = pos < capacity
    pos_c = jnp.clip(pos, 0, capacity - 1)
    onehot_c = jax.nn.one_hot(pos_c, capacity, dtype=jnp.float32)  # [n, C]
    # dispatch mask [n, E, C]: token t -> slot (top_t, pos_t), dropped -> 0
    dispatch = onehot_e[:, :, None] * onehot_c[:, None, :] * keep[:, None, None].astype(jnp.float32)

    send = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))  # [E, C, D]
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # recv[src, c, :] = slot c sent by source device src, all for MY expert
    y = expert_fn(expert_params, recv.reshape(e * capacity, d).astype(x.dtype))
    y = y.reshape(e, capacity, d).astype(jnp.float32)
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # back[e, c, :] = expert e's output for my token in slot (e, c)
    combine = dispatch * top_p[:, None, None]
    out = jnp.einsum("nec,ecd->nd", combine, back).astype(x.dtype)

    # Switch LB loss on the LOCAL token shard: f_e = fraction routed to e
    # (pre-drop), P_e = mean router prob. Local-batch aux is the standard
    # practice (per-core aux averaged by the ordinary loss machinery) and
    # keeps the gradient contract uniform: treat aux exactly like the task
    # loss when reducing/differentiating.
    f_e = jnp.mean(onehot_e, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return out, aux


# ---------------------------------------------------------------------------
# Top-k experts held by share: route over all, compute the experts held here
# ---------------------------------------------------------------------------
#
# The layer of a model whose experts outnumber the chips: this chip is told
# which experts it holds (``first … first + held - 1`` of ``E``), scores every
# token over all ``E``, and computes the part of the mixture its own experts
# give, for the tokens routed to them. What the absent experts would add is
# left out (their chips add it; on one chip that exchange does not run). No
# capacity and no drops: a token-expert slot is never discarded, however
# uneven the routing. The slots are sorted by expert into one list, laid out in
# blocks of `BLOCK` rows that belong to one expert each, and computed a round
# of rows at a time: the work follows the number of slots that landed here,
# not the fullest expert's (`held_experts`). A block's two products read its
# expert's weights where they lie (`ops/grouped.py`: two Mosaic kernels) where
# the program is traced for TPUs and the widths tile, and are XLA's batched
# products against gathered copies of the weights everywhere else.

#: rows of a block of the sorted layout: one expert's, so that a block is one
#: product with that expert's weights; an expert's last block is padded
BLOCK = 256


#: the name `sigmoid_topk_route` gives the chosen ids, for a checkpoint policy to keep (an identity elsewhere)
ROUTE_IDX = "moe_route_idx"


def _chosen(scores, idx):
    """``scores [T, E]`` at the chosen ``idx [T, k]``, by comparison and not `take_along_axis`: the same numbers
    (a token's choices are distinct, so each sum holds one score and zeros, forward and backward), and one
    fused pass over ``[T, k, E]`` where the gather of T·k single elements took 1.8 ms a call on the chip
    (PERF.md §5, PR 30)."""
    return jnp.sum(jnp.where(idx[:, :, None] == jnp.arange(scores.shape[-1]), scores[:, None, :], 0.0), axis=-1)


def sigmoid_topk_route(logits, k: int, bias, scale: float):
    """Top-``k`` of ``E`` by sigmoid score, float32 throughout.

    ``logits [T, E]``; the choice is the top-``k`` of ``sigmoid(logits) + bias``
    (``bias [E]``, a correction buffer, not trained), the mixture weights are
    the chosen scores themselves, normalised over the choice and scaled:
    ``w_i = scale · s_i / Σ_choice s_j``. Returns ``(idx [T, k] int32, w [T, k])``.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), k)
    # named before its first use: a layer checkpoint that keeps `ROUTE_IDX` (models/nemotron_h.KEPT) then
    # runs the sort once; named on the way out, the comparison below would still hang on the unnamed value
    idx = checkpoint_name(idx, ROUTE_IDX)
    chosen = _chosen(scores, idx)
    return idx, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def softmax_topk_route(logits, k: int):
    """Top-``k`` of ``E`` by softmax probability, float32 throughout.

    ``logits [T, E]``; ``p = softmax(logits)`` over all ``E``, the choice its
    ``k`` largest (the lower id wins a tie, as `lax.top_k` orders them), the
    mixture weights the chosen probabilities normalised over the choice:
    ``w_i = p_i / Σ_choice p_j``. Returns ``(idx [T, k] int32, w [T, k])``;
    the ids go under `ROUTE_IDX`, as `sigmoid_topk_route` names them.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx = lax.top_k(probs, k)
    idx = checkpoint_name(idx, ROUTE_IDX)  # before its first use: see `sigmoid_topk_route`
    chosen = _chosen(probs, idx)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def round_rows_for(tokens: int, k: int, experts: int, held: int, room: float = 1.5) -> int:
    """Rows of one round of `held_experts`: the slots expected on the experts held, ``tokens·k·held/experts``,
    with ``room``, and a block of padding an expert, in whole blocks; never more than every token on
    every held expert would need."""
    blocks = lambda rows: max(1, math.ceil(rows / BLOCK))
    return BLOCK * min(blocks(room * tokens * k * held / experts) + held, blocks(tokens) * held)


#: `jax.monitoring` events of `held_experts`, one per call traced for a mesh:
#: which realisation of a block's products it took; the journal's ``counters``
#: records carry them (obs/monitors.py)
GROUPED_CALLS_EVENT = "moe_grouped_calls"
XLA_CALLS_EVENT = "moe_xla_calls"


def _takes_the_kernels(products, dtype) -> bool:
    """The realisation of a block's products ``[(k, n), ...]``, from what the trace can observe:
    `ops/grouped.py`'s kernels where a mesh of TPUs is in use (the described chips of a compile-only
    test count as what they describe) and `grouped_product_fuses` admits the shapes of each; outside
    any mesh (``model.init``, shape inference) XLA's batched products, uncounted."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    fuses = all(grouped_product_fuses(mesh.abstract_device.device_kind, BLOCK, k, n, jnp.dtype(dtype).itemsize)
                for k, n in products)
    jax.monitoring.record_event(GROUPED_CALLS_EVENT if fuses else XLA_CALLS_EVENT)
    return fuses


def relu_squared(hidden):
    """``relu(h)²``: what stands between the two products of an ungated expert."""
    return jnp.square(jax.nn.relu(hidden))


def silu_gated(hidden):
    """``silu(gate) ⊙ up`` over a first product of twice the width, ``[gate | up]``: a gated expert's."""
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def held_experts(x, idx, w, w1, w2, first: int, round_rows: int, *, between):
    """The held experts' part of the mixture ``Σ_i w_i · W2_i between(W1_i x)``.

    ``x [T, D]``; ``idx, w [T, K]`` from the router (ids over all experts);
    ``w1 [H, D, F1]``, ``w2 [H, F2, D]`` the experts ``first … first + H - 1``;
    ``between``: what stands between the two products, elementwise on the
    float32 result of the first, ``[..., F1] -> [..., F2]`` (`relu_squared`
    with ``F2 = F1`` for an ungated expert; `silu_gated` with ``F1 = 2·F2``
    for a gated one, whose gate and up projections are one first weight side
    by side); ``round_rows`` a multiple of `BLOCK` (`round_rows_for`).
    Returns ``(y [T, D] float32, counts [H] int32)``: the sum over the slots
    that landed here, and how many landed on each held expert.

    Under ``dtpu.moe_route``: which tokens hit which held expert and with what
    weight (``[T, H]``; a token picks an expert at most once), one stable sort
    that lists the hits by expert and then by token, the layout of that list
    in blocks of `BLOCK` rows, one expert a block (an expert's last block
    padded), the gather of a round's rows and the weighted scatter-add back.
    Under ``dtpu.moe_experts``: the two products of a round and ``between``,
    every block with its own expert's weights: `ops.grouped.grouped_product`
    (forward and backward kernels; no copy of the weights a block, no weight
    gradient a block) or, where `_takes_the_kernels` says no, a batch of
    blocks against gathered copies of the weights.

    Round 0 takes the first ``round_rows`` rows of the layout and is
    straight-line code; the rows beyond go in groups of one, two, four …
    further rounds, each group behind a `lax.cond` taken only where the
    layout reaches that far. So every slot is computed whatever the routing
    (all tokens on every held expert: every round), the work follows the
    number of slots and not the fullest expert, a step whose slots fit
    ``round_rows`` pays for round 0 alone, and no round holds more than
    ``round_rows`` rows at a time.
    """
    tokens, dim = x.shape
    held = w1.shape[0]
    f32 = jnp.float32
    kernels = _takes_the_kernels([w1.shape[1:], w2.shape[1:]], x.dtype)
    with step_scope("moe_route"):
        hits = idx[:, :, None] == (first + jnp.arange(held))[None, None, :]       # [T, K, H]
        hit = jnp.any(hits, axis=1).T                                             # [H, T]
        gate = jnp.sum(jnp.where(hits, w[:, :, None].astype(f32), 0.0), axis=1).T.reshape(-1)  # [H·T]
        counts = jnp.sum(hit, axis=1, dtype=jnp.int32)                            # [H]
        # the hits' flat ids e·T + t, by expert and then by token; the misses follow
        order = jnp.argsort(jnp.logical_not(hit.reshape(-1)), stable=True).astype(jnp.int32)
        first_hit = jnp.cumsum(counts) - counts                                   # an expert's place in the list
        padded = -(-counts // BLOCK) * BLOCK
        ends = jnp.cumsum(padded)                                                 # ... and in the layout
        begins = ends - padded

    def one_round(x, gate, w1, w2, start):
        """The mixture's part from the rows ``start … start + round_rows - 1`` of the layout, ``[T, D]``."""
        rows = round_rows
        with step_scope("moe_route"):
            at = start + jnp.arange(rows // BLOCK) * BLOCK
            expert = jnp.minimum(jnp.searchsorted(ends, at, side="right"), held - 1)   # [blocks]; past the end: dead rows
            of_row = jnp.repeat(expert, BLOCK)
            rank = start + jnp.arange(rows) - begins[of_row]
            live = rank < counts[of_row]
            flat = order[jnp.clip(first_hit[of_row] + rank, 0, held * tokens - 1)]
            token = jnp.where(live, flat % tokens, tokens)       # dead rows read row 0 and write nothing
            picked = x[jnp.minimum(token, tokens - 1)]
            weight = jnp.where(live, gate[flat], 0.0)
            if kernels:
                live_blocks = jnp.clip((ends[-1] - start) // BLOCK, 0, rows // BLOCK)
            else:
                picked = picked.reshape(rows // BLOCK, BLOCK, dim)
                w1_of, w2_of = w1[expert].astype(x.dtype), w2[expert].astype(x.dtype)  # [blocks, ·, ·]
        with step_scope("moe_experts"):
            if kernels:
                hidden = grouped_product(picked, expert, live_blocks, w1, False, pallas_interpret())
                hidden = between(hidden).astype(x.dtype)
                out = grouped_product(hidden, expert, live_blocks, w2, False, pallas_interpret())
            else:
                hidden = jnp.einsum("brd,bdf->brf", picked, w1_of, preferred_element_type=f32)
                hidden = between(hidden).astype(x.dtype)
                out = jnp.einsum("brf,bfd->brd", hidden, w2_of, preferred_element_type=f32)
        with step_scope("moe_route"):
            return jnp.zeros((tokens, dim), f32).at[token].add(out.reshape(rows, dim) * weight[:, None], mode="drop")

    y = one_round(x, gate, w1, w2, 0)
    later = jax.checkpoint(one_round)  # a round not taken keeps nothing for the backward pass either
    done, rounds = 1, 1  # rounds done, and rounds in the next group: 1, 2, 4, ...
    while done * round_rows < held * (-(-tokens // BLOCK) * BLOCK):
        def group(y, x, gate, w1, w2, done=done, rounds=rounds):
            more = lambda acc, r: (acc + later(x, gate, w1, w2, (done + r) * round_rows), None)
            return lax.scan(more, y, jnp.arange(rounds))[0]

        y = lax.cond(ends[-1] > done * round_rows, group, lambda y, *_: y, y, x, gate, w1, w2)
        done, rounds = done + rounds, 2 * rounds
    return y, counts
