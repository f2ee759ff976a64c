"""Training/eval loops — the SPMD rebuild of `/root/reference/distribuuuu/trainer.py`.

Mapping from the reference's DDP mechanics to the TPU-native design:

| reference (torch/DDP)                          | here (JAX/XLA)                          |
|------------------------------------------------|-----------------------------------------|
| 1 process/GPU + DDP wrapper `trainer.py:134`   | SPMD `shard_map` over Mesh('data')      |
| DDP bucketed grad allreduce (C++ hooks)        | `lax.pmean(grads, 'data')` compiled into the step; XLA overlaps collectives with backward compute |
| SyncBatchNorm rewrite `trainer.py:131`         | BatchNorm(axis_name='data') — stats pmean inside the same program |
| per-iter `.item()` metric sync `trainer.py:53` | on-device psum'd counters, fetched at PRINT_FREQ |
| `optimizer.step()` replicated update           | identical pmean'd update on every device; params stay replicated |
| CrossEntropyLoss `trainer.py:43`               | float32 softmax-CE (metrics.cross_entropy_loss) |
| epoch LR set via param groups `trainer.py:25`  | lr passed as a traced scalar arg (no recompile) |

The jitted step donates the train state: params/opt state are updated in
place in HBM, so peak memory is ~one copy of state + activations.
"""

from __future__ import annotations

import functools
import importlib
import os
import statistics
import sys
import time
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distribuuuu_tpu import checkpoint as ckpt
from distribuuuu_tpu import obs
from distribuuuu_tpu import optim
from distribuuuu_tpu import resilience
from distribuuuu_tpu.config import cfg, dump_cfg
from distribuuuu_tpu.data import (
    construct_train_loader,
    construct_val_loader,
    prefetch_to_device,
)
from distribuuuu_tpu.data.transforms import device_normalize
from distribuuuu_tpu.logging import logger, setup_logger
from distribuuuu_tpu.metrics import (
    construct_meters,
    count_parameters,
    cross_entropy_loss,
    per_example_nll,
    topk_correct,
    topk_correct_weighted,
)
from distribuuuu_tpu.models import build_model
from distribuuuu_tpu.obs.trace import phase, step_scope
from distribuuuu_tpu.parallel import fsdp
from distribuuuu_tpu.parallel import seq as seqpar
from distribuuuu_tpu.runtime import data_mesh, setup_distributed, setup_seed
from distribuuuu_tpu.runtime.seeding import configure_determinism


@flax.struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any


# ---------------------------------------------------------------------------
# Step functions (per-device views under shard_map)
# ---------------------------------------------------------------------------

def _forward_loss(model, params, batch_stats, batch, train: bool, rng, qat=None):
    variables = {"params": params, "batch_stats": batch_stats}
    # u8 batches are normalized here on-device (fused into the first conv);
    # float inputs pass through for pre-normalized callers
    images = device_normalize(batch["image"])
    rngs = {"dropout": rng} if rng is not None else None
    # QUANT.QAT fine-tune (quant/qat.py): the forward runs the fake-quant
    # straight-through-estimator interception instead of the plain apply —
    # same variables, same BN/stats machinery, quantized-grid values
    apply = model.apply if qat is None else functools.partial(qat.apply, model)
    if train:
        logits, mutated = apply(
            variables, images, train=True, mutable=["batch_stats"], rngs=rngs
        )
        new_stats = mutated["batch_stats"]
    else:
        logits = apply(variables, images, train=False)
        new_stats = batch_stats
    # the loss outside the module gets a name of its own, so its forward and
    # backward read as jvp(dtpu.loss) and not as bare ops (obs/trace.py)
    with step_scope("loss"):
        loss = cross_entropy_loss(logits, batch["label"], cfg.TRAIN.LABEL_SMOOTH)
    if qat is not None and train and cfg.QUANT.QAT_DISTILL > 0.0:
        # self-distillation toward the model's own fp logits: the serve
        # gate's logit-RMSE metric, optimized directly (the rescue knob —
        # docs/PERFORMANCE.md "Quantized training"). stop_gradient on the
        # target: the fp twin is the reference, not a second student.
        fp_logits, _ = model.apply(
            variables, images, train=True, mutable=["batch_stats"], rngs=rngs
        )
        drift = logits.astype(jnp.float32) - jax.lax.stop_gradient(
            fp_logits.astype(jnp.float32)
        )
        loss = loss + cfg.QUANT.QAT_DISTILL * jnp.mean(drift**2)
    return loss, (logits, new_stats)


def _forward_loss_mae(model, params, batch_stats, batch, train: bool, rng, seq_n: int,
                      sample_weights=None):
    """Masked-autoencoder forward + pixel loss (TRAIN.TASK "mae").

    The mask is minted per step from the (data/fsdp-folded) step RNG —
    identical on every member of a seq group, which processes the same
    samples. The loss is mean squared error over MASKED patches only,
    normalized by the GLOBAL masked-token count: under seq sharding each
    member sums its local shard and ``psum_partial`` over the seq axis makes
    the loss (and thus the metric) replicated while keeping every parameter
    gradient member-partial — the contract `make_train_step`'s uniform
    seq-axis grad psum completes.
    """
    from distribuuuu_tpu.models.mae import patchify

    images = device_normalize(batch["image"])
    b = images.shape[0]
    patch = model.patch
    l_total = (images.shape[1] // patch) * (images.shape[2] // patch)
    mask_rng, dropout_rng = jax.random.split(rng)
    mask = jax.random.bernoulli(mask_rng, cfg.MODEL.MAE_MASK_RATIO, (b, l_total))
    pred = model.apply(
        {"params": params}, images, mask=mask, train=train,
        rngs={"dropout": dropout_rng} if train else None,
    )
    target = patchify(images.astype(jnp.float32), patch)
    mask_f = mask.astype(jnp.float32)
    if sample_weights is not None:
        # weight-masked exact metrics (eval): padded samples (zero image,
        # weight 0 — the val loader's final-batch fill) must not contaminate
        # the masked-MSE average, mirroring the classify path's nll*w
        mask_f = mask_f * sample_weights.astype(jnp.float32)[:, None]
    if seq_n > 1:
        target = seqpar.local_tokens(target)
        mask_f = seqpar.local_tokens(mask_f)
    with step_scope("loss"):
        err = jnp.mean((pred.astype(jnp.float32) - target) ** 2, axis=-1)  # [B, L_local]
        se = jnp.sum(err * mask_f)
        cnt = jnp.sum(mask_f)
        if seq_n > 1:
            # psum_partial, not lax.psum: the members' sums are PARTIAL and the
            # cotangent coming back is replicated — plain psum's unchecked-mode
            # transpose would scale every gradient by seq_n (parallel/seq.py)
            se, cnt = seqpar.psum_partial((se, cnt), seqpar.SEQ_AXIS)
        loss = se / jnp.maximum(cnt, 1.0)
    # pred rides the logits slot (metrics skip top-k for mae); MAE has no
    # BatchNorm, so the stats pass through untouched
    return loss, (pred, batch_stats)


TASKS = ("classify", "mae", "lm")


def _check_task(task: str) -> None:
    if task not in TASKS:
        raise ValueError(f"TRAIN.TASK must be one of {TASKS}, got {task!r}")


#: how a step's own counter (obs/journal WINDOW_COUNTERS) is reduced: over a
#: step's micro-batches, over the devices, over a window's steps
_COUNTER_REDUCERS = {
    "sum": (jnp.sum, jax.lax.psum, statistics.fmean),
    "max": (jnp.max, jax.lax.pmax, max),
}


def next_token_loss(logits_of, hidden, labels, block: int):
    """Mean next-token cross-entropy, the vocabulary's logits taken for
    ``block`` tokens at a time: ``logits_of(hidden[block, D]) -> [block, V]``
    float32 exists for one block only, forward and (rematerialised) backward.
    A token count that ``block`` does not divide is taken as one block."""
    tokens = labels.size
    if tokens % block:
        block = tokens
    hidden = hidden.reshape(tokens // block, block, hidden.shape[-1])
    labels = labels.reshape(tokens // block, block)

    @jax.checkpoint
    def block_nll(h, y):
        logits = logits_of(h)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.sum(jax.lax.map(lambda hy: block_nll(*hy), (hidden, labels))) / tokens


def _forward_loss_lm(model, params, batch_stats, batch):
    """Next-token forward + loss (TRAIN.TASK "lm"): a row of ``batch["tokens"]``
    is ``L + 1`` ids, inputs and labels one leaf shifted. The model returns
    its final hidden states and its routing counters, which ride the logits
    slot (metrics skip top-k for lm); its buffers pass through untouched."""
    variables = {"params": params, "batch_stats": batch_stats}
    tokens = batch["tokens"]
    hidden, counters = model.apply(variables, tokens[:, :-1], train=True)
    with step_scope("loss"):
        loss = next_token_loss(
            lambda h: model.apply(variables, h, method="head_logits"),
            hidden, tokens[:, 1:], cfg.LM.LOSS_BLOCK,
        )
    return loss, (counters, batch_stats)


def make_train_step(
    model, tx, mesh: Mesh, topk: int, accum_steps: int = 1,
    nonfinite_guard: bool | None = None, state_specs=None, qat=None,
    task: str | None = None,
):
    """Build the jitted SPMD train step.

    Per-device: forward/backward on the local batch shard → `pmean` grads over
    the data axis → identical optimizer update everywhere. Metrics are raw
    *count* sums (`psum`) so averaging is exact regardless of shard sizes.

    ``state_specs`` (a TrainState of PartitionSpecs, from
    `parallel.fsdp.specs_of`) turns on ZeRO-style execution on a
    ``('data', 'fsdp')`` mesh: the state arrives as 1/N shards, the forward
    pass materializes full parameters via all-gather *inside* the loss (whose
    autodiff transpose is the grad reduce-scatter, so backward grads are
    already shards), and the optimizer update runs leafwise on the shard.
    ``None`` (the default) is the original fully-replicated path, bit-for-bit.

    ``accum_steps > 1``: the local batch is split into that many micro-batches
    and grads/metrics are averaged over a `lax.scan` before the single
    optimizer update — same effective batch as more chips, constant memory.
    BN running stats thread through the scan carry and EMA sequentially per
    micro-batch (torch-exact semantics).

    ``nonfinite_guard`` (default ``cfg.FAULT.NONFINITE_GUARD``): compile an
    all-finite check over loss+grads into the step. A bad step (NaN/inf from
    an overflowed bf16 reduction, a poisoned batch, a flaky chip) passes
    params, optimizer state and BN stats through *unchanged* and zeroes its
    metric contributions; the metrics gain a ``skipped`` flag the host loop
    counts (per-epoch ``skipped_steps``, consecutive-skip abort — see
    docs/FAULT_TOLERANCE.md). The check pieces ride the pmean'd values, so
    every device takes the same branch, and a finite step's selected values
    are bit-identical to an unguarded step's.

    ``qat`` (a `quant.QATModel`, default None): route the forward through
    the fake-quant straight-through-estimator interception — the
    ``QUANT.QAT`` fine-tune mode (quant/qat.py). The step's SPMD structure
    (collectives, guard, donation) is identical; only the traced forward
    changes.

    ``task`` (default ``cfg.TRAIN.TASK``): "classify" (softmax-CE, top-k
    metrics), "mae" (masked pixel reconstruction, `_forward_loss_mae`;
    top-k counters stay zero) or "lm" (next-token cross-entropy,
    `_forward_loss_lm`; top-k stays zero and the model's routing counters
    join the metrics).

    A mesh with a ``seq`` axis (cfg.MESH.SEQ > 1, `parallel/seq.py`) runs
    the model sequence-parallel: the batch replicates along seq (in_specs
    untouched — `fsdp.batch_axes` never includes seq), the model shards the
    token dim internally, and each member's grads are PARTIAL (its token
    shard's contribution) — a single ``psum`` over the seq axis, inserted
    before the data/fsdp reductions, completes them. Loss/metrics arrive
    seq-replicated (the model/loss psum their scalar reductions), so metric
    psums still span only the batch-bearing axes.
    """
    if nonfinite_guard is None:
        nonfinite_guard = cfg.FAULT.NONFINITE_GUARD
    if task is None:
        task = cfg.TRAIN.TASK
    _check_task(task)
    seq_n = seqpar.seq_size(mesh)
    if task != "classify" and qat is not None:
        raise ValueError("QUANT.QAT supports TRAIN.TASK 'classify' only")
    if task == "lm" and seq_n > 1:
        raise ValueError("TRAIN.TASK 'lm' has no sequence-parallel path: MESH.SEQ must be 1")
    rows_key = "tokens" if task == "lm" else "label"
    if fsdp.fsdp_size(mesh) > 1 and state_specs is None:
        # without specs the step would shard the batch over both axes but
        # reduce grads over 'data' only — silent per-fsdp-group divergence
        # (check_vma=False means nothing else trips). Fail at build time.
        raise ValueError(
            "make_train_step: mesh has an fsdp axis but state_specs is None "
            "— pass parallel.fsdp.specs_of(state) (see train_model)"
        )
    use_fsdp = state_specs is not None and fsdp.fsdp_size(mesh) > 1
    fsdp_n = fsdp.fsdp_size(mesh)
    param_specs = state_specs.params if use_fsdp else None
    # grads/BN stats/metrics reduce over every batch-bearing axis: fsdp
    # composes with dp, so the fleet mean spans both
    reduce_axes = ("data", fsdp.FSDP_AXIS) if use_fsdp else "data"
    # metric/guard psums span the batch-bearing devices only — values are
    # already seq-replicated when a seq axis exists
    n_reduce_devices = int(mesh.devices.size) // seq_n

    def grads_one(params, batch_stats, micro, rng):
        def loss_fn(p):
            if use_fsdp:
                # gather INSIDE the differentiated function: the transpose of
                # the tiled all-gather is a psum_scatter, so the grads this
                # returns are already 1/N shards (summed over the fsdp axis)
                p = fsdp.all_gather_params(p, param_specs)
            if task == "mae":
                return _forward_loss_mae(model, p, batch_stats, micro, True, rng, seq_n)
            if task == "lm":
                return _forward_loss_lm(model, p, batch_stats, micro)
            return _forward_loss(model, p, batch_stats, micro, True, rng, qat=qat)

        (loss, (logits, new_stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        return loss, logits, new_stats, grads

    # The name is the compiled module's (``jit_step_scoped``) and part of the
    # persistent compile cache's key, which a scope alone is not (debug info
    # is stripped before hashing, so a step that differs from a cached one in
    # its scopes alone loads the older build's metadata: whoever moves or adds
    # a scope renames the step). benchmark/xplane finds the train step by the
    # ``jit_step`` prefix, and tests/test_trace_phases.py pins it.
    def step_scoped(state: TrainState, batch, lr, rng):
        # distinct dropout stream per device (rng arrives replicated); on a
        # 2-D mesh the fold uses the linearized device index so a (d, f) mesh
        # reproduces the stream of a (d·f,)-device data-parallel mesh
        if use_fsdp:
            dev_idx = (
                jax.lax.axis_index("data") * fsdp_n
                + jax.lax.axis_index(fsdp.FSDP_AXIS)
            )
        else:
            dev_idx = jax.lax.axis_index("data")
        rng = jax.random.fold_in(rng, dev_idx)

        if accum_steps == 1:
            loss, logits, new_stats, grads = grads_one(
                state.params, state.batch_stats, batch, rng
            )
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]),
                batch,
            )

            def body(carry, xs):
                acc_grads, acc_loss, run_stats = carry
                mb, mb_rng = xs
                loss, logits, new_stats, grads = grads_one(
                    state.params, run_stats, mb, mb_rng
                )
                acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
                return (acc_grads, acc_loss + loss, new_stats), logits

            zero_grads = jax.tree.map(jnp.zeros_like, state.params)
            rngs = jax.random.split(rng, accum_steps)
            (sum_grads, sum_loss, new_stats), logits_all = jax.lax.scan(
                body, (zero_grads, jnp.float32(0.0), state.batch_stats), (micro, rngs)
            )
            grads = jax.tree.map(lambda g: g / accum_steps, sum_grads)
            loss = sum_loss / accum_steps
            if task == "lm":  # the counters of the micro-batches, a step's worth
                logits = {k: _COUNTER_REDUCERS[obs.WINDOW_COUNTERS[k]][0](v) for k, v in logits_all.items()}
            else:
                logits = logits_all.reshape(-1, logits_all.shape[-1])
            # Running stats thread through the scan carry, so each micro-batch
            # EMAs them IN ORDER — torch's sequential semantics, exactly (the
            # input stats never enter a train-mode forward, so grads/outputs
            # are unaffected; equality vs the sequential oracle is pinned in
            # tests/test_train_step.py).
        # What follows the gradient is named in the executable's metadata
        # (obs/trace.py STEP_SCOPES), so a device trace can tell the
        # optimizer from the gradient mean from the guard; the model's
        # forward and backward keep the module paths flax gives them. The
        # scopes are metadata only: same equations, same order, same fusions.
        with step_scope("grad_sync"):
            if seq_n > 1:
                # each seq member holds the PARTIAL gradient of its token shard
                # (the model's seq path keeps every parameter use partial —
                # slice-transpose zero-padding, bias-1/P head, psum'd loss
                # sums); the sum over the seq axis is the full gradient. This
                # runs FIRST so the fsdp/data reductions below see seq-complete
                # values, exactly as on a seq-less mesh.
                grads = jax.lax.psum(grads, seqpar.SEQ_AXIS)
            if use_fsdp:
                # sharded leaves arrive as per-shard fsdp-axis SUMS from the
                # gather transpose (÷N makes them means); replicated leaves still
                # differ along fsdp and take an explicit pmean there
                grads = fsdp.average_grads(grads, param_specs, fsdp_n)
            grads = jax.lax.pmean(grads, "data")
            # Running BN stats: averaged across replicas so state stays replicated.
            # (With SYNCBN the normalization stats are already cross-replica; this
            # additionally keeps the *running* estimates identical on every chip —
            # strictly more consistent than DDP's per-rank copies, SURVEY §2b.)
            new_stats = jax.lax.pmean(new_stats, reduce_axes)
        with step_scope("optimizer"):
            updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
            new_params = optim.apply_updates_with_lr(state.params, updates, lr)
        n = jnp.float32(batch[rows_key].shape[0])
        if task in ("mae", "lm"):
            # pixel reconstruction and next-token loss have no top-k; the
            # counters stay zero so the metric schema (and the meters) are
            # task-invariant
            correct = {1: jnp.float32(0.0), topk: jnp.float32(0.0)}
        else:
            with step_scope("metrics"):
                correct = topk_correct(logits, batch["label"], ks=(1, topk))
        if nonfinite_guard:
            with step_scope("guard"):
                # keep is derived from pmean'd values only, so it is identical on
                # every device and the selection below stays replicated. A NaN
                # anywhere on any device poisons the pmean'd grads, so checking
                # the post-collective values catches per-device faults too.
                keep = jnp.isfinite(jax.lax.pmean(loss, reduce_axes))
                local_ok = jnp.bool_(True)
                for g in jax.tree.leaves(grads):
                    local_ok = jnp.logical_and(local_ok, jnp.all(jnp.isfinite(g)))
                if use_fsdp:
                    # grads are per-device SHARDS here, so finiteness is a local
                    # fact — agree across the mesh or devices would diverge on
                    # the select below (the replicated path needs no collective:
                    # its pmean'd grads are identical everywhere already)
                    ok_count = jax.lax.psum(
                        local_ok.astype(jnp.float32), reduce_axes
                    )
                    keep = jnp.logical_and(keep, ok_count == n_reduce_devices)
                else:
                    keep = jnp.logical_and(keep, local_ok)

            def sel(new, old):
                return jnp.where(keep, new, old)

            # the selects are named where the update is: each is the last op
            # on its leaf, and a trace gives a fused update its root's scope
            with step_scope("optimizer"):
                new_params = jax.tree.map(sel, new_params, state.params)
                new_opt_state = jax.tree.map(sel, new_opt_state, state.opt_state)
                new_stats = jax.tree.map(sel, new_stats, state.batch_stats)
        with step_scope("metrics"):
            if nonfinite_guard:
                # a skipped step contributes nothing to the epoch averages (its
                # loss is NaN and NaN logits rank every label "correct")
                zero = jnp.float32(0.0)
                loss_term = jnp.where(keep, loss * n, zero)
                n = jnp.where(keep, n, zero)
                correct = {k: jnp.where(keep, v, zero) for k, v in correct.items()}
            else:
                loss_term = loss * n
            metrics = {
                "loss_sum": jax.lax.psum(loss_term, reduce_axes),
                "n": jax.lax.psum(n, reduce_axes),
                "correct1": jax.lax.psum(correct[1], reduce_axes),
                f"correct{topk}": jax.lax.psum(correct[topk], reduce_axes),
            }
            if nonfinite_guard:
                metrics["skipped"] = 1.0 - keep.astype(jnp.float32)
            if task == "lm":
                # the model's own counters, each reduced over the devices as
                # obs/journal WINDOW_COUNTERS declares it
                for name, value in logits.items():
                    metrics[name] = _COUNTER_REDUCERS[obs.WINDOW_COUNTERS[name]][1](value, reduce_axes)
        return (
            TrainState(params=new_params, batch_stats=new_stats, opt_state=new_opt_state),
            metrics,
        )

    state_in_specs = state_specs if use_fsdp else P()
    sharded = jax.shard_map(
        step_scoped,
        mesh=mesh,
        in_specs=(state_in_specs, P(fsdp.batch_axes(mesh)), P(), P()),
        out_specs=(state_in_specs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_eval_step(model, mesh: Mesh, topk: int, state_specs=None, qat=None,
                   task: str | None = None):
    """Jitted SPMD eval step with weight-masked exact metrics (SURVEY §3.3).

    Takes and returns the running metric totals so accumulation happens
    *inside* the compiled step (one dispatch per batch). ``zero_metrics()``
    builds the initial totals. ``state_specs`` mirrors `make_train_step`:
    fsdp-sharded params are all-gathered per batch for the forward pass.
    ``qat`` mirrors `make_train_step` too: under ``QUANT.QAT`` the eval
    forward is fake-quantized, so validation accuracy measures what the
    quantized serve path will deliver. ``task`` "mae" evaluates masked pixel
    reconstruction under a FIXED mask key (deterministic across runs and
    topologies); "loss" is the weighted mean masked-MSE, top-k stays zero.
    """
    if fsdp.fsdp_size(mesh) > 1 and state_specs is None:
        raise ValueError(
            "make_eval_step: mesh has an fsdp axis but state_specs is None "
            "— pass parallel.fsdp.specs_of(state) (see train_model)"
        )
    use_fsdp = state_specs is not None and fsdp.fsdp_size(mesh) > 1
    reduce_axes = ("data", fsdp.FSDP_AXIS) if use_fsdp else "data"
    if task is None:
        task = cfg.TRAIN.TASK
    seq_n = seqpar.seq_size(mesh)

    # named apart from the train step (``jit_eval_step``): a device trace
    # finds the train step by its ``jit_step`` prefix and must not find this
    def eval_step(state: TrainState, batch, totals):
        params = state.params
        if use_fsdp:
            params = fsdp.all_gather_params(params, state_specs.params)
        if task == "lm":
            loss, _ = _forward_loss_lm(model, params, state.batch_stats, batch)
            n_local = jnp.float32(batch["tokens"].shape[0])
            m = {
                "loss_sum": jax.lax.psum(loss * n_local, reduce_axes),
                "n": jax.lax.psum(n_local, reduce_axes),
                "correct1": jnp.float32(0.0),
                f"correct{topk}": jnp.float32(0.0),
            }
            return jax.tree.map(jnp.add, totals, m)
        w = batch["weight"]
        if task == "mae":
            # same mask for every batch/run: eval is a fixed, comparable
            # yardstick, not a sampled estimate that drifts between epochs
            eval_rng = jax.random.PRNGKey(cfg.RNG_SEED or 0)
            loss, _ = _forward_loss_mae(
                model, params, state.batch_stats, batch, False, eval_rng, seq_n,
                sample_weights=w,
            )
            n_local = jnp.sum(w)
            m = {
                "loss_sum": jax.lax.psum(loss * n_local, reduce_axes),
                "n": jax.lax.psum(n_local, reduce_axes),
                "correct1": jnp.float32(0.0),
                f"correct{topk}": jnp.float32(0.0),
            }
            return jax.tree.map(jnp.add, totals, m)
        apply = model.apply if qat is None else functools.partial(qat.apply, model)
        logits = apply(
            {"params": params, "batch_stats": state.batch_stats},
            device_normalize(batch["image"]),
            train=False,
        )
        logits32 = logits.astype(jnp.float32)
        nll = per_example_nll(logits32, batch["label"])
        correct = topk_correct_weighted(logits32, batch["label"], w, ks=(1, topk))
        m = {
            "loss_sum": jax.lax.psum(jnp.sum(nll * w), reduce_axes),
            "n": jax.lax.psum(jnp.sum(w), reduce_axes),
            "correct1": jax.lax.psum(correct[1], reduce_axes),
            f"correct{topk}": jax.lax.psum(correct[topk], reduce_axes),
        }
        return jax.tree.map(jnp.add, totals, m)

    state_in_specs = state_specs if use_fsdp else P()
    sharded = jax.shard_map(
        eval_step, mesh=mesh, in_specs=(state_in_specs, P(fsdp.batch_axes(mesh)), P()),
        out_specs=P(), check_vma=False,
    )
    # NB: totals is NOT donated — the buffers are 4 scalars, and donating a
    # replicated shard_map input deadlocked the XLA:CPU collective rendezvous.
    return jax.jit(sharded)


def zero_metrics(topk: int, mesh: Mesh):
    """Zeroed running totals, replicated over the mesh up front so the first
    eval step needs no implicit resharding. (Deliberately NOT donated — see
    the NB in make_eval_step.)"""
    z = jnp.zeros((), jnp.float32)
    totals = {"loss_sum": z, "n": z, "correct1": z, f"correct{topk}": z}
    return jax.device_put(totals, NamedSharding(mesh, P()))


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def create_train_state(model, key, mesh: Mesh, im_size: int):
    """Init the train state on device.

    On a 1-D data mesh the state is replicated across the mesh (the original
    contract). On a ``('data', 'fsdp')`` mesh (cfg.MESH.FSDP > 1) params and
    optimizer state are initialized DIRECTLY into their 1/N fsdp shards —
    ``out_shardings`` on the jitted init means XLA SPMD materializes each
    device's slice only, so even the first instant of a run never holds a
    replicated copy of state that doesn't fit replicated. The partition
    rules (`parallel/fsdp.py`) are priced on abstract shapes via
    `jax.eval_shape` before anything is allocated.
    """
    fsdp_n = fsdp.fsdp_size(mesh)
    init_model = model
    if getattr(model, "seq_axis", None) is not None:
        # init runs OUTSIDE shard_map (no seq axis bound), and the seq path
        # only reroutes activations — the parameter inventory is identical —
        # so a seq-less clone initializes the exact same model
        init_model = model.clone(seq_axis=None)

    def model_init(key):
        # a model that is not fed images says what it is initialised with
        dummy = getattr(init_model, "dummy_input", None)
        x = dummy(im_size) if dummy else jnp.zeros((1, im_size, im_size, 3), jnp.float32)
        variables = init_model.init(key, x, train=False)
        return variables["params"], variables.get("batch_stats", {})

    # fsdp_n derives from cfg.MESH (identical on every host), so the two
    # branches below are entered uniformly fleet-wide; the collective
    # difference DT101 sees (LAMB's fsdp-axis psum exists only in the
    # sharded optimizer) can never disagree between participants.
    if fsdp_n > 1:  # dtpu-lint: disable=DT101
        abs_params, _ = jax.eval_shape(model_init, key)
        param_specs = fsdp.tree_specs(abs_params, fsdp_n)
        # the optimizer update runs on the shard; LAMB's trust ratio needs
        # the specs to psum its norms over the fsdp axis
        tx = optim.construct_optimizer(
            param_specs=param_specs, fsdp_axis=fsdp.FSDP_AXIS
        )
    else:
        tx = optim.construct_optimizer()

    def init_fn(key):
        params, batch_stats = model_init(key)
        return TrainState(
            params=params, batch_stats=batch_stats, opt_state=tx.init(params)
        )

    if fsdp_n > 1:
        abs_state = jax.eval_shape(init_fn, key)
        specs = fsdp.train_state_specs(abs_state, mesh)
        c = fsdp.census(abs_state.params, specs.params)
        c_opt = fsdp.census(abs_state.opt_state, specs.opt_state)
        logger.info(
            f"fsdp={fsdp_n}: params {c['sharded_leaves']} leaves/"
            f"{c['sharded_bytes'] / 1e6:.1f} MB sharded, "
            f"{c['replicated_leaves']} leaves/"
            f"{c['replicated_bytes'] / 1e6:.1f} MB replicated; opt state "
            f"{c_opt['sharded_bytes'] / 1e6:.1f} MB sharded/"
            f"{c_opt['replicated_bytes'] / 1e6:.1f} MB replicated"
        )
        out_shardings = fsdp.shardings(specs, mesh)
    else:
        out_shardings = NamedSharding(mesh, P())
    # jit-then-call is deliberate here: init runs once per (model, mesh,
    # im_size) and a keyed cache would pin every model ever constructed.
    # Partitionable threefry for the init only: legacy (non-partitionable)
    # threefry bits are partitioning-DEPENDENT under SPMD, so the same seed
    # on a ('data','fsdp') mesh would initialize a different model than on a
    # 1-D mesh — the sharded-init path must be the same model at every
    # topology (the dp-oracle and elastic contracts both assume it).
    prev_prng = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        state = jax.jit(init_fn, out_shardings=out_shardings)(key)  # dtpu-lint: disable=DT003
    finally:
        jax.config.update("jax_threefry_partitionable", prev_prng)
    return state, tx


def _import_arch_modules() -> None:
    """Import MODEL.MODULE so out-of-tree archs self-register (the explicit
    analog of the reference's timm fallback, `trainer.py:117-128`). External
    factories must accept the `build_model` kwargs: ``num_classes``,
    ``dtype``, ``bn_axis_name``, ``remat`` (and ``stem_s2d`` when opted in;
    under TRAIN.TASK "lm" the LM section's keys in lower case).
    """
    for mod in filter(None, (m.strip() for m in cfg.MODEL.MODULE.split(","))):
        try:
            importlib.import_module(mod)
        except ImportError as exc:
            raise ImportError(
                f"MODEL.MODULE {mod!r} failed to import ({exc}). It must be "
                f"an importable module that registers archs via "
                f"distribuuuu_tpu.models.register_model."
            ) from exc


def _build_cfg_model():
    from distribuuuu_tpu.models.layers import set_bn_compute_dtype

    _import_arch_modules()
    _check_task(cfg.TRAIN.TASK)
    if cfg.MODEL.DTYPE not in ("float32", "bfloat16"):
        raise ValueError(
            f"MODEL.DTYPE must be 'float32' or 'bfloat16', got {cfg.MODEL.DTYPE!r}"
        )
    if cfg.MODEL.BN_DTYPE not in ("auto", "float32", "bfloat16"):
        # a typo ('bf16', 'float16') must not silently select float32
        # boundaries — that would measure/train the wrong A/B arm
        raise ValueError(
            f"MODEL.BN_DTYPE must be 'auto', 'float32' or 'bfloat16', "
            f"got {cfg.MODEL.BN_DTYPE!r}"
        )
    bn_dtype = cfg.MODEL.BN_DTYPE
    if bn_dtype == "auto":
        bn_dtype = cfg.MODEL.DTYPE
    set_bn_compute_dtype(jnp.bfloat16 if bn_dtype == "bfloat16" else jnp.float32)
    # the fused-epilogue route (ops/epilogue.py): like the BN boundary dtype a
    # process-global read at trace time, scoped to the run by
    # _model_globals_scoped
    from distribuuuu_tpu.ops.epilogue import set_fused_epilogue_default

    set_fused_epilogue_default(cfg.MODEL.FUSED_EPILOGUE)
    # SYNCBN spans every batch-bearing axis: on a ('data', 'fsdp') mesh the
    # batch shards over both, so stats pmean over the pair — a pure-dp run
    # and an fsdp run of the same device count normalize identically
    bn_axis = None
    if cfg.MODEL.SYNCBN:
        bn_axis = "data" if cfg.MESH.FSDP in (0, 1) else ("data", fsdp.FSDP_AXIS)
    kwargs = {}
    if cfg.MODEL.STEM_S2D:  # resnet/botnet-family option; loud TypeError elsewhere
        kwargs["stem_s2d"] = True
    if cfg.MODEL.SEQ_ATTN not in ("none", "ring", "ulysses"):
        raise ValueError(
            f"MODEL.SEQ_ATTN must be 'none', 'ring' or 'ulysses', "
            f"got {cfg.MODEL.SEQ_ATTN!r}"
        )
    if cfg.MESH.SEQ > 1:
        if cfg.MODEL.SEQ_ATTN == "none":
            # sharded tokens with dense per-shard attention would silently
            # attend within shards only — wrong math, so refuse at build
            raise ValueError(
                "MESH.SEQ > 1 needs MODEL.SEQ_ATTN 'ring' or 'ulysses' to "
                "stitch the attention contraction across token shards"
            )
        kwargs["seq_axis"] = seqpar.SEQ_AXIS
        kwargs["seq_impl"] = cfg.MODEL.SEQ_ATTN
        if cfg.TRAIN.TASK == "classify" and cfg.MODEL.ARCH.startswith("vit_"):
            # the class token has no home shard; gap pooling is the
            # seq-compatible representation (models/vit.py)
            kwargs["pool"] = "gap"
    if cfg.TRAIN.TASK == "lm":
        if cfg.MESH.SEQ > 1 or cfg.MESH.FSDP not in (0, 1):
            raise ValueError("TRAIN.TASK 'lm' runs data-parallel only: MESH.SEQ and MESH.FSDP must be 1")
        # a token model's factory takes the LM section, key by key in lower
        # case; it lives in the module MODEL.MODULE names, so an image run
        # neither imports nor builds any of it
        kwargs.update({key.lower(): value for key, value in cfg.LM.items()})
    if cfg.TRAIN.TASK == "mae":
        if not cfg.MODEL.ARCH.startswith("mae_"):
            raise ValueError(
                f"TRAIN.TASK 'mae' needs a pixel-decoder arch (mae_*), "
                f"got MODEL.ARCH {cfg.MODEL.ARCH!r}"
            )
        kwargs["decoder_dim"] = cfg.MODEL.MAE_DECODER_DIM
    elif cfg.MODEL.ARCH.startswith("mae_"):
        # the converse hole: an MAE model emits pixels, which softmax-CE
        # would crash into deep inside metrics — refuse with the story here
        raise ValueError(
            f"MODEL.ARCH {cfg.MODEL.ARCH!r} emits pixel reconstructions, "
            f"not class logits: set TRAIN.TASK 'mae'"
        )
    return build_model(
        cfg.MODEL.ARCH,
        num_classes=cfg.MODEL.NUM_CLASSES,
        dtype=jnp.bfloat16 if cfg.MODEL.DTYPE == "bfloat16" else jnp.float32,
        bn_axis_name=bn_axis,
        remat=cfg.MODEL.REMAT,
        **kwargs,
    )


def _pretrained_path() -> str:
    """Resolve MODEL.PRETRAINED=True to a local converted checkpoint.

    The reference downloads torchvision weights via torch.hub
    (`models/utils.py:1-4`, URLs `resnet.py:23-33`); TPU pods are typically
    egress-restricted, so here pretrained weights are provisioned once with
    the converter and found under ``$DTPU_PRETRAINED_DIR`` (default
    ``~/.cache/distribuuuu_tpu/pretrained/<arch>``).
    """
    root = os.environ.get(
        "DTPU_PRETRAINED_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "distribuuuu_tpu", "pretrained"),
    )
    path = os.path.join(root, cfg.MODEL.ARCH)
    if not os.path.isdir(path):
        raise FileNotFoundError(
            f"MODEL.PRETRAINED=True but no converted weights at {path}. "
            f"Provision once with: python scripts/convert_torch.py --arch "
            f"{cfg.MODEL.ARCH} --src <torchvision .pth> --dst {path}"
        )
    return path


# ---------------------------------------------------------------------------
# Epoch loops (reference `train_epoch`/`validate`, `trainer.py:14-103`)
# ---------------------------------------------------------------------------

def train_epoch(
    loader, mesh, train_step, state, epoch: int, rng, is_primary: bool,
    start_epoch: int = 0, run_tic: float | None = None,
    start_step: int = 0, best_acc1: float = 0.0, injector=None,
    fleet_poller=None,
):
    lr = optim.get_epoch_lr(epoch)
    if is_primary:
        logger.info(f"Epoch[{epoch}] current learning rate: {lr:.6f}")
    if start_step:
        # mid-epoch resume: fast-forward past already-consumed batches at
        # the index level (the loader never decodes the skipped samples)
        loader.set_epoch(epoch, start_batch=start_step)
        if is_primary:
            logger.info(
                f"Epoch[{epoch}] resuming mid-epoch at step {start_step}/{len(loader)}"
            )
    else:
        loader.set_epoch(epoch)
    lr_arr = jnp.asarray(lr, jnp.float32)
    topk = cfg.TRAIN.TOPK
    batch_time, data_time, losses, top1, topk_m, progress = construct_meters(
        len(loader), prefix=f"Epoch[{epoch}] ", topk=topk
    )
    # whole-run ETA across remaining epochs (reference cal_eta, utils.py:246-252)
    progress.configure_run_eta(
        tic=run_tic if run_tic is not None else time.time(),
        cur_epoch=epoch,
        start_epoch=start_epoch,
        max_epoch=cfg.OPTIM.MAX_EPOCH,
    )

    tel = obs.current()
    tel.epoch_start(epoch)
    # profiler windows (OBS.PROFILE_AT_STEPS / SIGUSR1 / legacy TRAIN.PROFILE)
    # are primary-only, like the journal they report into; from_cfg applies
    # the OBS.ENABLED gating (legacy TRAIN.PROFILE stays independent of it)
    prof = obs.ProfilerWindows.from_cfg(epoch, telemetry=tel) if is_primary else None
    # per optimizer step the fleet consumes this many samples — sized by the
    # BATCH-BEARING mesh devices (a submesh run leaves the other chips idle;
    # a seq group of P devices cooperates on one batch shard, so seq never
    # multiplies the sample count)
    step_imgs = (
        cfg.TRAIN.BATCH_SIZE * cfg.TRAIN.ACCUM_STEPS * seqpar.batch_device_count(mesh)
    )
    steps_per_epoch = len(loader)
    max_consec = cfg.FAULT.MAX_CONSECUTIVE_SKIPS
    epoch_skipped = 0
    consec_skipped = 0
    first_window = True
    window: list = []
    epoch_start = time.time()
    t_end = epoch_start
    t_window = epoch_start
    for it, batch in enumerate(
        prefetch_to_device(loader, mesh, cfg.TRAIN.PREFETCH), start=start_step
    ):
        data_time.update(time.time() - t_end)
        gstep = epoch * steps_per_epoch + it
        # step-progress heartbeat: the armed watchdog turns a wedged step
        # (dead peer in a collective) into a bounded-time loud failure
        resilience.watchdog_beat(gstep)
        if injector is not None and injector.should_kill(gstep):
            injector.kill_now()  # SIGKILL self: hard rank death, no cleanup
        if injector is not None and injector.should_hang(gstep):
            injector.hang_now()  # stall forever: the watchdog's prey
        if injector is not None and injector.should_preempt(gstep):
            # injection keys off gstep, identical on every host — safe to
            # stop without the multi-host agreement below
            resilience.request_preemption(f"injected at global step {gstep}")
            stop_here = True
        elif fleet_poller is not None and (fleet_kind := fleet_poller.check(gstep)):
            # fleet cooperative stop (resize / queue preemption): the agreed
            # stop step IS the multi-host agreement — every rank reads the
            # same published step and stops at the same boundary
            resilience.request_preemption(f"fleet {fleet_kind} at global step {gstep}")
            stop_here = True
        else:
            # multi-host: stop only when every host agrees on this step
            # boundary (a lone host leaving would strand the rest in their
            # next collective until the preemption deadline kills the job)
            stop_here = resilience.preemption_stop_requested(gstep)
        if stop_here:
            # state reflects exactly `it` consumed batches of this epoch;
            # commit it (with step + RNG + the fleet sample offset, so an
            # elastic relaunch can remap the position) before giving the
            # slice back
            path = ckpt.save_mid_checkpoint(
                cfg.OUT_DIR, epoch, it, state, best_acc1, rng,
                samples_per_step=step_imgs,
            )
            try:  # drain older async epoch saves; the emergency save above
                ckpt.wait_for_saves()  # is already durable (synchronous), so
            except Exception as exc:  # a failure here must not eat Preempted
                logger.error(f"async save wait during preemption failed: {exc!r}")
            resilience.RUN_STATS.preempted_at = (epoch, it)
            tel.event("preempt", epoch=epoch, step=it, path=path)
            tel.commit()  # durable now — the hard deadline may SIGKILL us
            logger.warning(
                f"Preempted at epoch {epoch} step {it}: emergency checkpoint "
                f"{path} committed; exiting"
            )
            raise resilience.Preempted(f"preempted at epoch {epoch} step {it}")
        if injector is not None and injector.is_nan_step(gstep):
            batch = resilience.poison_batch_nan(batch)
            if is_primary:
                logger.warning(f"FAULT INJECTION: NaN batch at global step {gstep}")
        # The step's first launches (the per-step key's two fold_ins). The
        # runtime lets the host run only a few steps ahead of the device and
        # blocks it inside whichever launch comes first once its queue is
        # full: here. So this phase holds the device's back-pressure on the
        # loop, and ``dispatch`` below the host's own cost of launching the
        # step (obs/trace.py phases).
        with phase("throttle", gstep=gstep):
            # two-level fold: no collisions however long the epoch runs
            step_rng = jax.random.fold_in(jax.random.fold_in(rng, epoch), it)
        if tel.wants_step_cost:
            # one-shot analytical step pricing for MFU: LOWERS the jitted
            # step (tracing only — no compile, CompileGuard stays exact)
            tel.capture_step_cost(train_step, state, batch, lr_arr, step_rng)
        if prof is not None:
            prof.maybe_start(gstep)
        with phase("dispatch", step_num=gstep):
            state, m = train_step(state, batch, lr_arr, step_rng)
        window.append(m)
        if prof is not None:
            prof.after_step(gstep, window)
        if it % cfg.TRAIN.PRINT_FREQ == 0 or it == len(loader) - 1:
            # device_get is the sync point (block_until_ready is unreliable on
            # some transports); fetch BEFORE timestamping the window
            with phase("fetch_wait", gstep=gstep):
                vals = jax.device_get(window)
            now = time.time()
            win_wall = now - t_window
            win_steps = len(window)
            was_warmup = first_window
            if first_window:
                # first window = compile + autotune: show it as .val but keep
                # it out of the running Time average (honest steady-state avg)
                batch_time.val = win_wall / win_steps
                first_window = False
            else:
                batch_time.update(win_wall / win_steps, n=win_steps)
            t_window = now
            # non-finite-guard accounting: per-epoch skipped_steps plus an
            # abort when skips run back-to-back (divergence, not a blip)
            win_skipped = 0
            for v in vals:
                if v.get("skipped", 0.0) >= 0.5:
                    win_skipped += 1
                    consec_skipped += 1
                    if consec_skipped >= max_consec:
                        tel.event(
                            "fault_abort", epoch=epoch, step=it,
                            consecutive=consec_skipped,
                        )
                        tel.commit()
                        raise resilience.NonFiniteDivergence(
                            f"{consec_skipped} consecutive non-finite steps at "
                            f"epoch {epoch} step {it} — aborting (loss/grads "
                            f"are NaN/inf every step; FAULT.MAX_CONSECUTIVE_"
                            f"SKIPS={max_consec})"
                        )
                else:
                    consec_skipped = 0
            epoch_skipped += win_skipped
            n = sum(v["n"] for v in vals)
            win_loss = win_acc1 = win_acck = None
            if n > 0:  # a window of all-skipped steps has nothing to average
                win_loss = float(sum(v["loss_sum"] for v in vals) / n)
                win_acc1 = float(100.0 * sum(v["correct1"] for v in vals) / n)
                win_acck = float(100.0 * sum(v[f"correct{topk}"] for v in vals) / n)
                losses.update(win_loss, n=int(n))
                top1.update(win_acc1, n=int(n))
                topk_m.update(win_acck, n=int(n))
            # the step's own counters beside its loss (a token model's
            # routing): the window's mean step, or its worst
            counters = {
                name: float(_COUNTER_REDUCERS[how][2](float(v[name]) for v in vals))
                for name, how in obs.WINDOW_COUNTERS.items() if name in vals[0]
            }
            window.clear()
            # journal the window from the values fetched above — telemetry
            # adds no sync of its own (docs/OBSERVABILITY.md)
            tel.window(
                counters=counters,
                epoch=epoch, step=it, gstep=gstep, steps=win_steps,
                skipped=win_skipped, lr=lr, wall_s=win_wall,
                data_time=data_time.avg, imgs=win_steps * step_imgs,
                warmup=was_warmup, loss=win_loss, acc1=win_acc1, acck=win_acck,
            )
            if is_primary:
                progress.display(it)
        t_end = time.time()
    if prof is not None:  # epoch ended inside a capture window (short epoch)
        prof.finish(window)
    resilience.RUN_STATS.skipped_steps[epoch] = epoch_skipped
    if epoch_skipped and is_primary:
        logger.warning(
            f"Epoch[{epoch}] skipped_steps: {epoch_skipped} non-finite step(s) "
            f"left params/optimizer state untouched"
        )
    steps_run = len(loader) - start_step
    wall = time.time() - epoch_start
    if steps_run > 0 and wall > 0:
        imgs = step_imgs * steps_run
        if is_primary:
            logger.info(
                f"Epoch[{epoch}] done: {wall:.1f}s, {imgs / wall:.0f} img/s "
                f"({imgs / wall / jax.device_count():.0f}/chip)"
            )
        tel.epoch_end(
            epoch=epoch, steps=steps_run, skipped=epoch_skipped,
            wall_s=wall, imgs=imgs,
        )
    return state


def validate(
    loader, mesh, eval_step, state, is_primary: bool, print_freq=None,
    prefix="Test: ", epoch: int | None = None,
):
    topk = cfg.TRAIN.TOPK
    print_freq = print_freq or cfg.TEST.PRINT_FREQ
    eval_tic = time.time()
    batch_time, data_time, losses, top1, topk_m, progress = construct_meters(
        len(loader), prefix=prefix, topk=topk
    )
    totals = zero_metrics(topk, mesh)
    t_end = time.time()
    t_window = t_end
    window_n = 0
    vals = None  # last boundary fetch; the final iteration is always a boundary
    for it, batch in enumerate(prefetch_to_device(loader, mesh, cfg.TRAIN.PREFETCH)):
        data_time.update(time.time() - t_end)
        resilience.watchdog_beat(it, phase="eval")
        totals = eval_step(state, batch, totals)
        window_n += 1
        # Boundary fetches exist to feed the progress display, so only the
        # displaying rank pays them; other ranks run fetch-free (their single
        # sync point is the final-totals fetch after the loop).
        if is_primary and (it % print_freq == 0 or it == len(loader) - 1):
            vals = jax.device_get(totals)  # sync point
            # charge the whole window's wall time across its steps so the
            # Time average is true step time, not just print-boundary steps
            now = time.time()
            if it == 0:
                # compile window: display-only, excluded from the average
                batch_time.val = (now - t_window) / window_n
            else:
                batch_time.update((now - t_window) / window_n, n=window_n)
            t_window = now
            window_n = 0
            n = max(vals["n"], 1.0)
            losses.avg = float(vals["loss_sum"] / n)
            losses.val = losses.avg
            top1.avg = float(100.0 * vals["correct1"] / n)
            top1.val = top1.avg
            topk_m.avg = float(100.0 * vals[f"correct{topk}"] / n)
            topk_m.val = topk_m.avg
            progress.display(it)
        t_end = time.time()
    if vals is None:  # non-primary rank, or empty loader
        vals = jax.device_get(totals)
    n = max(vals["n"], 1.0)
    acc1 = float(100.0 * vals["correct1"] / n)
    acck = float(100.0 * vals[f"correct{topk}"] / n)
    if is_primary:
        logger.info(f" * Acc@1 {acc1:.3f} Acc@{topk} {acck:.3f}")
    obs.current().event(
        "eval", epoch=epoch, acc1=acc1, acck=acck,
        loss=float(vals["loss_sum"] / n), wall_s=round(time.time() - eval_tic, 3),
        samples=float(vals["n"]),
    )
    return acc1, acck


# ---------------------------------------------------------------------------
# Top-level entry points (reference `train_model`/`test_model`)
# ---------------------------------------------------------------------------

def _enable_compile_cache() -> None:
    """Point jax at the persistent compile cache (cfg.TRAIN.COMPILE_CACHE,
    default on): identical programs compile once per machine, so a
    dtpu-agent supervised restart (or any relaunch) resumes without paying
    the full step compile again. Hit/miss counts ride the existing obs
    compile counters (``/jax/compilation_cache/*`` in ``counters`` records)."""
    if not cfg.TRAIN.COMPILE_CACHE:
        return
    from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache(cfg.TRAIN.COMPILE_CACHE_DIR or None)
    logger.info(f"persistent XLA compile cache: {cache_dir}")


def _journal_state_bytes(state, mesh: Mesh) -> None:
    """Typed per-device state-bytes record: the measured half of the fsdp
    1/N claim (obs/memory.py). Epoch-boundary-grade host work, no sync."""
    try:
        obs.current().event(
            "state_bytes", **obs.state_bytes(state, fsdp=fsdp.fsdp_size(mesh))
        )
    except Exception as exc:  # observability must never kill the run
        logger.warning(f"state-bytes snapshot failed: {exc!r}")


def _journal_activation_bytes(model, mesh: Mesh) -> None:
    """Typed per-device activation-byte census: the seq-axis twin of
    `_journal_state_bytes` — the priced 1/seq claim (obs/memory.py
    ``activation_bytes``; the allocator's `memory` snapshots are the
    on-chip measured complement). Transformer archs only (the census needs
    token geometry); silently skipped elsewhere."""
    patch = getattr(model, "patch", None)
    dim = getattr(model, "dim", None)
    depth = getattr(model, "depth", None)
    mlp_dim = getattr(model, "mlp_dim", None)
    if None in (patch, dim, depth, mlp_dim):
        return
    l_global = (cfg.TRAIN.IM_SIZE // patch) ** 2
    if getattr(model, "pool", None) == "token":
        l_global += 1  # the class token rides the stream
    try:
        obs.current().event(
            "activation_bytes",
            **obs.activation_bytes(
                batch_per_device=cfg.TRAIN.BATCH_SIZE,
                l_global=l_global,
                seq=seqpar.seq_size(mesh),
                dim=dim,
                depth=depth,
                mlp_dim=mlp_dim,
                dtype_bytes=2 if cfg.MODEL.DTYPE == "bfloat16" else 4,
            ),
        )
    except Exception as exc:  # observability must never kill the run
        logger.warning(f"activation-bytes census failed: {exc!r}")


def _build_qat(model, state, mesh: Mesh):
    """Calibrate the ``QUANT.QAT`` fake-quant sites on the run's weights.

    Runs `quant.calibrate_qat` (the PTQ calibration pass) eagerly over
    ``QUANT.CALIB_BATCHES`` seeded standard-normal batches — the
    `convert.golden_inputs` family, i.e. post-normalization scale, matching
    what `device_normalize`'d training batches look like — and journals a
    typed ``qat`` record so the fine-tune's provenance (mode, site count,
    distill weight) rides the run's telemetry.
    """
    import numpy as np

    from distribuuuu_tpu import quant

    try:
        # the canonical validator (one source for the valid-grid rule);
        # re-raised with the cfg knob named so the fix is obvious
        quant.qat._check_mode(cfg.QUANT.QAT_MODE)
    except ValueError as exc:
        raise ValueError(f"QUANT.QAT_MODE: {exc}") from None
    if fsdp.fsdp_size(mesh) > 1:
        # calibration runs eager forwards on the committed params; fsdp
        # shards would need a host-side all-gather first. QAT is a
        # fine-tune mode — run it on a data-parallel mesh.
        raise ValueError(
            "QUANT.QAT requires MESH.FSDP 1: the calibration pass runs on "
            "the unsharded weights (fine-tune the model data-parallel)"
        )
    if seqpar.seq_size(mesh) > 1 or cfg.TRAIN.TASK == "mae":
        # the eager calibration forward has no seq group to stitch ring
        # attention across, and the quant serve grid targets classifiers
        raise ValueError(
            "QUANT.QAT requires MESH.SEQ 1 and TRAIN.TASK 'classify' "
            "(fine-tune the classifier data-parallel)"
        )
    tic = time.time()
    rng = np.random.default_rng(cfg.QUANT.CALIB_SEED)
    shape = (cfg.QUANT.CALIB_BATCH_SIZE, cfg.TRAIN.IM_SIZE, cfg.TRAIN.IM_SIZE, 3)
    batches = [
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for _ in range(cfg.QUANT.CALIB_BATCHES)
    ]
    def _host_local(a):
        # eager calibration forwards refuse pod-global arrays (committed to
        # a multi-host mesh they are not fully addressable per process);
        # pure DP replicates params on every device, so the first
        # addressable shard IS the full value — the fsdp refusal above
        # guarantees no leaf is actually sharded
        if hasattr(a, "addressable_data"):
            return np.asarray(a.addressable_data(0))
        return np.asarray(a)

    variables = jax.tree.map(
        _host_local, {"params": state.params, "batch_stats": state.batch_stats}
    )
    qat_model = quant.calibrate_qat(
        model, variables, batches, mode=cfg.QUANT.QAT_MODE
    )
    wall = time.time() - tic
    obs.current().event(
        "qat",
        mode=cfg.QUANT.QAT_MODE,
        layers=qat_model.n_sites,
        calib_batches=cfg.QUANT.CALIB_BATCHES,
        distill=float(cfg.QUANT.QAT_DISTILL),
        wall_s=round(wall, 3),
        im_size=cfg.TRAIN.IM_SIZE,
    )
    logger.info(
        f"QUANT.QAT: {cfg.QUANT.QAT_MODE} fake-quant fine-tune over "
        f"{qat_model.n_sites} conv/dense site(s) (calibrated in {wall:.2f}s, "
        f"distill weight {cfg.QUANT.QAT_DISTILL})"
    )
    return qat_model


def _model_globals_scoped(fn):
    """Restore the process-global model-trace knobs on return: a run with
    MODEL.BN_DTYPE=bfloat16 or MODEL.FUSED_EPILOGUE=True must not silently
    change what a later *direct* build_model() call in the same process
    traces with."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from distribuuuu_tpu.models import layers
        from distribuuuu_tpu.ops import epilogue

        prev = layers.get_bn_compute_dtype()
        prev_fused = epilogue.get_fused_epilogue_default()
        try:
            return fn(*args, **kwargs)
        finally:
            layers.set_bn_compute_dtype(prev)
            epilogue.set_fused_epilogue_default(prev_fused)

    return wrapper


# back-compat alias (tests decorate helpers with it)
_bn_dtype_scoped = _model_globals_scoped


@functools.lru_cache(maxsize=None)
def _recommit_fn(mesh: Mesh, spec_treedef=None, spec_leaves=None):
    """Jitted sharding-preserving copy, cached per (mesh, spec tree): binding
    the callable once keeps the compile cache keyed on a stable function
    object (a fresh ``jax.jit(lambda ...)`` per call retraces every call —
    DT003; this was dtpu-lint's first real catch, regression-pinned in
    tests/test_analysis.py). Meshes, treedefs and PartitionSpec tuples are
    hashable and O(1)-few per process, so the cache is bounded."""
    if spec_treedef is None:
        out_shardings = NamedSharding(mesh, P())
    else:
        out_shardings = jax.tree_util.tree_unflatten(
            spec_treedef, [NamedSharding(mesh, s) for s in spec_leaves]
        )
    return jax.jit(lambda s: jax.tree.map(jnp.copy, s), out_shardings=out_shardings)


def _recommit_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Launder restored checkpoint arrays through a jitted copy.

    Orbax hands back host-resident array layouts (``memory_kind=
    unpinned_host`` on some runtimes); feeding those straight into the
    donated train step crashes XLA:CPU on its second invocation. The jitted
    copy re-materializes the state exactly as `create_train_state` does —
    same sharding (replicated, or the fsdp partition the restore targeted),
    device-committed buffers — so donation behaves identically to the
    fresh-init path. Values are copied bit-exactly; the copy is
    sharding-PRESERVING, never a re-replication (an fsdp state must not be
    blown back up to a full per-chip copy by its own resume path).
    """
    specs = fsdp.specs_of(state)
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    if all(s == P() for s in leaves):
        return _recommit_fn(mesh)(state)  # replicated: the original path
    return _recommit_fn(mesh, treedef, tuple(leaves))(state)


@_model_globals_scoped
def train_model():
    """Full training run (reference `trainer.py:106-173`).

    Returns ``(final_state, best_acc1)``.
    """
    configure_determinism(cfg.CUDNN.DETERMINISTIC)  # before first backend use
    _enable_compile_cache()
    info = setup_distributed()
    key = setup_seed(cfg.RNG_SEED, info.process_index)
    if info.is_primary:
        dump_cfg()
    setup_logger(
        cfg.OUT_DIR,
        info.process_index,
        journal_path=obs.journal_path(cfg.OUT_DIR) if cfg.OBS.ENABLED else None,
    )
    resilience.reset_run_stats()
    # a stale flag from an earlier preempted run in this process must not
    # immediately re-preempt the relaunch
    resilience.clear_preemption()
    if cfg.FAULT.HANDLE_SIGNALS:
        resilience.install_preemption_handler()
    # telemetry opens before any compile so the monitoring bridge sees the
    # init/step compiles too; non-primary processes get the no-op handle
    obs.start_run(cfg.OUT_DIR, is_primary=info.is_primary)
    if cfg.OBS.ENABLED and cfg.OBS.PROFILE_SIGUSR1 and info.is_primary:
        obs.install_sigusr1_handler()
    injector = resilience.FaultInjector()
    if injector.active:
        logger.warning(
            f"FAULT INJECTION active: io_indices={sorted(injector.io_indices)} "
            f"(failures={injector.io_failures}), nan_steps="
            f"{sorted(injector.nan_steps)}, preempt_step={injector.preempt_step}"
        )
    # fleet-managed runs (dtpu-fleet, env DTPU_FLEET_SIGNALS): poll the
    # controller's cooperative-stop files at step boundaries. The stop-step
    # margin must exceed the worst host-loop drift between ranks: hosts sync
    # at every PRINT_FREQ device_get and dispatch at most PREFETCH batches
    # ahead, so PRINT_FREQ + 2*PREFETCH + a safety pad covers it.
    fleet_poller = resilience.FleetSignalPoller.from_env(
        is_primary=info.is_primary,
        margin_steps=cfg.TRAIN.PRINT_FREQ + 2 * cfg.TRAIN.PREFETCH + 4,
    )
    if fleet_poller is not None:
        logger.info(
            f"Fleet-managed run: gang epoch {fleet_poller.fleet_epoch}, "
            f"cooperative-stop signals at {fleet_poller.signals_dir}"
        )
    mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
    # fleet-wide samples one optimizer step consumes — the unit elastic
    # resume remaps checkpointed sample offsets with (seq devices share
    # their group's batch shard, so they don't multiply it)
    samples_per_step = (
        cfg.TRAIN.BATCH_SIZE * cfg.TRAIN.ACCUM_STEPS * seqpar.batch_device_count(mesh)
    )
    logger.info(
        f"Devices: {info.global_device_count} ({info.process_count} hosts), "
        f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}, "
        f"global batch={samples_per_step}"
        + (f" (accum x{cfg.TRAIN.ACCUM_STEPS})" if cfg.TRAIN.ACCUM_STEPS > 1 else "")
    )

    if cfg.MODEL.ARCH == "botnet50" and cfg.TRAIN.IM_SIZE != cfg.TEST.CROP_SIZE:
        # BoTNet's position-embedding tables are sized by the training crop;
        # fail here rather than after a full epoch at the first validate()
        raise ValueError(
            f"botnet50 requires TRAIN.IM_SIZE == TEST.CROP_SIZE "
            f"(got {cfg.TRAIN.IM_SIZE} vs {cfg.TEST.CROP_SIZE}): the relative "
            f"position tables are sized by the training crop"
        )
    model = _build_cfg_model()
    init_key, dropout_key = jax.random.split(key)
    # Both keys must be host-identical: multi-controller JAX requires every
    # process to pass the same value for replicated (P()) jit inputs. Per-
    # device dropout diversity comes from fold_in(axis_index) inside the step.
    state, tx = create_train_state(model, init_key, mesh, cfg.TRAIN.IM_SIZE)
    logger.info(f"Model:\n{cfg.MODEL.ARCH}")
    logger.info(f"Params(M): {count_parameters(state.params):.3f}")
    if seqpar.seq_size(mesh) > 1 and jax.tree.leaves(state.batch_stats):
        # BN statistics would need their own seq-aware reduction (the token
        # shards see different activations); no transformer arch here has BN
        raise ValueError(
            "MESH.SEQ > 1 requires a BatchNorm-free model (vit_*/mae_*): "
            f"{cfg.MODEL.ARCH} carries batch_stats"
        )
    # the committed state's actual shardings are the authoritative specs the
    # step functions carry (None on a 1-D mesh: the replicated fast path)
    state_specs = (
        fsdp.specs_of(state) if fsdp.fsdp_size(mesh) > 1 else None
    )
    _journal_state_bytes(state, mesh)
    _journal_activation_bytes(model, mesh)

    train_loader = construct_train_loader(mesh)
    val_loader = construct_val_loader(mesh)

    start_epoch, start_step, best_acc1 = 0, 0, 0.0
    resumed = False
    if cfg.TRAIN.AUTO_RESUME:
        # rollback depth: the dtpu-agent's poison escalation rides the env
        # var (it supervises arbitrary worker commands and never edits
        # YAMLs); a hand-set RESUME.ROLLBACK works the same way
        rollback = int(os.environ.get("DTPU_RESUME_ROLLBACK", cfg.RESUME.ROLLBACK))
        if rollback > 0:
            logger.warning(
                f"Auto-resume with rollback depth {rollback}: the "
                f"{rollback} most-advanced known-good checkpoint(s) will be "
                f"skipped (poison escalation)"
            )
        res = ckpt.restore_latest(
            cfg.OUT_DIR,
            state,
            step_granular=cfg.RESUME.STEP_GRANULAR,
            skip_corrupt=cfg.RESUME.SKIP_CORRUPT,
            verify_integrity=cfg.RESUME.VERIFY_INTEGRITY,
            samples_per_step=samples_per_step,
            rollback=rollback,
        )
        if res is not None:
            state, start_epoch, start_step, best_acc1, rng_key, path = res
            if rng_key is not None:
                # mid-epoch resume: continue the interrupted run's dropout
                # stream even when RNG_SEED is unset (fresh OS entropy would
                # otherwise desync the replay of the in-progress epoch)
                dropout_key = jnp.asarray(rng_key)
            resumed = True
            obs.current().event(
                "resume", path=path, epoch=start_epoch, step=start_step,
                best_acc1=float(best_acc1),
            )
            logger.info(
                f"Resumed from {path} (epoch {start_epoch}, step {start_step}, "
                f"best {best_acc1:.3f})"
            )
    if not resumed and cfg.MODEL.WEIGHTS:
        state, _, _ = ckpt.load_checkpoint(
            cfg.MODEL.WEIGHTS, state, load_opt=cfg.TRAIN.LOAD_OPT
        )
        resumed = True  # restored arrays: recommit below
        logger.info(f"Warm-started weights from {cfg.MODEL.WEIGHTS}")
    elif not resumed and cfg.MODEL.PRETRAINED:
        state, _, _ = ckpt.load_checkpoint(_pretrained_path(), state, load_opt=False)
        resumed = True
        logger.info(f"Initialized from pretrained weights ({cfg.MODEL.ARCH})")
    if resumed:
        state = _recommit_state(state, mesh)

    # steps are built AFTER resume/warm-start on purpose: the QAT fine-tune
    # mode calibrates its fake-quant scales on the weights the run will
    # actually train (a rescue fine-tune starts from the failing model's
    # checkpoint, not from a fresh init)
    qat_model = _build_qat(model, state, mesh) if cfg.QUANT.QAT else None
    train_step = make_train_step(
        model, tx, mesh, cfg.TRAIN.TOPK, accum_steps=cfg.TRAIN.ACCUM_STEPS,
        state_specs=state_specs, qat=qat_model,
    )
    eval_step = make_eval_step(
        model, mesh, cfg.TRAIN.TOPK, state_specs=state_specs, qat=qat_model
    )

    run_tic = time.time()
    # distributed watchdog: armed for the whole epoch loop (train + eval
    # collectives both hang when a peer dies), beaten at every step
    # boundary. The first beat window includes the step compile —
    # FAULT.HANG_TIMEOUT_S must comfortably exceed it (docs/FAULT_TOLERANCE.md).
    resilience.start_watchdog(cfg.FAULT.HANG_TIMEOUT_S)
    try:
        for epoch in range(start_epoch, cfg.OPTIM.MAX_EPOCH):
            state = train_epoch(
                train_loader, mesh, train_step, state, epoch, dropout_key,
                info.is_primary, start_epoch=start_epoch, run_tic=run_tic,
                start_step=start_step if epoch == start_epoch else 0,
                best_acc1=best_acc1, injector=injector,
                fleet_poller=fleet_poller,
            )
            acc1, _ = validate(
                val_loader, mesh, eval_step, state, info.is_primary, epoch=epoch
            )
            is_best = acc1 > best_acc1
            best_acc1 = max(acc1, best_acc1)
            resilience.watchdog_beat(phase="checkpoint")  # long saves ≠ hangs
            with phase("checkpoint", epoch=epoch) as ck_phase:
                path = ckpt.save_checkpoint(cfg.OUT_DIR, epoch, state, best_acc1, is_best)
            if cfg.OBS.TRAIN_SPANS:
                # the epoch boundary's checkpoint phase as a typed span: the
                # DISPATCH wall (saves are async — the write itself overlaps
                # the next epoch; obs/trace.py, zero added syncs)
                tel_run = obs.current()
                tel_run.span(
                    tel_run.trace_tag(f"ck{epoch}"), "checkpoint",
                    1000.0 * ck_phase.seconds, epoch=epoch,
                )
            logger.info(f"Saving checkpoint (async): {path} (best Acc@1 {best_acc1:.3f})")
    finally:
        # disarm BEFORE the final waits: a completed (or crashed) run must
        # never be hard-killed by its own watchdog while draining saves
        resilience.stop_watchdog()
        # runs on success, preemption AND any mid-epoch exception: never
        # abandon an in-flight async Orbax write (a partial directory would
        # poison the next auto-resume scan). Guarded so a failed background
        # write cannot replace a primary exception (a Preempted exit must
        # stay a Preempted exit) — but a CLEAN run with a failed final
        # checkpoint must not exit 0.
        primary_exc = sys.exc_info()[0] is not None
        saves_durable = True
        try:
            try:
                ckpt.wait_for_saves()
            except Exception as exc:
                saves_durable = False
                if not primary_exc:
                    raise
                logger.error(f"final checkpoint wait failed: {exc!r}")
        finally:
            # the journal gets its run_end (and closes) on every exit path —
            # clean, preempted, diverged or crashed
            obs.end_run(
                best_acc1=best_acc1,
                epochs=cfg.OPTIM.MAX_EPOCH,
                clean=not primary_exc and saves_durable,
            )
    if saves_durable:
        # completed run with every epoch checkpoint durable: any leftover
        # emergency checkpoint is strictly dominated — clean it up. (If the
        # final write failed, the emergency checkpoints stay: they may be
        # the most-advanced restorable state.)
        ckpt.prune_mid_checkpoints(cfg.OUT_DIR, before_epoch=cfg.OPTIM.MAX_EPOCH)
    return state, best_acc1


@_model_globals_scoped
def test_model():
    """Evaluation run (reference `trainer.py:176-209`)."""
    configure_determinism(cfg.CUDNN.DETERMINISTIC)
    _enable_compile_cache()
    info = setup_distributed()
    setup_logger(cfg.OUT_DIR, info.process_index)
    mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
    model = _build_cfg_model()
    key = jax.random.PRNGKey(0)
    state, _ = create_train_state(model, key, mesh, cfg.TRAIN.IM_SIZE)
    logger.info(f"Params(M): {count_parameters(state.params):.3f}")
    state_specs = (
        fsdp.specs_of(state) if fsdp.fsdp_size(mesh) > 1 else None
    )
    if cfg.MODEL.WEIGHTS:
        state, _, _ = ckpt.load_checkpoint(cfg.MODEL.WEIGHTS, state)
        logger.info(f"Loaded weights from {cfg.MODEL.WEIGHTS}")
    elif cfg.MODEL.PRETRAINED:
        state, _, _ = ckpt.load_checkpoint(_pretrained_path(), state, load_opt=False)
        logger.info(f"Loaded pretrained weights ({cfg.MODEL.ARCH})")
    val_loader = construct_val_loader(mesh)
    # a QUANT.QAT config evaluates the fake-quant forward here too —
    # standalone eval must measure what the quantized serve path delivers,
    # not the fp twin (calibrated on the weights just loaded)
    qat_model = _build_qat(model, state, mesh) if cfg.QUANT.QAT else None
    eval_step = make_eval_step(
        model, mesh, cfg.TRAIN.TOPK, state_specs=state_specs, qat=qat_model
    )
    return validate(val_loader, mesh, eval_step, state, info.is_primary)
