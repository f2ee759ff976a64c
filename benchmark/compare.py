"""The comparison that decides ``correct``: the program's first three steps against the plain reference.

The program's readings come from the very object the window then times (the
compiled step with its state, driven through ``train_epoch`` on the pool's
first three batches). The reference makes the same weights from the seed,
follows the same three batches in float32, and the numbers compared are:

- ``loss1``, ``loss2``, ``loss3``: each step's loss, relative gap;
- ``grad``: the first gradient as the optimizer got it, per leaf the gap
  between the two norms over the reference's norm of that leaf or of the
  median leaf (whichever is larger); the worst leaf counts;
- ``delta``: the same measure on the parameters' change after three steps,
  over the leaves whose reference gradient is at least a thousandth of the
  median leaf's (the others move by round-off alone under an adaptive
  optimizer);
- ``stats``: the same measure on the change of the running statistics, where
  the model has any;
- ``grad_mid``, ``delta_mid``: the *median* leaf's gap in place of the worst,
  over the leaves of 4096 entries or more (the kernels and matrices). Where a
  model's gradient is chaotic under rounding (resnet50 at initialisation on
  noise images: ``PERF.md`` section 2) a small leaf's norm reads the noise and
  the worst of 161 leaves its tail, in the program and in the control alike;
  the median of the large leaves is steady from seed to seed;
- ``grad_large``, ``delta_large``: the *worst* leaf over those same large leaves.
  It reads 0.015 to 0.05 where the worst of all leaves reads 0.15 to 0.46, so a
  limit on it can lie well under what one kernel left unmoved or moved double
  reads (0.8 to 1), which a median cannot see.

Which of them a cell compares is what its file gives a limit for.

Each number has a limit of its own in the cell's file.
"""

from __future__ import annotations

import statistics

import numpy as np

STEPS = 3
GRAD_FLOOR = 1e-3  # of the median leaf's gradient norm
LARGE_LEAF = 4096  # entries: from here on a leaf's norm averages enough entries to be steady


def split_rows(batch: dict, shards: int) -> list[dict]:
    """Contiguous blocks of rows, every leaf split on its leading axis."""
    (rows,) = {v.shape[0] for v in batch.values()}  # the leaves agree on the rows or it is no batch
    if rows % shards:
        raise ValueError(f"{rows} rows do not split over {shards} shards")
    n = rows // shards
    return [{k: v[i * n:(i + 1) * n] for k, v in batch.items()} for i in range(shards)]


def reference_readings(ref, opt, settings: dict, key, batches, lrs, shards: int,
                       precision: str = "f32", fault: str | None = None) -> dict:
    """Three steps of the plain reference; returns losses and per-leaf norms.

    ``settings``: the keys merged into the program's ``cfg``; the reference reads its
    sizes from them and the optimizer its hyperparameters (``OPTIM``). ``batches``:
    the pool's first batches as they are, whatever leaves the input kind gives them.

    ``shards``: the rows each device of the cell sees are a contiguous block;
    statistics of a normalisation are per block, gradients and running
    statistics are averaged over blocks, as data parallelism defines them.
    ``fault`` plants one of the faults a cell can have into the reference
    (for reading what the comparison makes of it): ``half_batch`` leaves out
    the second half of every block, ``no_exchange`` keeps the first block's
    gradient and statistics and drops the others'.
    """
    import jax
    import jax.numpy as jnp

    hp = settings["OPTIM"]
    params = jax.jit(lambda k: ref.init(k, settings))(key)
    stats = ref.init_stats(settings)
    p0, s0 = params, stats
    opt_state = opt.init(params)
    grad_fn = jax.jit(lambda p, s, block: jax.value_and_grad(ref.loss_fn, has_aux=True)(p, s, block, precision))
    update = jax.jit(lambda p, o, g, lr: opt.step(p, o, g, lr, hp))
    mean = jax.jit(lambda trees: jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees))
    norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.ravel()) for k, v in ref.compare_leaves(t).items()})
    diff_norms = jax.jit(lambda a, b: norms({k: a[k] - b[k] for k in a}))
    stat_diff_norms = jax.jit(lambda a, b: {k: jnp.linalg.norm((a[k] - b[k]).ravel()) for k in a})

    out = {"loss": []}
    for step in range(STEPS):
        blocks = split_rows(batches[step], shards)
        if fault == "half_batch":
            blocks = [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in blocks]
        losses, grads, new_stats = [], [], []
        for block in blocks:
            (loss, block_stats), g = grad_fn(params, stats, block)
            losses.append(loss)
            grads.append(g)
            new_stats.append(block_stats)
            if fault == "no_exchange":
                break
        loss = float(np.mean(jax.device_get(losses)))
        g = mean(grads) if len(grads) > 1 else grads[0]
        stats = (mean(new_stats) if len(new_stats) > 1 else new_stats[0]) if stats else stats
        del grads, new_stats
        out["loss"].append(loss)
        if step == 0:
            out["grad_norm"] = {k: float(v) for k, v in jax.device_get(norms(g)).items()}
        params, opt_state = update(params, opt_state, g, jnp.float32(lrs[step]))
    out["delta_norm"] = {k: float(v) for k, v in jax.device_get(diff_norms(params, p0)).items()}
    out["leaf_size"] = {k: int(v.size) for k, v in ref.compare_leaves(p0).items()}
    out["stats_delta_norm"] = (
        {k: float(v) for k, v in jax.device_get(stat_diff_norms(stats, s0)).items()} if s0 else {}
    )
    return out


def _worst_leaf_gap(got: dict, want: dict, keep=None, floor: float | None = None) -> tuple[float, str]:
    """Worst leaf of |got - want| / max(want, median want), over the leaves in ``keep``."""
    names = [n for n in want if keep is None or n in keep]
    if floor is None:
        floor = statistics.median(want[n] for n in names)
    worst, worst_name = 0.0, ""
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], floor, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, worst_name = gap, n
    return worst, worst_name


def _median_leaf_gap(got: dict, want: dict, keep) -> float:
    """Median leaf of |got - want| / max(want, median want), over the leaves in ``keep``."""
    floor = statistics.median(want.values())
    gaps_ = [abs(got[n] - want[n]) / max(want[n], floor, 1e-30) for n in keep]
    gap = statistics.median(gaps_) if gaps_ else float("inf")
    return gap if np.isfinite(gap) else float("inf")


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared, and for the per-leaf ones the leaf that set them."""
    numbers, leaves = {}, {}
    for i in range(STEPS):
        gap = abs(got["loss"][i] - want["loss"][i]) / abs(want["loss"][i])
        numbers[f"loss{i + 1}"] = gap if np.isfinite(gap) else float("inf")
    numbers["grad"], leaves["grad"] = _worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    median_grad = statistics.median(want["grad_norm"].values())
    moved = {n for n, g in want["grad_norm"].items() if g >= GRAD_FLOOR * median_grad}
    numbers["delta"], leaves["delta"] = _worst_leaf_gap(got["delta_norm"], want["delta_norm"], moved)
    large = {n for n, size in want.get("leaf_size", {}).items() if size >= LARGE_LEAF}
    numbers["grad_mid"] = _median_leaf_gap(got["grad_norm"], want["grad_norm"], large)
    numbers["delta_mid"] = _median_leaf_gap(got["delta_norm"], want["delta_norm"], large & moved)
    if large:  # the floor stays the median of all leaves, as for the median leaf's gap
        numbers["grad_large"], leaves["grad_large"] = _worst_leaf_gap(
            got["grad_norm"], want["grad_norm"], large, statistics.median(want["grad_norm"].values()))
        numbers["delta_large"], leaves["delta_large"] = _worst_leaf_gap(
            got["delta_norm"], want["delta_norm"], large & moved, statistics.median(want["delta_norm"].values()))
    if want.get("stats_delta_norm"):
        numbers["stats"], leaves["stats"] = _worst_leaf_gap(
            got["stats_delta_norm"], want["stats_delta_norm"]
        )
    return {"numbers": numbers, "leaves": leaves}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result's line, each number beside its limit.

    Every limit of the cell has to have its number, and every number a limit:
    a number that is not compared is left out of the cell's limits on purpose
    and named in ``PERF.md``, never silently.
    """
    compared = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            compared[name] = {"value": None, "limit": limit}
            ok = False
            continue
        value = numbers[name]
        compared[name] = {"value": value, "limit": limit}
        ok = ok and bool(value <= limit)
    return ok, compared
