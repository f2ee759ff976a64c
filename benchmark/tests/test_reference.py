"""The plain references agree with ``build_model`` + ``make_train_step`` at a tiny size on the CPU.

The program runs in float32 here (``MODEL.DTYPE float32``) so that the
comparison is of the mathematics: loss, the first gradient, one optimizer
update and the running statistics, element by element.
"""

import json

import numpy as np
import pytest

from benchmark import compare, harness, traffic

# float32 on both sides; what differs is the order of the reductions (flax's
# fast variance E[x^2] - E[x]^2 against the two-pass form, XLA's conv
# algorithms), which leaves some 1e-6 relative in an activation and grows
# through 50 layers of a backward pass to some 1e-4 of a leaf's largest entry.
LOSS_TOL = 2e-5
LEAF_TOL = 2e-3


def one_step(config_name, tmp_path):
    import jax

    cell, config = harness.load_cell(f"{config_name}.train")
    settings = harness.settings_for(cell, config, rehearse=True)
    settings["MODEL"]["DTYPE"] = "float32"
    # 64 px leaves stage 4 a 2x2 map: 32 rows a channel under BatchNorm, where a
    # near-constant channel amplifies float32 round-off through 1/sqrt(var + eps)
    # until single entries of a gradient differ by a quarter. 128 px and 16 rows
    # condition the problem as the real size does.
    settings["TRAIN"].update(IM_SIZE=128, BATCH_SIZE=16)
    seed = 3
    program = harness.Program(cell, config, settings, seed, str(tmp_path))
    ref, opt, hp = program.ref, program.opt, program.hp
    names = list(ref.shapes(settings))
    pool = traffic.make_pool(config["input"], seed, 1, program.global_batch, settings)
    params0 = jax.tree.map(np.array, program.params0)
    program.epoch(traffic.PoolLoader(pool, steps=1), epoch=0)
    state = jax.tree.map(np.array, program.state)
    program.end_run()
    loss = [r for r in program.journal() if r["kind"] == "window"][0]["loss"]
    got = {
        "loss": loss,
        "grad": ref.from_program(opt.first_gradient(state.opt_state, params0, hp), names),
        "params": ref.from_program(state.params, names),
        "stats": ref.from_program(state.batch_stats, list(ref.init_stats(settings))),
    }
    # the reference, from the seed
    from benchmark.reference import schedule

    key = program.weights_key
    p = ref.init(key, settings)
    s = ref.init_stats(settings)
    (ref_loss, new_stats), g = jax.value_and_grad(ref.loss_fn, has_aux=True)(p, s, pool[0])
    new_p, _ = opt.step(p, opt.init(p), g, schedule.lr_at_epoch(hp, 0), hp)
    want = {"loss": float(ref_loss), "grad": g, "params": new_p, "stats": new_stats, "params0": p}
    return ref, got, want


def worst(got, want):
    """Worst entry of any leaf, against that leaf's largest entry."""
    out = 0.0
    for name in want:
        w = np.asarray(want[name])
        out = max(out, float(np.max(np.abs(np.asarray(got[name]) - w)) / (np.max(np.abs(w)) + 1e-30)))
    return out


def norm_gap(got, want, keep=None):
    """The benchmark's own measure: worst leaf of the gap between the two norms."""
    norms = lambda t: {k: float(np.linalg.norm(np.asarray(v))) for k, v in t.items()}
    return compare._worst_leaf_gap(norms(got), norms(want), keep)[0]


# resnet50 at initialisation on noise images is chaotic entry by entry: a 1e-6 relative
# nudge of the weights moves single entries of a stage-4 gradient by 20 % of the leaf's
# largest (measured with the reference against itself), through ReLU and max-pool
# switches and 53 BatchNorms. Norms are steady (1e-3), so leaves are compared by the
# benchmark's own measure, and entry by entry only where no switch lies downstream: the
# classifier. ViT-B/16 is smooth and is compared entry by entry throughout.
ENTRYWISE = {"resnet50": ("fc.w", "fc.b"), "vit_b16": None}
ENTRY_TOL = 2e-3
NORM_TOL = {"resnet50": 5e-2, "vit_b16": 5e-3}  # chaos moves a resnet leaf's norm by percents (2 % read)


@pytest.mark.parametrize("config_name", ["resnet50", "vit_b16"])
def test_program_matches_reference_in_float32(config_name, tmp_path):
    ref, got, want = one_step(config_name, tmp_path)
    assert abs(got["loss"] - want["loss"]) / want["loss"] < LOSS_TOL
    pick = ENTRYWISE[config_name] or list(want["grad"])
    assert worst({k: got["grad"][k] for k in pick}, {k: want["grad"][k] for k in pick}) < ENTRY_TOL
    grads_got = ref.compare_leaves({k: np.asarray(v) for k, v in got["grad"].items()})
    grads = ref.compare_leaves({k: np.asarray(v) for k, v in want["grad"].items()})
    assert norm_gap(grads_got, grads) < NORM_TOL[config_name]
    if want["stats"]:
        # the change of the running statistics from their start (mean 0, variance 1)
        start = lambda k: 1.0 if k.endswith(".var") else 0.0
        change = {k: np.asarray(v) - start(k) for k, v in want["stats"].items()}
        got_change = {k: np.asarray(v) - start(k) for k, v in got["stats"].items()}
        assert norm_gap(got_change, change) < NORM_TOL[config_name]
        assert worst(got_change, change) < ENTRY_TOL  # forward only: no switch amplifies it
    # one optimizer update: the step each leaf took. Under LAMB an entry with no gradient
    # (the key's bias) moves by the sign of round-off, so the packed leaves are compared as
    # their parts and the gradient-free ones are left out by the benchmark's own rule.
    step_want = ref.compare_leaves({k: np.asarray(want["params"][k]) - np.asarray(want["params0"][k]) for k in want["params"]})
    step_got = ref.compare_leaves({k: np.asarray(got["params"][k]) - np.asarray(want["params0"][k]) for k in want["params"]})
    norms = {k: float(np.linalg.norm(v)) for k, v in grads.items()}
    floor = compare.GRAD_FLOOR * float(np.median(list(norms.values())))
    kept = {k for k in step_want if norms[k] >= floor}
    assert len(kept) >= len(step_want) - 12  # only the 12 key biases may drop out
    assert norm_gap(step_got, step_want, kept) < NORM_TOL[config_name]


def test_control_precision_is_told_from_the_reference():
    """fp8 operands in the reference's place move every compared number off zero (CPU, tiny size)."""
    import jax

    from benchmark import files
    from benchmark.reference import schedule

    cell, config = harness.load_cell("resnet50.train")
    settings = harness.settings_for(cell, config, rehearse=True)
    ref = files.load_module("reference", "resnet50")
    opt = files.load_module("reference", "optim_sgd")
    pool = traffic.make_pool(config["input"], 5, 3, 8, settings)
    lrs = [schedule.lr_at_epoch(settings["OPTIM"], e) for e in (0, 1, 1)]
    key = harness.seed_key(5)
    want = compare.reference_readings(ref, opt, settings, key, pool, lrs, 1)
    again = compare.reference_readings(ref, opt, settings, key, pool, lrs, 1)
    control = compare.reference_readings(ref, opt, settings, key, pool, lrs, 1, precision="fp8")
    same = compare.gaps(again, want)["numbers"]
    off = compare.gaps(control, want)["numbers"]
    assert all(v == 0.0 for v in same.values())  # the same seed gives the same readings
    assert off["loss1"] > 1e-3 and off["grad"] > 1e-2
    ok, _ = compare.verdict(off, {"loss1": 1e-3, "grad": 1e-2})
    assert not ok
