"""What the cells' own limits (the device limits, not the rehearsal's) make of made readings: a sound
run passes; running statistics never updated, one kernel left unmoved and a state returned
unchanged do not. These faults read 1 by the measure itself at any size, so no chip is needed."""

import copy

import pytest

from benchmark import compare, harness

KERNELS = [f"s{i}.conv" for i in range(1, 8)]
SMALL = [f"s{i}.bn.scale" for i in range(1, 8)]


def reference():
    """Readings shaped like a model's: large kernels and small scales, all with a gradient."""
    want = {"loss": [7.0, 6.9, 6.8], "grad_norm": {}, "delta_norm": {}, "leaf_size": {}}
    for i, name in enumerate(SMALL + KERNELS):  # the kernels' norms lie above the median leaf's
        want["grad_norm"][name] = 1.0 + 0.1 * i
        want["delta_norm"][name] = 0.01 * (1.0 + 0.1 * i)
        want["leaf_size"][name] = 36864 if name in KERNELS else 64
    want["stats_delta_norm"] = {f"s{i}.bn.{k}": 0.3 + 0.01 * i for i in range(1, 8) for k in ("mean", "var")}
    return want


def sound(want):
    """A program that reads a thousandth off on every norm, and 30 % off on the small leaves:
    what resnet50's chaotic gradient does to a 64-entry scale."""
    got = copy.deepcopy(want)
    for key in ("grad_norm", "delta_norm", "stats_delta_norm"):
        for name in got.get(key, ()):
            got[key][name] *= 1.3 if name in SMALL else 1.001
    got["loss"] = [v * 1.0001 for v in want["loss"]]
    return got


def unmoved_kernel(got):
    got["delta_norm"][KERNELS[-1]] = 0.0


def stats_unchanged(got):
    got["stats_delta_norm"] = dict.fromkeys(got["stats_delta_norm"], 0.0)


def state_unchanged(got):
    got["delta_norm"] = dict.fromkeys(got["delta_norm"], 0.0)
    stats_unchanged(got)


@pytest.mark.parametrize("fault,failed", [
    (None, []),
    (stats_unchanged, ["stats"]),
    (unmoved_kernel, ["delta_large"]),  # the median of the large leaves does not see one of them
    (state_unchanged, ["delta_mid", "delta_large", "stats"]),
])
@pytest.mark.parametrize("workload", ["resnet50.train", "resnet50.train_dp4"])
def test_resnet50_limits(workload, fault, failed):
    cell, _ = harness.load_cell(workload)
    want = reference()
    got = sound(want)
    if fault:
        fault(got)
    correct, compared = compare.verdict(compare.gaps(got, want)["numbers"], cell["limits"])
    assert [n for n, p in compared.items() if p["value"] > p["limit"]] == failed
    assert correct is (not failed)
    for name in failed:
        assert compared[name]["value"] == pytest.approx(1.0, abs=0.01)  # nothing moved where the reference did


def test_a_limit_without_its_number_is_not_correct():
    want = reference()
    del want["stats_delta_norm"]
    correct, compared = compare.verdict(compare.gaps(sound(want), want)["numbers"], {"stats": 0.08})
    assert correct is False and compared["stats"]["value"] is None
