"""The trace reduction: unions, not sums; checked on made intervals and on the recorded fixture."""

import json
import os

import pytest

from benchmark import hlo, scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
OPS, MODS = xplane.OPS_LINE, xplane.MODULES_LINE


def make(ops, modules=(), host=()):
    return xplane.Trace({"/device:TPU:0": {OPS: list(ops), MODS: list(modules)}}, list(host))


def test_busy_is_a_union_not_a_sum():
    # two ops overlap for 40 ns: a sum of durations says 140, the union 100
    trace = make([("fusion.1", 0, 60), ("fusion.2", 20, 100), ("copy.3", 150, 200)])
    assert trace.busy_ns("/device:TPU:0") == 150
    assert sum(e - s for _, s, e in trace.devices["/device:TPU:0"][OPS]) == 190
    assert trace.idle_share() == pytest.approx(1 - 150 / 200)


def test_step_time_classes_and_exposed_collectives():
    classes = {"conv.1": "mxu", "bn.2": "vector", "all-reduce.3": "collective"}
    ops = []
    for base in (0, 1000, 2000):
        ops += [("conv.1", base + 0, base + 400), ("bn.2", base + 300, base + 600),
                ("all-reduce.3", base + 500, base + 800)]
    modules = [("jit_step(123)", b, b + 900) for b in (0, 1000, 2000)]
    trace = make(ops, modules)
    assert trace.step_device_ms() == pytest.approx(800 / 1e6)  # union of 0..800 per step
    assert trace.step_period_ms() == pytest.approx(1000 / 1e6)
    assert scopes.ms_per_step(trace, lambda op: classes.get(op) == "mxu") == pytest.approx(400 / 1e6)
    # the collective runs 500..800, compute until 600: 200 ns a step are exposed
    assert trace.exposed_collective_ns(classes) == 600
    share = trace.busy_ns("/device:TPU:0", classes, only="vector") / trace.busy_ns("/device:TPU:0")
    assert share == pytest.approx(900 / 2400)


def test_idle_gap_is_named_by_the_innermost_host_span():
    trace = make([("a", 0, 100), ("b", 400, 500)],
                 host=[("main:bench.train_epoch", 0, 500), ("main:bench.trace.steady", 110, 395),
                       ("main:fetch", 50, 480), ("main:device_get", 120, 390)])
    gaps = trace.idle_gaps(3)
    assert gaps[0][0] == "main:device_get @+0.00s" and gaps[0][1] == pytest.approx(300 / 1e9)


def test_only_the_steady_span_is_read():
    ops = [("a", 0, 100), ("b", 150, 250), ("c", 300, 400), ("d", 450, 550)]
    trace = xplane.Trace({"/device:TPU:0": {OPS: ops, MODS: []}}, [], steady=(120, 420))
    assert trace.window() == (120, 420)
    assert trace.busy_ns("/device:TPU:0") == 200  # b and c; a and d lie outside
    assert trace.idle_share() == pytest.approx(1 - 200 / 300)


def test_interval_helpers():
    assert xplane.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5), (8, 10)]
    assert xplane.op_key("%fusion.12 = bf16[8] fusion(...)") == "fusion.12"


FIXTURE = os.path.join(HERE, "fixtures", "resnet50_two_steps.xplane.pb")


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="recorded fixture not present")
def test_recorded_fixture_reduces_to_its_recorded_numbers():
    """A cut of the first hand-read trace of this PR (two steps of resnet50.train on a TPU v5 lite)."""
    with open(os.path.join(HERE, "fixtures", "resnet50_two_steps.expected.json")) as f:
        want = json.load(f)
    with open(os.path.join(HERE, "fixtures", "resnet50_two_steps.classes.json")) as f:
        classes = json.load(f)
    trace = xplane.load(FIXTURE)
    device = trace.busiest()
    ops = trace.devices[device][OPS]
    summed = sum(e - s for _, s, e in ops)
    assert trace.busy_ns(device) == want["busy_ns"]
    assert summed == want["sum_of_durations_ns"]
    assert trace.busy_ns(device) <= summed
    lo, hi = trace.window()
    assert hi - lo == want["window_ns"]
    assert trace.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    assert len(trace.step_events(device)) == want["steps"]
    assert trace.step_device_ms() == pytest.approx(want["step_device_ms"], rel=1e-9)
    assert trace.step_period_ms() == pytest.approx(want["step_period_ms"], rel=1e-9)
    assert scopes.ms_per_step(trace, lambda op: classes.get(op) == "mxu") == pytest.approx(want["mxu_ms_per_step"], rel=1e-9)
    # on this chip the ops of one core never overlap, so here the union equals the sum; the made
    # intervals above are where a sum in a union's place fails
    assert want["steps"] == 2 and 90 < want["step_device_ms"] < 105
    got = trace.class_ns(classes, device)
    assert got == want["class_ns"]
    assert trace.exposed_collective_ns(classes, device) == want["exposed_collective_ns"]
