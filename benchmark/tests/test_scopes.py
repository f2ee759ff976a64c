"""The program's phases read from the step's metadata and the trace's spans: made HLO text and
made intervals, the eight readers on a made run, the recorded fixture, and the CPU rehearsal."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark import files, scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OPS, MODS = xplane.OPS_LINE, xplane.MODULES_LINE
NEW_METRICS = ("step_fwd_ms", "step_bwd_ms", "step_optimizer_ms", "step_tail_ms", "step_unscoped_pct",
               "h2d_ms_per_step", "dispatch_ms_per_step", "loop_host_busy_pct")

J = "jit(step_training)"
TEXT = f"""HloModule jit_step_training, is_scheduled=true

%fused_computation.wgrad (param_0: f32[8,8], param_1: f32[8,8]) -> f32[8,8] {{
  %param_0 = f32[8,8]{{1,0}} parameter(0)
  %param_1 = f32[8,8]{{1,0}} parameter(1)
  %convolution.7 = f32[8,8]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{J}/transpose(jvp(ResNet))/layer1_0/conv1/conv_general_dilated" stack_frame_id=3}}
  %multiply.1 = f32[8,8]{{1,0}} multiply(%convolution.7, %param_1), metadata={{op_name="{J}/dtpu.optimizer/mul"}}
  %add.1 = f32[8,8]{{1,0}} add(%multiply.1, %param_1), metadata={{op_name="{J}/dtpu.optimizer/add"}}
  ROOT %add.2 = f32[8,8]{{1,0}} add(%add.1, %param_0), metadata={{op_name="{J}/dtpu.optimizer/add"}}
}}

%fused_computation.update (param_0.1: f32[8], param_1.1: f32[8], param_2.1: pred[]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %param_1.1 = f32[8]{{0}} parameter(1)
  %param_2.1 = pred[] parameter(2)
  %multiply.2 = f32[8]{{0}} multiply(%param_0.1, %param_1.1), metadata={{op_name="{J}/dtpu.optimizer/mul"}}
  %add.3 = f32[8]{{0}} add(%multiply.2, %param_1.1), metadata={{op_name="{J}/dtpu.optimizer/add"}}
  %subtract.1 = f32[8]{{0}} subtract(%param_0.1, %add.3), metadata={{op_name="{J}/dtpu.optimizer/sub"}}
  %broadcast.1 = pred[8]{{0}} broadcast(%param_2.1), dimensions={{}}, metadata={{op_name="{J}/dtpu.optimizer/jit(_where)/select_n"}}
  ROOT %select.1 = f32[8]{{0}} select(%broadcast.1, %subtract.1, %param_0.1), metadata={{op_name="{J}/dtpu.optimizer/jit(_where)/select_n"}}
}}

%fused_computation.finite (param_0.3: f32[8], param_1.3: f32[8]) -> pred[] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  %param_1.3 = f32[8]{{0}} parameter(1)
  %multiply.4 = f32[8]{{0}} multiply(%param_0.3, %param_1.3), metadata={{op_name="{J}/dtpu.grad_sync/mul"}}
  %divide.4 = f32[8]{{0}} divide(%multiply.4, %param_1.3), metadata={{op_name="{J}/dtpu.grad_sync/div"}}
  ROOT %is-finite.4 = pred[8]{{0}} is-finite(%divide.4), metadata={{op_name="{J}/dtpu.guard/is_finite"}}
}}

%fused_computation.bn (param_0.2: f32[8,8]) -> f32[8,8] {{
  %param_0.2 = f32[8,8]{{1,0}} parameter(0)
  ROOT %multiply.3 = f32[8,8]{{1,0}} multiply(%param_0.2, %param_0.2), metadata={{op_name="{J}/jvp(ResNet)/layer1_0/bn1/mul"}}
}}

%add (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y)
}}

ENTRY %main.1 (p0: f32[8,8], p1: f32[8,8], p2: f32[8], keep: pred[]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="state.params[\\'conv1\\'][\\'kernel\\']"}}
  %p1 = f32[8,8]{{1,0}} parameter(1)
  %p2 = f32[8]{{0}} parameter(2)
  %keep = pred[] parameter(3)
  %convolution.1 = f32[8,8]{{1,0}} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={{op_name="{J}/jvp(ResNet)/conv1/conv_general_dilated"}}
  %fusion.bn = f32[8,8]{{1,0}} fusion(%convolution.1), kind=kLoop, calls=%fused_computation.bn, metadata={{op_name="{J}/jvp(ResNet)/layer1_0/bn1/mul"}}
  %reduce.loss = f32[8]{{0}} reduce(%fusion.bn, %p2), dimensions={{0}}, to_apply=%add, metadata={{op_name="{J}/jvp(dtpu.loss)/jit(log_softmax)/reduce_max"}}
  %fusion.wgrad = f32[8,8]{{1,0}} fusion(%fusion.bn, %p1), kind=kOutput, calls=%fused_computation.wgrad, metadata={{op_name="{J}/dtpu.optimizer/add"}}
  %all-reduce.1 = f32[8]{{0}} all-reduce(%p2), replica_groups={{{{0,1}}}}, to_apply=%add, metadata={{op_name="{J}/dtpu.grad_sync/psum"}}
  %fusion.finite = pred[8]{{0}} fusion(%p2, %all-reduce.1), kind=kLoop, calls=%fused_computation.finite, metadata={{op_name="{J}/dtpu.guard/is_finite"}}
  %fusion.update = f32[8]{{0}} fusion(%p2, %all-reduce.1, %keep), kind=kLoop, calls=%fused_computation.update, metadata={{op_name="{J}/dtpu.optimizer/jit(_where)/select_n"}}
  %reduce.metrics = f32[8]{{0}} reduce(%fusion.wgrad, %p2), dimensions={{0}}, to_apply=%add, metadata={{op_name="{J}/dtpu.metrics/reduce_sum"}}
  %copy-start.1 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}, u32[]) copy-start(%fusion.wgrad)
  ROOT %copy-done.1 = f32[8,8]{{1,0}} copy-done(%copy-start.1)
}}
"""


@pytest.mark.parametrize("op_name,want", [
    (f"{J}/jvp(ResNet)/layer3_0/bn3/mul", ("fwd", "layer3_0/bn3")),
    (f"{J}/transpose(jvp(ResNet))/layer3_1/conv3/conv_general_dilated", ("bwd", "layer3_1/conv3")),
    (f"{J}/jvp(ViT)/block7/attn/qkv/dot_general", ("fwd", "block7/attn/qkv")),
    (f"{J}/jvp(ResNet)/conv1/jit(_pad)/pad", ("fwd", "conv1")),
    (f"{J}/jvp(ResNet)/reduce_sum", ("fwd", "ResNet")),
    (f"{J}/transpose(jvp(dtpu.loss))/jit(log_softmax)/div", ("bwd", "dtpu.loss")),
    (f"{J}/jit(shmap_body)/dtpu.grad_sync/psum", ("grad_sync", "dtpu.grad_sync")),
    (f"{J}/dtpu.guard/jit(_where)/select_n", ("guard", "dtpu.guard")),
    (f"{J}/mul", scopes.NO_SCOPE),
    ("state.params['conv1']['kernel']", scopes.NO_SCOPE),
    (None, scopes.NO_SCOPE),
])
def test_scope_of_one_op_name(op_name, want):
    assert scopes.scope_of_name(op_name) == want


def test_a_fusion_takes_its_convolutions_scope_else_its_roots():
    got = scopes.scope_of(TEXT)
    # root and own metadata under dtpu.optimizer, but it holds the weight gradient's convolution
    assert got["fusion.wgrad"] == ("bwd", "layer1_0/conv1")
    # no convolution or dot: the root's scope, however many of its ops carry another
    assert got["fusion.update"] == ("optimizer", "dtpu.optimizer")
    assert got["fusion.finite"] == ("guard", "dtpu.guard")
    assert got["fusion.bn"] == ("fwd", "layer1_0/bn1")
    assert got["convolution.1"] == ("fwd", "conv1")
    assert got["reduce.loss"] == ("fwd", "dtpu.loss")
    assert got["all-reduce.1"][0] == "grad_sync" and got["reduce.metrics"][0] == "metrics"
    assert got["copy-done.1"] == got["p0"] == scopes.NO_SCOPE
    assert "multiply.1" not in got  # the entry computation's instructions only
    assert scopes.scope_of(TEXT) is got  # kept for the run: one parse for the five readers
    assert scopes.scope_of("HloModule nothing") == {}


def made_trace(host=(), steady=None, steps=4):
    """``steps`` steps of 1000 ns; inside each: fwd 0-300, bwd 300-700 (two ops that overlap
    for 100), optimizer 700-800, metrics 800-850, a copy 850-900, idle to 1000."""
    ops, modules = [], []
    for i in range(steps):
        b = 1000 * i
        ops += [("%convolution.1 = f32[8,8] convolution(...)", b, b + 200), ("fusion.bn", b + 200, b + 300),
                ("fusion.wgrad", b + 300, b + 600), ("fusion.wgrad", b + 500, b + 700),
                ("fusion.update", b + 700, b + 800), ("reduce.metrics", b + 800, b + 850),
                ("copy-done.1", b + 850, b + 900)]
        modules.append(("jit_step_training(77)", b, b + 950))
    return xplane.Trace({"/device:TPU:0": {OPS: ops, MODS: modules}}, list(host), steady)


def test_the_phases_partition_the_step_and_an_overlap_counts_once():
    trace, got = made_trace(), scopes.scope_of(TEXT)
    ms = {p: scopes.phase_ms_per_step(trace, got, (p,)) for p in ("fwd", "bwd", "optimizer", "metrics", "unscoped")}
    assert ms["fwd"] == pytest.approx(300 / 1e6)
    assert ms["bwd"] == pytest.approx(400 / 1e6)  # 300 + 200 of durations, 400 of time
    assert ms["optimizer"] == pytest.approx(100 / 1e6) and ms["unscoped"] == pytest.approx(50 / 1e6)
    assert sum(ms.values()) == pytest.approx(trace.step_device_ms())
    assert scopes.phase_ms_per_step(trace, got, scopes.TAIL) == pytest.approx(50 / 1e6)
    assert scopes.phase_ms_per_step(trace, got, ("grad_sync",)) is None  # no such op ran: nothing, not zero


def test_a_host_span_half_outside_the_steady_span_is_left_out():
    host = [("main/1:bench.trace.steady", 1000, 3000), ("main/1:dtpu.dispatch", 900, 1100),
            ("main/1:dtpu.dispatch", 1200, 1260), ("main/1:dtpu.dispatch", 2900, 3100),
            ("python/2:dtpu.h2d_transfer", 1500, 1900), ("main/1:not.dtpu.dispatch.either", 1300, 1400)]
    trace = made_trace(host, steady=(1000, 3000))
    assert scopes.host_span_ms(trace, "dtpu.dispatch") == [pytest.approx(60 / 1e6)]
    assert scopes.host_span_ms(trace, "dtpu.h2d_transfer") == [pytest.approx(400 / 1e6)]
    assert scopes.host_span_ms(trace, "dtpu.fetch_wait") == []


# -- the eight readers, as the harness calls them ------------------------------

def read_all(ctx) -> dict:
    return {name: files.load_module("layer_metrics", name).read(ctx) for name in NEW_METRICS}


def made_ctx(tmp_path, text, trace, waits):
    (tmp_path / scopes.STEP_HLO).write_text(text)
    journal = [{"kind": "run_start", "out_dir": str(tmp_path)},
               {"kind": "counters", "scope": "epoch", "epoch": 2, "waits": waits}]
    return {"trace": trace, "journal": journal, "window": {"epoch": 2, "seconds": 20.0}}


def test_the_readers_on_a_made_run(tmp_path):
    host = [("main/1:dtpu.dispatch", 100 + 1000 * i, 100 + 1000 * i + d) for i, d in enumerate((30, 50, 40))]
    host += [("python/2:dtpu.h2d_transfer", 1000 * i, 1000 * i + 600) for i in range(3)]
    ctx = made_ctx(tmp_path, TEXT, made_trace(host),
                   {"fetch_wait_s": 4.0, "throttle_s": 15.0, "data_wait_s": 0.5, "dispatch_s": 0.2})
    got = read_all(ctx)
    assert got["step_fwd_ms"] == pytest.approx(300 / 1e6) and got["step_bwd_ms"] == pytest.approx(400 / 1e6)
    assert got["step_optimizer_ms"] == pytest.approx(100 / 1e6) and got["step_tail_ms"] == pytest.approx(50 / 1e6)
    assert got["step_unscoped_pct"] == pytest.approx(100 * 50 / 900)
    assert got["dispatch_ms_per_step"] == pytest.approx(40 / 1e6)  # the median
    assert got["h2d_ms_per_step"] == pytest.approx(600 / 1e6)
    assert got["loop_host_busy_pct"] == pytest.approx(100 * (1 - 19.5 / 20.0))
    # each is found by the harness under its own name
    assert set(NEW_METRICS) <= {module.NAME for module in files.layer_metric_modules()}


def test_a_program_without_the_scopes_reads_nothing_and_never_a_zero(tmp_path):
    """The parent of this PR: flax's names are there, no ``dtpu.`` scope, span or counter is."""
    plain = TEXT.replace("dtpu.optimizer/", "").replace("dtpu.guard/", "").replace("dtpu.metrics/", "")
    got = read_all(made_ctx(tmp_path, plain, made_trace(), {"data_wait_s": 0.5, "h2d_transfer_s": 0.4}))
    assert got["step_optimizer_ms"] is None and got["step_tail_ms"] is None
    assert got["step_unscoped_pct"] == pytest.approx(100 * 200 / 900)  # update, metrics and the copy
    assert got["step_fwd_ms"] == pytest.approx(300 / 1e6)
    assert got["h2d_ms_per_step"] is None and got["dispatch_ms_per_step"] is None
    assert got["loop_host_busy_pct"] is None
    # no device plane (a CPU rehearsal), no step text, no journal record: nothing, and no error
    assert set(read_all({"trace": None, "journal": [], "window": {"epoch": 2, "seconds": 20.0}}).values()) == {None}
    none_written = {"trace": made_trace(), "journal": [{"kind": "run_start", "out_dir": str(tmp_path / "absent")}],
                    "window": {"epoch": 2, "seconds": 20.0}}
    assert read_all(none_written)["step_unscoped_pct"] is None


# -- the recorded fixture ------------------------------------------------------

FIXTURE = os.path.join(HERE, "fixtures", "resnet50_scoped_steps")


@pytest.mark.skipif(not os.path.isfile(FIXTURE + ".xplane.pb"), reason="recorded fixture not present")
def test_recorded_fixture_reads_its_recorded_numbers(tmp_path):
    """A cut of a traced ``resnet50.train`` run of this tree on a TPU v5 lite: the steps' device
    ops and the host's spans, the step's text cut to names, calls and ``op_name`` (the text itself
    is megabytes), the run's ``counters`` record, and what the eight readers made of them there."""
    with open(FIXTURE + ".expected.json") as f:
        want = json.load(f)
    with gzip.open(FIXTURE + ".hlo_names.txt.gz", "rt") as f:
        text = f.read()
    trace = xplane.load(FIXTURE + ".xplane.pb")
    got_scopes = scopes.scope_of(text)
    for op, scope in want["scopes"].items():  # the compiler's names PERF.md section 5 gives an owner
        assert list(got_scopes[op]) == scope, op
    ctx = made_ctx(tmp_path, text, trace, want["waits"])
    ctx["window"] = want["window"]
    got = read_all(ctx)
    for name in NEW_METRICS:
        assert got[name] == pytest.approx(want["metrics"][name], rel=1e-9), name
    parts = sum(got[n] for n in ("step_fwd_ms", "step_bwd_ms", "step_optimizer_ms", "step_tail_ms"))
    whole = trace.step_device_ms()
    assert parts + got["step_unscoped_pct"] / 100 * whole == pytest.approx(whole, rel=0.01)
    assert got["step_unscoped_pct"] < 5.0
    assert len(trace.step_events(trace.busiest())) == want["steps"]
    assert trace.step_events(trace.busiest())[0][0].startswith("jit_step_training(")


# -- the rehearsal -------------------------------------------------------------

def test_rehearsal_still_ends_correct_and_prints_the_counter_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "resnet50.train", "--seed",
         str(2**31 + 26), "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    busy = line["metrics"]["cpu_rehearsal.loop_host_busy_pct"]
    assert busy["unit"] == "%" and 0.0 <= busy["value"] <= 100.0
    # no device plane on a CPU: the trace's readers find nothing and say nothing
    assert not any(name.startswith("cpu_rehearsal.step_") for name in line["metrics"])
