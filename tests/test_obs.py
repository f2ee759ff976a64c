"""dtpu-obs telemetry subsystem (docs/OBSERVABILITY.md), on the CPU mesh.

Coverage map (the ISSUE-3 acceptance list):

- journal schema round-trip + validation + crash-torn-tail tolerance;
- MFU arithmetic against a hand-computed ResNet-50 case, and the lowered
  (no-compile) step-cost against a hand-computable dense step;
- monitoring-counter capture, unit (injected events) and end-to-end across
  a 2-epoch smoke train;
- typed resilience events: skipped steps, consecutive-skip abort, emergency
  checkpoint + preempt, resume markers across a relaunch;
- programmatic profiler windows: OBS.PROFILE_AT_STEPS and the SIGUSR1
  trigger;
- summarize/validate CLI golden output;
- the instrumented step loop still compiles exactly once (CompileGuard) and
  the obs package + every instrumented module stays dtpu-lint clean with NO
  baseline (stricter than the repo-wide baselined invariant in
  tests/test_analysis.py).
"""

import json
import os
import signal

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distribuuuu_tpu import obs, resilience, trainer
from distribuuuu_tpu.analysis.core import lint_paths
from distribuuuu_tpu.analysis.guards import CompileGuard
from distribuuuu_tpu.models import list_models, register_model
from distribuuuu_tpu.obs import flops as obs_flops
from distribuuuu_tpu.obs import profiler as obs_profiler
from distribuuuu_tpu.obs.__main__ import main as obs_cli
from distribuuuu_tpu.obs.journal import Journal, read_journal, validate_record
from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.obs.summarize import render
from distribuuuu_tpu.runtime import data_mesh

# ---------------------------------------------------------------------------
# Tiny arch + recipe (same shape as tests/test_resilience.py's)
# ---------------------------------------------------------------------------

if "obs_tiny" not in list_models():

    class _ObsTiny(nn.Module):
        num_classes: int = 4

        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Conv(4, (3, 3), use_bias=False, dtype=jnp.float32)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x).mean(axis=(1, 2))
            return nn.Dense(self.num_classes)(x)

    @register_model("obs_tiny")
    def obs_tiny(num_classes, dtype, bn_axis_name=None, remat=False):
        return _ObsTiny(num_classes=num_classes)


def _tiny_run_cfg(c, out_dir, max_epoch=2):
    """4 steps/epoch DUMMY_INPUT recipe on the tiny arch (seconds per run)."""
    c.MODEL.ARCH = "obs_tiny"
    c.MODEL.NUM_CLASSES = 4
    c.MODEL.DTYPE = "float32"
    c.MODEL.DUMMY_INPUT = True
    c.TRAIN.BATCH_SIZE = 2
    c.TRAIN.IM_SIZE = 8
    c.TEST.IM_SIZE = 8
    c.TEST.CROP_SIZE = 8
    c.TEST.BATCH_SIZE = 2
    c.TRAIN.DUMMY_EPOCH_SAMPLES = 64  # // (2 * 8 devices) = 4 steps/epoch
    c.TRAIN.PRINT_FREQ = 2
    c.OPTIM.MAX_EPOCH = max_epoch
    c.OPTIM.WARMUP_EPOCHS = 0
    c.RNG_SEED = 5
    c.FAULT.HANDLE_SIGNALS = False  # keep process signal state test-local
    c.OUT_DIR = str(out_dir)
    return c


def _records(out_dir):
    return list(read_journal(obs.journal_path(str(out_dir))))


def _kinds(records):
    return [r["kind"] for r in records]


def _assert_valid(records):
    errors = [e for r in records for e in validate_record(r)]
    assert errors == [], errors


@pytest.fixture(autouse=True)
def _reset_obs():
    resilience.reset_run_stats()
    resilience.clear_preemption()
    obs_profiler._sigusr1_requested.clear()
    yield
    obs.end_run()  # close any telemetry a failing test left open
    resilience.clear_preemption()
    resilience.uninstall_preemption_handler()
    obs_profiler._sigusr1_requested.clear()


# ---------------------------------------------------------------------------
# Journal: schema round-trip + validation
# ---------------------------------------------------------------------------

def test_journal_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    j.append({"ts": 1.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 2})
    # numpy scalars must serialize as plain JSON numbers
    j.append(
        {
            "ts": np.float64(2.0),
            "kind": "eval",
            "epoch": np.int32(1),
            "acc1": np.float32(76.4),
            "acck": 93.1,
            "loss": None,
            "wall_s": 1.5,
            "samples": np.float32(64.0),
        }
    )
    j.close()
    recs = list(read_journal(path))
    _assert_valid(recs)
    assert _kinds(recs) == ["fault_skipped_steps", "eval"]
    assert recs[1]["epoch"] == 1 and abs(recs[1]["acc1"] - 76.4) < 1e-3
    # round-trip through json again (the file really is plain JSONL)
    with open(path) as f:
        assert all(json.loads(line) for line in f)


def test_journal_validation_catches_bad_records():
    ok = {"ts": 1.0, "kind": "preempt", "epoch": 1, "step": 3, "path": "x"}
    assert validate_record(ok) == []
    assert validate_record({"ts": 1.0, "kind": "no_such_kind"})  # unknown kind
    assert validate_record({"kind": "preempt"})  # missing ts + fields
    bad_type = dict(ok, epoch="one")
    assert any("epoch" in e for e in validate_record(bad_type))
    # bool must not satisfy an int-typed field (bool subclasses int)
    assert any("step" in e for e in validate_record(dict(ok, step=True)))


def test_journal_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        f.write('{"ts": 1.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 1}\n')
        f.write('{"ts": 2.0, "kind": "fau')  # crash mid-append
    recs = list(read_journal(path))
    assert len(recs) == 1  # torn tail skipped, not fatal
    with pytest.raises(json.JSONDecodeError):
        list(read_journal(path, strict=True))


def test_reopen_after_torn_tail_heals_and_keeps_both_runs(tmp_path):
    """A crash mid-append leaves a partial line; the relaunch's Journal must
    drop it before appending — gluing a new record onto the fragment would
    make the whole (two-run) journal unreadable."""
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        f.write('{"ts": 1.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 1}\n')
        f.write('{"ts": 2.0, "kind": "fau')  # SIGKILL mid-append
    j = Journal(path)  # relaunch into the same OUT_DIR
    j.append({"ts": 3.0, "kind": "fault_skipped_steps", "epoch": 1, "count": 2})
    j.close()
    recs = list(read_journal(path))
    _assert_valid(recs)
    assert [r["epoch"] for r in recs] == [0, 1]  # run 1 kept, run 2 readable


def test_open_next_part_never_truncates_committed_parts(tmp_path):
    """The remote-commit rollover (journal + log writer): each open continues
    the part sequence; a relaunch must never overwrite an earlier launch's
    committed objects."""
    from distribuuuu_tpu.runtime import pathio

    base = str(tmp_path / "j.jsonl")
    for expected_part, payload in enumerate(["a", "b", "c"]):
        f, part = pathio.open_next_part(base)
        f.write(payload)
        f.close()
        assert part == expected_part
    assert open(base).read() == "a"
    assert open(base + ".part1").read() == "b"
    assert open(base + ".part2").read() == "c"


def test_read_journal_reassembles_parts_in_order(tmp_path):
    base = str(tmp_path / "j.jsonl")
    for suffix, epoch in [("", 0), (".part1", 1), (".part2", 2)]:
        with open(base + suffix, "w") as f:
            f.write(json.dumps(
                {"ts": 1.0, "kind": "fault_skipped_steps", "epoch": epoch, "count": 1}
            ) + "\n")
    recs = list(read_journal(base))
    _assert_valid(recs)
    assert [r["epoch"] for r in recs] == [0, 1, 2]


def test_summarize_cli_corrupt_journal_exits_1(tmp_path):
    path = str(tmp_path / "corrupt.jsonl")
    with open(path, "w") as f:
        f.write("not json at all\n")  # non-tail corruption: corrupt, not torn
        f.write('{"ts": 1.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 1}\n')
    assert obs_cli(["summarize", path]) == 1
    assert obs_cli(["validate", path]) == 1


def test_validate_cli(tmp_path):
    good = str(tmp_path / "good.jsonl")
    Journal(good).append({"ts": 1.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 1})
    assert obs_cli(["validate", good]) == 0
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"ts": 1.0, "kind": "eval"}\n')  # missing required fields
    assert obs_cli(["validate", bad]) == 1


# ---------------------------------------------------------------------------
# MFU arithmetic + step cost
# ---------------------------------------------------------------------------

def test_mfu_arithmetic_hand_computed_resnet_case():
    """ResNet-50 @ 224px: ~12.3 GFLOPs per trained image (fwd+bwd). A global
    step of 256 images in 0.1s on 8 devices with a v5e-class peak of
    197 TFLOP/s/device: (256 * 12.3e9 / 0.1) / (8 * 197e12) = 0.019980."""
    got = obs_flops.mfu(256 * 12.3e9, 0.1, 8, 197e12)
    assert got == pytest.approx(0.0199797, rel=1e-4)
    # degenerate inputs → None (MFU is omitted, never fabricated)
    assert obs_flops.mfu(None, 0.1, 8, 197e12) is None
    assert obs_flops.mfu(1e9, 0.1, 8, None) is None
    assert obs_flops.mfu(1e9, 0.0, 8, 197e12) is None
    assert obs_flops.mfu(1e9, 0.1, 0, 197e12) is None


def test_peak_flops_table_and_override():
    class _Dev:  # the table's own values: tests/test_kernel_choice.py
        device_kind = "cpu"

    assert obs_flops.peak_flops_per_device(_Dev()) is None
    # explicit override beats the table and unknown hardware
    assert obs_flops.peak_flops_per_device(_Dev(), override_tflops=1.5) == pytest.approx(1.5e12)
    _Dev.device_kind = "TPU v5 lite"
    assert obs_flops.peak_flops_per_device(_Dev(), override_tflops=1.5) == pytest.approx(1.5e12)


def test_lowered_step_cost_dense_hand_computed():
    """One Dense fwd+bwd: matmul 2*B*I*O fwd plus two matmuls in bwd
    (dW = x^T g, dx = g W^T) ≈ 6*B*I*O total — the lowered cost model must
    land in that ballpark, and lowering must trigger NO backend compile."""
    B, I, O = 32, 64, 16

    @jax.jit
    def step(w, x):
        def loss_fn(w):
            return jnp.mean(x @ w)

        return jax.value_and_grad(loss_fn)(w)

    w = jnp.zeros((I, O), jnp.float32)
    x = jnp.ones((B, I), jnp.float32)
    with CompileGuard(exact=0):  # pricing must not compile anything
        cost = obs_flops.lowered_step_cost(step, w, x)
    assert cost is not None
    base = 2.0 * B * I * O
    assert base <= cost["flops"] <= 4 * base  # 1-3 matmuls + pointwise slack


# ---------------------------------------------------------------------------
# Monitoring bridge
# ---------------------------------------------------------------------------

def test_monitoring_bridge_captures_events_and_deltas():
    bridge = MonitoringBridge().install()
    try:
        before = bridge.snapshot()
        jax.monitoring.record_event("/test/dtpu_obs_event")
        jax.monitoring.record_event_duration_secs("/test/dtpu_obs_duration", 0.25)
        jax.monitoring.record_event_duration_secs("/test/dtpu_obs_duration", 0.5)
        after = bridge.snapshot()
        delta = MonitoringBridge.delta(after, before)
        assert delta["counters"]["/test/dtpu_obs_event"] == 1
        d = delta["durations"]["/test/dtpu_obs_duration"]
        assert d["count"] == 2 and d["total_s"] == pytest.approx(0.75)
    finally:
        bridge.close()
    # closed bridge stops counting
    snap = bridge.snapshot()
    jax.monitoring.record_event("/test/dtpu_obs_event")
    assert bridge.snapshot() == snap


# ---------------------------------------------------------------------------
# End-to-end: 2-epoch smoke train emits a schema-valid journal
# ---------------------------------------------------------------------------

def test_smoke_train_emits_schema_valid_journal(fresh_cfg, tmp_path):
    _tiny_run_cfg(fresh_cfg, tmp_path / "out")
    trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    kinds = set(_kinds(recs))
    assert {
        "run_start", "window", "epoch_train", "eval", "checkpoint",
        "counters", "memory", "run_end",
    } <= kinds

    start = next(r for r in recs if r["kind"] == "run_start")
    assert start["devices"] == jax.device_count()
    assert start["global_batch"] == 2 * jax.device_count()
    assert len(start["config_fingerprint"]) == 12

    windows = [r for r in recs if r["kind"] == "window"]
    assert windows[0]["warmup"] is True  # compile window flagged
    for w in windows:
        assert 0.0 <= w["goodput"] <= 1.0
        assert w["flops_per_step"] and w["flops_per_step"] > 0
        assert "mfu" in w  # None on CPU (peak unknown), but always present
        assert w["step_time"] > 0
        # the data-wait alarm's signal (ISSUE-11): producer-starvation
        # time / window wall, journaled on every window
        assert 0.0 <= w["data_wait_frac"] <= 1.0

    # train-side spans (obs/trace.py TRAIN_PHASES): each window journals the
    # loop's four measured phases and the rest of its wall (host) under one
    # trace id; epoch boundaries add a checkpoint span — all fed from the
    # existing PRINT_FREQ fetch
    from distribuuuu_tpu.obs.trace import TRAIN_PHASES

    assert "compute" not in TRAIN_PHASES
    window_phases = set(TRAIN_PHASES) - {"checkpoint"}
    spans = [r for r in recs if r["kind"] == "span"]
    assert {s["phase"] for s in spans} == set(TRAIN_PHASES)
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], set()).add(s["phase"])
    window_traces = [p for p in by_trace.values() if "dispatch" in p]
    assert len(window_traces) == len(windows)
    assert all(window_phases == p for p in window_traces)

    # monitoring counters journaled per epoch; epoch 0 must have seen the
    # compile machinery (trace events fire even when the persistent compile
    # cache serves the binary)
    epoch_counters = [
        r for r in recs if r["kind"] == "counters" and r.get("scope") == "epoch"
    ]
    assert [r["epoch"] for r in epoch_counters] == [0, 1]
    seen0 = set(epoch_counters[0]["counters"]) | set(epoch_counters[0]["durations"])
    assert any("compile" in k for k in seen0)

    evals = [r for r in recs if r["kind"] == "eval"]
    assert [r["epoch"] for r in evals] == [0, 1]
    ckpts = [r for r in recs if r["kind"] == "checkpoint"]
    assert {c["ckpt_kind"] for c in ckpts} <= {"epoch", "best"}
    assert sum(1 for c in ckpts if c["ckpt_kind"] == "epoch") == 2
    mems = [r for r in recs if r["kind"] == "memory"]
    assert len(mems) == 2 and all(m["live_bytes"] > 0 for m in mems)

    end = recs[-1]
    assert end["kind"] == "run_end" and end["clean"] is True
    assert end["best_acc1"] == pytest.approx(100.0)
    # epoch 1 serves every shape from the epoch-0 jit cache
    assert epoch_counters[1]["durations"].get(
        "/jax/core/compile/backend_compile_duration", {"count": 0}
    )["count"] == 0


def test_obs_disabled_is_a_noop(fresh_cfg, tmp_path):
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=1)
    c.OBS.ENABLED = False
    c.OBS.PROFILE_AT_STEPS = [0]  # master switch must gate the profiler too
    trainer.train_model()
    assert not os.path.exists(obs.journal_path(str(tmp_path / "out")))
    assert not os.path.exists(str(tmp_path / "out" / "profile"))
    assert obs.current().enabled is False


def test_legacy_train_profile_survives_obs_disabled(fresh_cfg, tmp_path):
    """TRAIN.PROFILE predates the telemetry subsystem: OBS.ENABLED=False must
    not silently swallow its epoch-0 trace (journal-less, trace on disk)."""
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=1)
    c.OBS.ENABLED = False
    c.TRAIN.PROFILE = True
    c.TRAIN.PROFILE_START = 1
    c.TRAIN.PROFILE_STEPS = 2
    trainer.train_model()
    assert os.path.isdir(str(tmp_path / "out" / "profile" / "gstep_000001"))
    assert not os.path.exists(obs.journal_path(str(tmp_path / "out")))


# ---------------------------------------------------------------------------
# Typed resilience events
# ---------------------------------------------------------------------------

@pytest.mark.faultinject
def test_skipped_steps_produce_typed_events(fresh_cfg, tmp_path):
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out")
    c.FAULT.INJECT_NAN_STEPS = [1]
    trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    skipped = [r for r in recs if r["kind"] == "fault_skipped_steps"]
    assert [(r["epoch"], r["count"]) for r in skipped] == [(0, 1)]
    assert sum(w["skipped"] for w in recs if w["kind"] == "window") == 1
    epochs = {r["epoch"]: r for r in recs if r["kind"] == "epoch_train"}
    assert epochs[0]["skipped"] == 1 and epochs[1]["skipped"] == 0


@pytest.mark.faultinject
def test_consecutive_abort_produces_typed_event(fresh_cfg, tmp_path):
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=1)
    c.FAULT.INJECT_NAN_STEPS = [0, 1, 2, 3]
    c.FAULT.MAX_CONSECUTIVE_SKIPS = 2
    with pytest.raises(resilience.NonFiniteDivergence):
        trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    aborts = [r for r in recs if r["kind"] == "fault_abort"]
    assert len(aborts) == 1 and aborts[0]["consecutive"] == 2
    assert recs[-1]["kind"] == "run_end" and recs[-1]["clean"] is False


@pytest.mark.faultinject
def test_preemption_emits_emergency_checkpoint_preempt_and_resume(fresh_cfg, tmp_path):
    from distribuuuu_tpu import config

    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=3)
    c.FAULT.INJECT_PREEMPT_STEP = 5  # epoch 1, step 1
    with pytest.raises(SystemExit):
        trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    emergencies = [
        r for r in recs if r["kind"] == "checkpoint" and r["ckpt_kind"] == "emergency"
    ]
    assert [(r["epoch"], r["step"]) for r in emergencies] == [(1, 1)]
    assert emergencies[0]["synchronous"] is True
    preempts = [r for r in recs if r["kind"] == "preempt"]
    assert [(r["epoch"], r["step"]) for r in preempts] == [(1, 1)]
    assert recs[-1]["kind"] == "run_end" and recs[-1]["clean"] is False

    # relaunch: same OUT_DIR journal gains a second run with a resume marker
    config.reset_cfg()
    _tiny_run_cfg(config.cfg, tmp_path / "out", max_epoch=3)
    trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    assert sum(1 for r in recs if r["kind"] == "run_start") == 2
    resumes = [r for r in recs if r["kind"] == "resume"]
    assert [(r["epoch"], r["step"]) for r in resumes] == [(1, 1)]
    assert recs[-1]["kind"] == "run_end" and recs[-1]["clean"] is True


def test_preemption_hooks_fire_once_and_are_deduped():
    calls = []

    def hook():
        calls.append(1)

    resilience.register_preemption_hook(hook)
    resilience.register_preemption_hook(hook)  # deduped
    try:
        resilience.request_preemption("test")
        resilience.request_preemption("test again")  # flag already set: no refire
        assert calls == [1]
    finally:
        resilience.unregister_preemption_hook(hook)
        resilience.clear_preemption()


def test_setup_logger_emits_journal_path_and_registers_commit(tmp_path):
    import glob

    from distribuuuu_tpu import logging as dtpu_logging

    dtpu_logging.setup_logger(str(tmp_path), 0, journal_path="/some/journal.jsonl")
    try:
        assert dtpu_logging.commit_logs in resilience._preemption_hooks
        dtpu_logging.commit_logs()  # local handlers: flush, never raise
        logs = glob.glob(str(tmp_path / "*.log"))
        assert logs
        with open(logs[0]) as f:
            assert "telemetry journal: /some/journal.jsonl" in f.read()
    finally:
        resilience.unregister_preemption_hook(dtpu_logging.commit_logs)


# ---------------------------------------------------------------------------
# Profiler windows
# ---------------------------------------------------------------------------

def test_profile_at_steps_config_window(fresh_cfg, tmp_path):
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=1)
    c.OBS.PROFILE_AT_STEPS = [1]
    c.OBS.PROFILE_STEPS = 2
    trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    profiles = [r for r in recs if r["kind"] == "profile"]
    assert len(profiles) == 1
    p = profiles[0]
    assert p["gstep"] == 1 and p["steps"] == 2 and p["trigger"] == "config"
    assert os.path.isdir(p["logdir"])  # raw trace kept for offline tooling


def test_sigusr1_triggers_profile_window(fresh_cfg, tmp_path):
    c = _tiny_run_cfg(fresh_cfg, tmp_path / "out", max_epoch=1)
    c.OBS.PROFILE_STEPS = 2
    assert obs.install_sigusr1_handler()
    os.kill(os.getpid(), signal.SIGUSR1)  # delivered before the train loop
    assert obs_profiler.profile_requested()
    trainer.train_model()
    recs = _records(tmp_path / "out")
    _assert_valid(recs)
    profiles = [r for r in recs if r["kind"] == "profile"]
    assert len(profiles) == 1 and profiles[0]["trigger"] == "sigusr1"
    assert profiles[0]["steps"] == 2
    assert not obs_profiler.profile_requested()  # request consumed


# ---------------------------------------------------------------------------
# Summarize CLI (golden)
# ---------------------------------------------------------------------------

_GOLDEN_RECORDS = [
    {"ts": 0.0, "kind": "run_start", "run_id": "r1", "arch": "resnet50",
     "hosts": 1, "devices": 8, "local_devices": 8, "platform": "tpu",
     "device_kind": "TPU v5 lite", "global_batch": 2048,
     "config_fingerprint": "deadbeef0123", "jax_version": "0.4.37"},
    {"ts": 10.0, "kind": "window", "epoch": 0, "step": 0, "gstep": 0,
     "steps": 30, "skipped": 0, "lr": 0.2, "step_time": 0.25,
     "data_time": 0.01, "imgs_per_sec": 8192.0, "goodput": 0.5,
     "warmup": True, "loss": 6.9, "acc1": 0.1, "acck": 0.5, "mfu": None},
    {"ts": 20.0, "kind": "window", "epoch": 0, "step": 30, "gstep": 30,
     "steps": 30, "skipped": 1, "lr": 0.2, "step_time": 0.2,
     "data_time": 0.01, "imgs_per_sec": 10240.0, "goodput": 0.9,
     "warmup": False, "loss": 5.5, "acc1": 1.0, "acck": 4.0, "mfu": 0.412},
    {"ts": 30.0, "kind": "epoch_train", "epoch": 0, "steps": 60, "skipped": 1,
     "wall_s": 30.0, "imgs_per_sec": 9000.0, "goodput": 0.9},
    {"ts": 31.0, "kind": "fault_skipped_steps", "epoch": 0, "count": 1},
    {"ts": 35.0, "kind": "eval", "epoch": 0, "acc1": 34.2, "acck": 61.0,
     "loss": 3.2, "wall_s": 5.0, "samples": 50000.0},
    {"ts": 36.0, "kind": "checkpoint", "ckpt_kind": "epoch", "epoch": 0,
     "path": "/exp/checkpoints/ckpt_ep_001", "wall_s": 0.8, "synchronous": False},
    {"ts": 37.0, "kind": "counters", "scope": "run",
     "counters": {"/jax/compilation_cache/compile_requests_use_cache": 4},
     "durations": {"/jax/core/compile/backend_compile_duration":
                   {"count": 3, "total_s": 42.5}},
     "waits": {"decode_wait_s": 1.25}},
    {"ts": 38.0, "kind": "memory", "epoch": 0, "live_arrays": 321,
     "live_bytes": 2_500_000},
    {"ts": 39.0, "kind": "profile", "gstep": 40, "steps": 5,
     "logdir": "/exp/profile/gstep_000040", "trigger": "sigusr1",
     "device_ms_per_step": 201.5,
     "top_ops": [{"op": "fusion.1", "ms_per_step": 80.2, "pct": 39.8}]},
    {"ts": 40.0, "kind": "run_end", "best_acc1": 34.2, "epochs": 1,
     "wall_s": 40.0, "goodput": 0.88, "total_skipped": 1, "clean": True},
]


def test_summarize_golden_output(tmp_path, capsys):
    _assert_valid(_GOLDEN_RECORDS)  # the golden journal obeys its own schema
    report = render(_GOLDEN_RECORDS)
    for expected in [
        "run r1: resnet50 on 8xTPU v5 lite (1 host(s)), global batch 2048, "
        "config deadbeef0123",
        "result: best Acc@1 34.200 over 1 epoch(s) in 40.0s, goodput 88.0%, clean exit",
        "    0 |    60 |      10240.0 | 0.2000s / 0.2000s |  41.20% |       1",
        "eval[0]: Acc@1 34.200  Acc@k 61.000  (5.0s, 50000 samples)",
        "compiles: 3 backend compile(s), 42.5s total",
        "host waits: decode_wait_s=1.2s",
        "faults: skipped_steps=1  emergency_ckpts=0  preempts=0  resumes=0  aborts=0",
        "checkpoints: 1 save(s) (avg dispatch 0.80s), 0 restore(s)",
        "memory (last epoch): 321 live arrays, 2.5 MB",
        "profile @ gstep 40 (5 step(s), trigger=sigusr1): /exp/profile/gstep_000040",
        "device op time: 201.50 ms/step",
        "   39.8%    80.200 ms  fusion.1",
    ]:
        assert expected in report, f"missing line: {expected!r}\n--- report ---\n{report}"

    # the CLI renders the same thing from disk and exits 0
    path = str(tmp_path / "g.jsonl")
    with open(path, "w") as f:
        for r in _GOLDEN_RECORDS:
            f.write(json.dumps(r) + "\n")
    assert obs_cli(["summarize", path]) == 0
    assert "run r1: resnet50" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Invariants: one compile per shape, lint-clean instrumentation
# ---------------------------------------------------------------------------

def test_instrumented_loop_compiles_exactly_once(fresh_cfg, tmp_path):
    """The full telemetry surface — step-cost lowering, windows, epoch ends,
    counters — around a jitted train step must leave its compile cache at
    exactly one entry across two epochs (the acceptance criterion)."""
    from distribuuuu_tpu import optim
    from distribuuuu_tpu.models import build_model

    fresh_cfg.OUT_DIR = str(tmp_path)
    mesh = data_mesh(-1)
    model = build_model("obs_tiny", num_classes=4, dtype=jnp.float32)
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, 8)
    step = trainer.make_train_step(model, tx, mesh, topk=2)
    n = 2 * jax.device_count()
    rng = np.random.default_rng(0)
    batch = {
        "image": jax.device_put(
            rng.integers(0, 256, (n, 8, 8, 3), dtype=np.uint8),
            NamedSharding(mesh, P("data", None, None, None)),
        ),
        "label": jax.device_put(
            rng.integers(0, 4, n).astype(np.int32), NamedSharding(mesh, P("data"))
        ),
    }
    lr = jnp.asarray(0.1, jnp.float32)
    key = jax.random.PRNGKey(1)
    tel = obs.start_run(str(tmp_path), is_primary=True)
    assert tel.enabled
    try:
        with CompileGuard(step, exact=1, name="train_step"):
            tel.capture_step_cost(step, state, batch, lr, key)
            for epoch in range(2):
                tel.epoch_start(epoch)
                window = []
                for it in range(4):
                    state, m = step(state, batch, lr, key)
                    window.append(m)
                # one fetch per 4-step epoch: the PRINT_FREQ boundary idiom,
                # compressed for the test  # dtpu-lint: disable=DT001
                vals = jax.device_get(window)
                tel.window(
                    epoch=epoch, step=3, gstep=epoch * 4 + 3, steps=len(vals),
                    skipped=0, lr=0.1, wall_s=0.05, data_time=0.0,
                    imgs=float(len(vals) * n), warmup=epoch == 0,
                    loss=float(sum(v["loss_sum"] for v in vals)),
                )
                tel.epoch_end(
                    epoch=epoch, steps=4, skipped=0, wall_s=0.05, imgs=4.0 * n
                )
        assert tel.step_flops and tel.step_flops > 0
    finally:
        obs.end_run(best_acc1=0.0, epochs=2)
    recs = _records(tmp_path)
    _assert_valid(recs)
    assert _kinds(recs).count("window") == 2


def test_obs_package_and_instrumented_modules_lint_clean_without_baseline():
    """Stricter than the repo-wide (baselined) invariant: the obs package and
    every module this PR instrumented must be clean with NO baseline — new
    instrumentation cannot hide behind grandfathered findings."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    targets = [
        os.path.join(root, "distribuuuu_tpu", "obs"),
        os.path.join(root, "distribuuuu_tpu", "trainer.py"),
        os.path.join(root, "distribuuuu_tpu", "checkpoint.py"),
        os.path.join(root, "distribuuuu_tpu", "logging.py"),
        os.path.join(root, "distribuuuu_tpu", "resilience.py"),
        os.path.join(root, "distribuuuu_tpu", "data", "loader.py"),
    ]
    findings = lint_paths(targets)
    assert findings == [], [str(f) for f in findings]
