"""Telemetry core: the one handle the whole stack reports through.

`Telemetry` owns the metrics journal (obs/journal.py), the jax.monitoring
bridge (obs/monitors.py), the step-cost/MFU state (obs/flops.py) and the
wall-clock goodput ledger. The trainer drives the per-window/per-epoch
cadence; every other layer (checkpoint saves, loader waits, resilience
events) reports through `current()` — a module-level handle that is a no-op
`NullTelemetry` outside a run, so instrumented code never needs to know
whether observability is on, or whether it is rank 0.

Sync discipline (the reason this file exists instead of a metrics callback):
telemetry adds **zero** device syncs. Window records are computed from the
values the trainer already fetched at its PRINT_FREQ boundary; counters are
host integers; the step cost comes from *lowering* (tracing) the step, never
compiling or running it; memory snapshots walk host-side buffer metadata at
epoch boundaries. The instrumented trainer still compiles exactly once per
shape and stays dtpu-lint DT001-clean — both pinned in tests/test_obs.py.

Goodput: productive step seconds ÷ elapsed run seconds. Productive time is
the wall time of steady-state windows scaled by their non-skipped step
fraction; compile/warmup windows, eval, checkpoint stalls and preemption
gaps all count in the denominator only — so the number honestly reports
"fraction of this run's lifetime spent making optimizer progress".
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
import uuid

import jax

from distribuuuu_tpu.logging import logger
from distribuuuu_tpu.obs import flops as _flops
from distribuuuu_tpu.obs import memory as _memory
from distribuuuu_tpu.obs.journal import Journal, validate_record
from distribuuuu_tpu.obs.monitors import MonitoringBridge


def _obs_cfg():
    from distribuuuu_tpu.config import cfg

    return cfg.OBS if "OBS" in cfg else None


def journal_path(out_dir: str) -> str:
    """Where the run's journal lives (OUT_DIR/OBS.JOURNAL)."""
    from distribuuuu_tpu.runtime import pathio

    oc = _obs_cfg()
    name = oc.JOURNAL if oc is not None else "telemetry.jsonl"
    return pathio.join(out_dir, name)


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class NullTelemetry:
    """Inert telemetry: every reporting site works unconditionally (non-rank-0
    processes, OBS.ENABLED=False, library use outside train_model)."""

    enabled = False
    journal = None
    journal_path = None
    step_flops = None

    def event(self, kind: str, **fields) -> None:
        pass

    def span(self, trace_id: str, phase: str, ms: float, **fields) -> None:
        pass

    def trace_tag(self, tag: str) -> str:
        return ""

    def add_wait(self, name: str, seconds: float) -> None:
        pass

    def epoch_start(self, epoch: int) -> None:
        pass

    def window(self, **kw) -> None:
        pass

    def epoch_end(self, **kw) -> None:
        pass

    def capture_step_cost(self, step_fn, *args) -> None:
        pass

    @property
    def wants_step_cost(self) -> bool:
        return False

    def commit(self) -> None:
        pass

    def close(self) -> None:
        pass


_NULL = NullTelemetry()
_CURRENT: "Telemetry | NullTelemetry" = _NULL


def current() -> "Telemetry | NullTelemetry":
    """The active run's telemetry (NullTelemetry when none)."""
    return _CURRENT


def set_current(tel: "Telemetry | NullTelemetry | None") -> None:
    global _CURRENT
    _CURRENT = tel if tel is not None else _NULL


class Telemetry:
    """Rank-0 journaling telemetry for one training/eval run."""

    enabled = True

    def __init__(self, out_dir: str, *, run_tic: float | None = None):
        oc = _obs_cfg()
        self.journal_path = journal_path(out_dir)
        self.journal = Journal(
            self.journal_path, fsync=bool(oc.FSYNC) if oc is not None else False
        )
        self.bridge = MonitoringBridge().install()
        self._run_tic = run_tic if run_tic is not None else time.time()
        self._productive_s = 0.0
        self._total_skipped = 0
        self._mfu_enabled = bool(oc.MFU) if oc is not None else True
        self._peak = _flops.peak_flops_per_device(
            override_tflops=oc.PEAK_TFLOPS_PER_DEVICE if oc is not None else 0.0
        )
        self._memory_snapshots = bool(oc.MEMORY_SNAPSHOTS) if oc is not None else True
        self._device_count = jax.device_count()
        self.step_flops: float | None = None
        self._step_cost_tried = not self._mfu_enabled
        self._epoch_step_times: list[float] = []
        self._epoch_mark = self.bridge.snapshot()
        self._waits: dict[str, float] = {}
        self._waits_mark: dict[str, float] = {}
        # separate per-WINDOW marks (data_wait_frac) so the per-epoch
        # counters delta above is undisturbed
        self._win_waits_mark: dict[str, float] = {}
        self._wait_lock = threading.Lock()
        # run-scoped trace tag for train-side spans (obs/trace.py)
        self._trace = uuid.uuid4().hex[:8]
        self._train_spans = bool(oc.TRAIN_SPANS) if oc is not None else True

    # -- journal ------------------------------------------------------------

    def event(self, kind: str, **fields) -> None:
        """Append one typed record (ts added, schema-validated)."""
        record = {"ts": time.time(), "kind": kind, **fields}
        errors = validate_record(record)
        if errors:
            # an invalid record is an obs bug; surface it loudly in logs (and
            # in tests, which validate the whole journal) but never kill the
            # run that was being observed
            logger.error(f"telemetry: invalid {kind!r} record dropped: {errors}")
            return
        self.journal.append(record)

    # -- tracing -------------------------------------------------------------

    def trace_tag(self, tag: str) -> str:
        """A run-scoped trace id for train-side spans (``train-<run>-<tag>``)."""
        return f"train-{self._trace}-{tag}"

    def span(self, trace_id: str, phase: str, ms: float, **fields) -> None:
        """One typed ``span`` record (obs/trace.py; host wall only)."""
        from distribuuuu_tpu.obs import trace as _trace

        self.event("span", **_trace.span_fields(trace_id, phase, ms, **fields))

    # -- cross-thread counters ----------------------------------------------

    def add_wait(self, name: str, seconds: float) -> None:
        """Accumulate a named host-wait counter (loader decode wait, H2D
        transfer time, ...). Thread-safe: called from producer threads."""
        with self._wait_lock:
            self._waits[name] = self._waits.get(name, 0.0) + float(seconds)

    def _waits_delta(self) -> dict[str, float]:
        with self._wait_lock:
            delta = {
                k: round(v - self._waits_mark.get(k, 0.0), 6)
                for k, v in self._waits.items()
                if v - self._waits_mark.get(k, 0.0) > 0
            }
            self._waits_mark = dict(self._waits)
        return delta

    def _window_wait_delta(self, name: str) -> float:
        """Per-window delta of one wait counter (window-scoped marks — the
        per-epoch ``counters`` delta keeps its own)."""
        with self._wait_lock:
            total = self._waits.get(name, 0.0)
            delta = total - self._win_waits_mark.get(name, 0.0)
            self._win_waits_mark[name] = total
        return max(0.0, delta)

    # -- step cost / MFU -----------------------------------------------------

    @property
    def wants_step_cost(self) -> bool:
        return not self._step_cost_tried

    def capture_step_cost(self, step_fn, *args) -> None:
        """One-shot analytical pricing of the jitted step (lowering only — no
        compile, no execution; see obs/flops.py). Safe to call every step;
        only the first call does work."""
        if self._step_cost_tried:
            return
        self._step_cost_tried = True
        cost = _flops.lowered_step_cost(step_fn, *args)
        if cost is not None:
            self.step_flops = cost["flops"]
            logger.info(
                f"step cost (XLA model): {self.step_flops:.3e} flops/global step"
                + (
                    f", peak {self._peak * self._device_count / 1e12:.1f} TFLOP/s fleet"
                    if self._peak
                    else " (hardware peak unknown: MFU omitted)"
                )
            )

    # -- training cadence ----------------------------------------------------

    def epoch_start(self, epoch: int) -> None:
        self._epoch_step_times = []
        self._epoch_mark = self.bridge.snapshot()
        # rebase the per-WINDOW wait marks: the eval loop rides the same
        # prefetch_to_device consumer and its q.get() waits land in the
        # run-global counters — without the rebase the whole inter-epoch
        # eval wait would be billed to the next epoch's first window as a
        # false data_wait_frac=1.0 starvation signal
        with self._wait_lock:
            self._win_waits_mark = dict(self._waits)

    def window(
        self,
        *,
        epoch: int,
        step: int,
        gstep: int,
        steps: int,
        skipped: int,
        lr: float,
        wall_s: float,
        data_time: float,
        imgs: float,
        warmup: bool,
        loss: float | None = None,
        acc1: float | None = None,
        acck: float | None = None,
        counters: dict | None = None,
    ) -> None:
        """One PRINT_FREQ window, fed from the trainer's existing boundary
        fetch. Derives step time, percentiles (over this epoch's steady-state
        windows), throughput, goodput and MFU. ``counters``: the step's own
        counters of `journal.WINDOW_COUNTERS`, from the same fetch."""
        steps = max(1, steps)
        wall_s = max(wall_s, 1e-9)
        step_time = wall_s / steps
        if not warmup:
            self._epoch_step_times.append(step_time)
            self._productive_s += wall_s * (steps - skipped) / steps
        self._total_skipped += skipped
        times = sorted(self._epoch_step_times) or [step_time]
        mfu_val = (
            _flops.mfu(self.step_flops, step_time, self._device_count, self._peak)
            if not warmup
            else None
        )
        # producer-starvation fraction: time the step loop spent blocked on
        # q.get() in prefetch_to_device (the ``data_wait_s`` counter the
        # loader feeds from the consumer thread) over this window's wall —
        # the data-wait alarm's signal, measured where the stall is felt
        data_wait_s = self._window_wait_delta("data_wait_s")
        data_wait_frac = min(1.0, data_wait_s / wall_s)
        # where the rest of the wall went (obs/trace.py phases): blocked in
        # the step's first launches by the runtime's full queue, inside the
        # train_step call, inside the boundary fetch, or in none — a slow
        # window with the loss in throttle_s or fetch_wait_s lost it on the
        # device or its feed, one with the loss in dispatch_s in the
        # runtime's launch path, one with the loss in none to a host that
        # was not running the loop
        throttle_s = self._window_wait_delta("throttle_s")
        dispatch_s = self._window_wait_delta("dispatch_s")
        fetch_wait_s = self._window_wait_delta("fetch_wait_s")
        self.event(
            "window",
            epoch=epoch,
            step=step,
            gstep=gstep,
            steps=steps,
            skipped=skipped,
            lr=float(lr),
            step_time=round(step_time, 6),
            step_time_p50=round(_percentile(times, 0.50), 6),
            step_time_p90=round(_percentile(times, 0.90), 6),
            step_time_max=round(times[-1], 6),
            data_time=round(float(data_time), 6),
            data_wait_frac=round(data_wait_frac, 6),
            throttle_s=round(throttle_s, 6),
            dispatch_s=round(dispatch_s, 6),
            fetch_wait_s=round(fetch_wait_s, 6),
            imgs_per_sec=round(imgs / wall_s, 3),
            goodput=round(self.goodput(), 6),
            mfu=round(mfu_val, 6) if mfu_val is not None else None,
            flops_per_step=self.step_flops,
            warmup=bool(warmup),
            loss=float(loss) if loss is not None else None,
            acc1=float(acc1) if acc1 is not None else None,
            acck=float(acck) if acck is not None else None,
            **(counters or {}),
        )
        if self._train_spans:
            # the window IS the trace: its wall splits into the loop's four
            # measured phases and ``host``, the rest — all from values already
            # on the host, zero syncs
            tid = self.trace_tag(f"g{gstep}")
            phases = {
                "data_wait": data_wait_s,
                "throttle": throttle_s,
                "dispatch": dispatch_s,
                "fetch_wait": fetch_wait_s,
            }
            phases["host"] = max(0.0, wall_s - sum(phases.values()))
            for name, seconds in phases.items():
                self.span(tid, name, 1000.0 * seconds, gstep=gstep, epoch=epoch)

    def epoch_end(
        self, *, epoch: int, steps: int, skipped: int, wall_s: float, imgs: float
    ) -> None:
        """Epoch summary + typed fault events + counter deltas + memory."""
        self.event(
            "epoch_train",
            epoch=epoch,
            steps=steps,
            skipped=skipped,
            wall_s=round(wall_s, 3),
            imgs_per_sec=round(imgs / max(wall_s, 1e-9), 3),
            goodput=round(self.goodput(), 6),
        )
        if skipped:
            self.event("fault_skipped_steps", epoch=epoch, count=skipped)
        snap = self.bridge.snapshot()
        delta = MonitoringBridge.delta(snap, self._epoch_mark)
        self._epoch_mark = snap
        self.event(
            "counters",
            scope="epoch",
            epoch=epoch,
            counters=delta["counters"],
            durations=delta["durations"],
            waits=self._waits_delta(),
        )
        if self._memory_snapshots:
            self.event("memory", epoch=epoch, **_memory.snapshot())

    def goodput(self) -> float:
        elapsed = max(time.time() - self._run_tic, 1e-9)
        return min(1.0, self._productive_s / elapsed)

    # -- durability ----------------------------------------------------------

    def commit(self) -> None:
        """Durability point for the preemption path (journal.commit)."""
        try:
            self.journal.commit()
        except Exception as exc:
            logger.warning(f"telemetry journal commit failed: {exc!r}")

    def close(self) -> None:
        self.bridge.close()
        self.journal.close()


# ---------------------------------------------------------------------------
# Run lifecycle
# ---------------------------------------------------------------------------

def _config_fingerprint() -> str:
    from distribuuuu_tpu.config import cfg

    try:
        text = cfg.dump()
    except Exception:
        text = repr(cfg)
    return hashlib.sha256(text.encode()).hexdigest()[:12]


def start_run(
    out_dir: str, *, is_primary: bool = True, run_tic: float | None = None
) -> "Telemetry | NullTelemetry":
    """Open the run's telemetry and make it `current()`.

    Only the primary process journals (OBS.ENABLED gates globally); every
    other process gets the NullTelemetry so call sites stay unconditional.
    Emits the ``run_start`` record (config fingerprint, topology) and
    registers the journal's durability hook on the resilience preemption
    path — a preempted run keeps its telemetry the same way it keeps its
    emergency checkpoint.
    """
    from distribuuuu_tpu import resilience
    from distribuuuu_tpu.config import cfg

    end_run()  # a leftover handle from a crashed/aborted run in-process
    oc = _obs_cfg()
    if not is_primary or oc is None or not oc.ENABLED:
        set_current(_NULL)
        return _NULL
    tel = Telemetry(out_dir, run_tic=run_tic)
    set_current(tel)
    dev = jax.devices()[0]
    tel.event(
        "run_start",
        run_id=f"{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:8]}",
        arch=cfg.MODEL.ARCH,
        hosts=jax.process_count(),
        devices=jax.device_count(),
        local_devices=jax.local_device_count(),
        platform=dev.platform,
        device_kind=dev.device_kind,
        global_batch=int(
            cfg.TRAIN.BATCH_SIZE * cfg.TRAIN.ACCUM_STEPS * jax.device_count()
        ),
        config_fingerprint=_config_fingerprint(),
        jax_version=jax.__version__,
        peak_tflops_per_device=(tel._peak / 1e12) if tel._peak else None,
        out_dir=str(out_dir),
        pid=os.getpid(),
    )
    resilience.register_preemption_hook(tel.commit)
    return tel


def end_run(*, best_acc1: float = 0.0, epochs: int = 0, clean: bool = True) -> None:
    """Emit ``run_end`` (with the run's resilience totals) and close the
    journal. Idempotent; called from train_model's finally."""
    global _CURRENT
    tel = _CURRENT
    if not tel.enabled:
        set_current(_NULL)
        return
    from distribuuuu_tpu import resilience

    snap = tel.bridge.snapshot()
    tel.event(
        "counters",
        scope="run",
        counters=snap["counters"],
        durations=snap["durations"],
        waits=dict(tel._waits),
    )
    tel.event(
        "run_end",
        best_acc1=float(best_acc1),
        epochs=int(epochs),
        wall_s=round(time.time() - tel._run_tic, 3),
        goodput=round(tel.goodput(), 6),
        total_skipped=int(resilience.RUN_STATS.total_skipped),
        clean=bool(clean),
    )
    tel.close()
    # drop the journal's durability hook: a later run registers its own
    # handle, and dead hooks must not accumulate across relaunch tests
    resilience.unregister_preemption_hook(tel.commit)
    set_current(_NULL)
