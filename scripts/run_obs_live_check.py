"""dtpu-obs live-telemetry smoke check — the CI `obs-live` job's driver
(and a local one-command sanity run, docs/OBSERVABILITY.md "Live metrics").

What it proves, end to end on CPU:

1. a 2-step tiny train emits a journal carrying the new live-plane signals
   (per-window ``data_wait_frac``, train-side ``span`` records);
2. the export sidecar (`ObsPlane`: incremental JournalTailer -> live
   aggregator -> /metrics) serves Prometheus text over HTTP, and the
   goodput + step-rate gauges are present and FINITE;
3. a deliberately-low goodput-floor alarm rule fires, lands as a typed
   ``alarm`` record in the sidecar's ``.part4000`` supervisory part, and
   shows as active in the scrape;
4. the whole reassembled journal — run records + spans + alarm part —
   schema-validates (``obs validate``).

Exit 0 = all of the above held. Usage:

    python scripts/run_obs_live_check.py [--out-dir DIR]
"""

import argparse
import math
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _parse_prom(text: str) -> dict:
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            metrics[name] = float(value)
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="/tmp/obs_live_smoke")
    args = ap.parse_args()
    out_dir = args.out_dir

    from distribuuuu_tpu import config, trainer
    from distribuuuu_tpu.obs.__main__ import main as obs_cli
    from distribuuuu_tpu.obs.alarms import AlarmEngine, parse_alarm_rules
    from distribuuuu_tpu.obs.exporter import SIDECAR_PART, ObsPlane
    from distribuuuu_tpu.obs.journal import ValidatedJournal, read_journal
    from distribuuuu_tpu.obs.telemetry import journal_path

    # 1. tiny 2-step CPU train (DUMMY_INPUT: no dataset needed)
    config.reset_cfg()
    c = config.cfg
    c.MODEL.ARCH = "resnet18"
    c.MODEL.DTYPE = "float32"
    c.MODEL.DUMMY_INPUT = True
    c.TRAIN.BATCH_SIZE = 2
    c.TRAIN.IM_SIZE = 32
    c.TEST.IM_SIZE = 32
    c.TEST.CROP_SIZE = 32
    c.TEST.BATCH_SIZE = 2
    c.TRAIN.DUMMY_EPOCH_SAMPLES = 32  # // (2 * 8 devices) = 2 steps/epoch
    c.TRAIN.PRINT_FREQ = 1
    c.OPTIM.MAX_EPOCH = 1
    c.OPTIM.WARMUP_EPOCHS = 0
    c.RNG_SEED = 1
    c.OUT_DIR = out_dir
    trainer.train_model()

    journal = journal_path(out_dir)
    windows = [r for r in read_journal(journal) if r["kind"] == "window"]
    assert windows, "train journaled no windows"
    assert all("data_wait_frac" in w for w in windows), "data_wait_frac missing"
    spans = [r for r in read_journal(journal) if r["kind"] == "span"]
    assert {s["phase"] for s in spans} >= {"data_wait", "throttle", "dispatch", "fetch_wait", "host"}, spans
    print(f"train OK: {len(windows)} window(s), {len(spans)} span(s)")

    # 2. + 3. the export sidecar with a deliberately-unmeetable goodput
    # floor (a 1-epoch CPU smoke spends nearly all its life compiling, so
    # goodput < 0.999 is guaranteed) — the alarm must fire
    alarm_journal = ValidatedJournal(
        f"{journal}.part{SIDECAR_PART}", label="obs-live sidecar"
    )
    plane = ObsPlane(
        journal,
        alarm_event=alarm_journal.event,
        alarm_engine=AlarmEngine(
            parse_alarm_rules(["goodput_floor=goodput<0.999"]),
            alarm_journal.event,
        ),
        port=0,  # ephemeral: CI must not collide on a fixed port
        interval_s=0.2,
    )
    plane.start()
    try:
        url = f"http://127.0.0.1:{plane.server.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            text = resp.read().decode()
    finally:
        plane.stop()
        alarm_journal.close()
    metrics = _parse_prom(text)
    for gauge in ("dtpu_goodput", "dtpu_imgs_per_sec", "dtpu_step_time"):
        assert gauge in metrics, f"{gauge} missing from scrape:\n{text}"
        assert math.isfinite(metrics[gauge]), f"{gauge} not finite"
    assert metrics["dtpu_steps_total"] >= 2
    print(
        f"scrape OK: goodput {metrics['dtpu_goodput']:.4f}, "
        f"{metrics['dtpu_imgs_per_sec']:.1f} img/s, "
        f"{int(metrics['dtpu_steps_total'])} steps"
    )
    assert metrics["dtpu_alarm_active"] >= 1.0, "goodput-floor alarm did not fire"
    alarms = [r for r in read_journal(journal) if r["kind"] == "alarm"]
    assert any(r["rule"] == "goodput_floor" for r in alarms), alarms
    print(f"alarm OK: {len(alarms)} typed alarm record(s) in .part{SIDECAR_PART}")

    # 4. the whole journal (train + spans + sidecar alarm part) validates
    rc = obs_cli(["validate", journal])
    assert rc == 0, "obs validate failed"
    print("obs-live smoke: ALL CHECKS PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
