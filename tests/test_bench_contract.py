"""bench.py driver contract: exactly one parseable JSON line, required keys.

A formatting regression or harness crash would cost a measurement its
evidence, so the contract is pinned by a real subprocess run of both modes on
the fake CPU mesh (tiny shapes via the DTPU_BENCH_* envs), and the device
fields by an in-process check of both lines.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_KEYS = {
    "metric", "value", "unit", "vs_baseline", "platform", "device_kind", "device_count",
}


def _run_bench(extra_env):
    env = dict(
        os.environ,
        DTPU_BENCH_BATCH="4",
        DTPU_BENCH_IM_SIZE="32",
        # the contract under test is the JSON line, not the arch: resnet18
        # compiles ~3x faster than the production resnet50 default on this
        # 1-core box
        DTPU_BENCH_ARCH="resnet18",
        **extra_env,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "cpu_mesh_run.py"),
         os.path.join(REPO, "bench.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got: {lines}"
    return json.loads(lines[0])


@pytest.mark.slow
def test_bench_train_json_contract():
    rec = _run_bench({})
    assert set(rec) == BENCH_KEYS
    assert rec["platform"] == "cpu"
    assert rec["unit"] == "images/sec/chip"
    assert "train images/sec/chip" in rec["metric"]
    assert "resnet18" in rec["metric"]  # the arch label must track the env
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0


@pytest.mark.slow
def test_bench_eval_json_contract():
    rec = _run_bench({"DTPU_BENCH_EVAL": "1"})
    assert set(rec) == BENCH_KEYS
    assert "eval images/sec/chip" in rec["metric"]
    # the eval comparison point is an estimate, and the metric must say so
    assert "est" in rec["metric"]
    assert rec["value"] > 0


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


@pytest.mark.parametrize("line", ["fail", "normal"])
def test_bench_json_line_names_its_device(line, monkeypatch, capsys):
    """Both lines bench.py can print carry the device JAX reports — a rate
    measured on a CPU says ``"platform": "cpu"`` (in-process: no compile)."""
    import jax

    bench = _load_bench()
    monkeypatch.setenv("DTPU_PERFDB", "0")  # no registry write from a test
    if line == "fail":
        bench._fail_line("BENCH FAILED: RuntimeError")
    else:
        bench._print_metric("train", "resnet18", 32, 4, 1, 1.0, 20, baseline=400.0)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert set(rec) == BENCH_KEYS
    assert rec["platform"] == jax.devices()[0].platform == "cpu"
    assert rec["device_kind"] == jax.devices()[0].device_kind
    assert rec["device_count"] == jax.device_count()
    assert (rec["value"] == 0.0) == (line == "fail")
