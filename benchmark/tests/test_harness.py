"""CPU rehearsal of the harness: every cell's files walk through ``train_epoch`` at tiny sizes,
the result line keeps the contract's keys, files added by name are found, faults read as not
correct, and without the switch a run on a CPU fails with no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RUN = [sys.executable, os.path.join(BENCH, "run.py")]
PREFIX = "cpu_rehearsal."


def run(args, env=None, cwd=ROOT, timeout=900):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("XLA_FLAGS", None)
    full_env.update(env or {})
    return subprocess.run(args, capture_output=True, text=True, cwd=cwd, env=full_env, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cells():
    """Every cell that has a file: those of ``BENCHMARK.json`` and those kept for a later PR
    (``resnet50.train_dp4`` is rehearsed here on four virtual devices though it is not yet proved on the chip)."""
    out = []
    for name in sorted(os.listdir(os.path.join(BENCH, "workloads"))):
        with open(os.path.join(BENCH, "workloads", name)) as f:
            out.append(json.load(f))
    return out


@pytest.mark.parametrize("cell", cells(), ids=lambda c: c["name"])
def test_rehearsal_walks_the_cell(cell):
    traced = cell["chips"] == 1 and cell["config"] == "resnet50"
    proc = run(RUN + ["--workload", cell["name"], "--seed", str(2**31 + 17), "--seconds", "1",
                      "--trace", "1" if traced else "0", "--rehearse-cpu"])
    line = last_line(proc)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"  # the numbers compared come last in the line
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == cell["chips"]
    # no number from a CPU stands under a device metric's name
    assert line["metrics"] and all(name.startswith(PREFIX) for name in line["metrics"])
    if traced:
        assert PREFIX + "data_wait_pct" in line["metrics"]
        assert PREFIX + "step_mfu_pct" not in line["metrics"]  # no device trace on a CPU: nothing to read
    else:
        assert set(line["metrics"]) == {PREFIX + "img_per_s_per_chip", PREFIX + "setup_s"}
    for name, pair in line["compared"].items():
        assert pair["value"] <= pair["limit"], name
    # each number compared stands beside its limit at the end of standard error too
    assert f"compared {list(line['compared'])[-1]}:" in proc.stderr.strip().splitlines()[-1]


def test_without_the_switch_a_cpu_run_fails_and_prints_no_result():
    proc = run(RUN + ["--workload", "resnet50.train", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_bare_directory_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    proc = run([sys.executable, "benchmark/run.py", "--workload", "resnet50.train", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse-cpu"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_cell_a_traffic_mix_and_a_metric_added_as_files_are_found_by_name():
    """The added mix replays one batch under the loader's marker: a mode no committed cell uses yet."""
    cell_path = os.path.join(BENCH, "workloads", "zz_added.train_replay.json")
    mix_path = os.path.join(BENCH, "mixes", "zz_train_replay.json")
    metric_path = os.path.join(BENCH, "layer_metrics", "zz_steps_counted.py")
    with open(os.path.join(BENCH, "workloads", "vit_b16.train.json")) as f:
        cell = json.load(f)
    cell.update(name="zz_added.train_replay", traffic="zz_train_replay")
    try:
        with open(cell_path, "w") as f:
            json.dump(cell, f)
        with open(mix_path, "w") as f:
            json.dump({"name": "zz_train_replay", "pool_batches": 4, "input_mode": "replay"}, f)
        with open(metric_path, "w") as f:
            f.write('NAME = "zz_steps_counted"\nUNIT = "steps"\n\n\ndef read(ctx):\n'
                    '    return ctx["window"]["steps"]\n')
        proc = run(RUN + ["--workload", "zz_added.train_replay", "--seed", "5", "--seconds", "1",
                          "--trace", "1", "--rehearse-cpu"])
        line = last_line(proc)
        assert line["correct"] is True
        assert line["metrics"][PREFIX + "zz_steps_counted"]["value"] == line["attempted"]
        # a replayed batch ships once: the prefetch thread spends next to nothing on transfers
        assert line["host"]["h2d_transfer_s"] < 0.5 * line["window_s"]
    finally:
        for path in (cell_path, mix_path, metric_path):
            if os.path.exists(path):
                os.remove(path)


@pytest.mark.parametrize("workload,fault,correct", [
    ("resnet50.train", "unchanged", False),
    ("resnet50.train", "stats_unchanged", False),
    ("resnet50.train", "half_batch", False),
    ("resnet50.train_dp4", "no_exchange", False),
    ("vit_b16.train", "unchanged", False),
    ("vit_b16.train", "half_batch", False),
])
def test_a_broken_timed_path_reads_as_not_correct(workload, fault, correct):
    proc = run([sys.executable, os.path.join(HERE, "_drive_fault.py"), workload, fault])
    assert last_line(proc)["correct"] is correct


def test_benchmark_json_names_what_the_files_hold():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["paths"] == ["benchmark"] and bench["command"] == ["python3", "benchmark/run.py"]
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads", cell["name"] + ".json")) as f:
            data = json.load(f)
        assert (data["config"], data["traffic"], data["chips"], data["why"]) == (
            cell["config"], cell["traffic"], cell["chips"], cell["why"])
        assert data["limits"], "a cell without limits compares nothing"
        with open(os.path.join(BENCH, "mixes", cell["traffic"] + ".json")) as f:
            assert json.load(f)["name"] == cell["traffic"]
    for config in bench["configs"]:
        assert config["file"] == f"benchmark/configs/{config['name']}.json"
        with open(os.path.join(ROOT, config["file"])) as f:
            assert json.load(f)["source"] == config["source"]
    readers = {n[:-3] for n in os.listdir(os.path.join(BENCH, "layer_metrics")) if n.endswith(".py")}
    assert {m["name"] for m in bench["per_layer"]} <= readers  # a reader may wait for its first cell
