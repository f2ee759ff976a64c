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


ADDED_INPUT = '''"""An input kind added as a file: the image kind's leaves drawn otherwise, and a leaf no other kind has."""
import numpy as np


def make_pool(seed, pool_batches, global_batch, settings):
    rng = np.random.default_rng([seed, 99])
    size, classes = int(settings["TRAIN"]["IM_SIZE"]), int(settings["MODEL"]["NUM_CLASSES"])
    return [{"image": rng.integers(64, 192, (global_batch, size, size, 3), dtype=np.uint8),
             "label": rng.integers(0, min(classes, 10), global_batch).astype(np.int32),
             "weight": np.ones((global_batch,), np.float32),
             "zz_row": np.arange(global_batch, dtype=np.int32) + 1000 * i} for i in range(pool_batches)]
'''

# the added reference is ViT's, and insists on getting the batch as the input kind made it
ADDED_REFERENCE_TAIL = '''

_vit_loss_fn = loss_fn


def loss_fn(params, stats, batch, precision="f32"):
    if batch["zz_row"].shape != batch["label"].shape:
        raise ValueError("the block of rows lost a leaf, or split one otherwise than its neighbours")
    return _vit_loss_fn(params, stats, batch, precision)
'''

ADDED_KERNEL = '''"""A kernel's cost added as a file."""


def cost(operands, results):
    (_, shape), = results
    return {"flops": 2.0 * shape[0] * shape[1], "bytes": 4 * shape[0] * shape[1], "matrix": False}
'''

ADDED_METRICS = {
    "zz_steps_counted": 'NAME = "zz_steps_counted"\nUNIT = "steps"\n\n\ndef read(ctx):\n    return ctx["window"]["steps"]\n',
    # what the harness does for a kernel call of the compiled step, on a made call: the CPU's step holds none
    "zz_kernel_flops": 'NAME = "zz_kernel_flops"\nUNIT = "flops"\n\n\ndef read(ctx):\n'
                       '    call = {"kernel": "zz_kernel", "operands": [("bf16", (8, 16))], "results": [("bf16", (8, 16))]}\n'
                       '    return ctx["roofline"].kernel_costs({"zz_kernel.1": call})["zz_kernel.1"]["flops"]\n',
}


def test_a_cell_a_traffic_mix_and_a_metric_added_as_files_are_found_by_name(tmp_path):
    """A configuration with its reference and its ``flops/`` file, an input kind, a kernel's cost, a traffic
    mix, a cell and two metrics, all added as files to a temporary copy of ``benchmark/`` in which no file
    that was there is touched, walk the rehearsal. The added mix replays one batch under the loader's
    marker: a mode no committed cell uses yet."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests", "tools"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    with open(os.path.join(BENCH, "workloads", "vit_b16.train.json")) as f:
        cell = json.load(f)
    cell.update(name="zz_vit.train_replay", config="zz_vit", traffic="zz_train_replay")
    with open(os.path.join(BENCH, "configs", "vit_b16.json")) as f:
        config = json.load(f)
    config.update(name="zz_vit", input="zz_rows")
    (bench / "workloads" / "zz_vit.train_replay.json").write_text(json.dumps(cell))
    (bench / "mixes" / "zz_train_replay.json").write_text(
        json.dumps({"name": "zz_train_replay", "pool_batches": 4, "input_mode": "replay"}))
    (bench / "configs" / "zz_vit.json").write_text(json.dumps(config))
    (bench / "inputs" / "zz_rows.py").write_text(ADDED_INPUT)
    (bench / "reference" / "zz_vit.py").write_text((bench / "reference" / "vit_b16.py").read_text() + ADDED_REFERENCE_TAIL)
    shutil.copy(bench / "flops" / "vit_b16.py", bench / "flops" / "zz_vit.py")
    (bench / "kernels" / "zz_kernel.py").write_text(ADDED_KERNEL)
    for name, text in ADDED_METRICS.items():
        (bench / "layer_metrics" / f"{name}.py").write_text(text)
    # the copy's own run.py puts the copy first on the path; the program comes from the repo
    proc = run([sys.executable, str(bench / "run.py"), "--workload", "zz_vit.train_replay", "--seed", "5",
                "--seconds", "1", "--trace", "1", "--rehearse-cpu"], env={"PYTHONPATH": ROOT}, cwd=str(tmp_path))
    line = last_line(proc)
    assert line["correct"] is True
    assert line["metrics"][PREFIX + "zz_steps_counted"]["value"] == line["attempted"]
    assert line["metrics"][PREFIX + "zz_kernel_flops"]["value"] == 2.0 * 8 * 16
    # a replayed batch ships once: the prefetch thread spends next to nothing on transfers
    assert line["host"]["h2d_transfer_s"] < 0.5 * line["window_s"]
    assert (tmp_path / "benchmark_out" / "zz_vit.train_replay").is_dir()  # the copy ran, not the repo's files
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_traced_run_that_meets_a_kernel_with_no_file_fails_and_prints_no_result():
    proc = run([sys.executable, os.path.join(HERE, "_drive_fault.py"), "vit_b16.train", "unpriced_kernel"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "zz_unpriced_kernel" in proc.stderr and "kernels" in proc.stderr


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
    cells = {c["name"] for c in bench["workloads"]}
    for metric in bench["per_layer"]:
        assert set(metric.get("workloads", ())) <= cells
    for config in bench["configs"]:  # what a configuration names is there under that name
        with open(os.path.join(ROOT, config["file"])) as f:
            kind = json.load(f)["input"]
        assert os.path.isfile(os.path.join(BENCH, "inputs", f"{kind}.py"))
        for folder in ("reference", "flops"):
            assert os.path.isfile(os.path.join(BENCH, folder, f"{config['name']}.py"))
