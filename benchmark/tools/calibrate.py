"""Read the numbers that ``correct`` compares, on many seeds in one process.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--controls fp8,bf16] [--faults half_batch,no_exchange] [--out file.jsonl]

For each seed: build the cell's program, drive its first three steps through
``train_epoch`` (the timed path, at the timed size), follow them with the
float32 reference, and print the gaps (the *lower* readings a limit is set
from). For the control seeds also put the reference in the program's place in
each of ``--controls`` (``fp8`` is the control of a bfloat16 configuration;
``bf16`` is no control but the look at what the configuration's own precision
does to the numbers), and with each fault planted (the *upper* readings); a
state, or the running statistics alone, left unchanged needs no run and is made
from the reference's readings. Every record carries ``correct``: what
``compare.verdict`` makes of it at the cell's own limits. No window is measured:
training's readings need none. It needs the chip unless ``--rehearse-cpu`` is given.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--controls", default="fp8")
    parser.add_argument("--faults", default="half_batch")
    parser.add_argument("--out", default="")
    parser.add_argument("--leaves-out", default="", help="also write every reading's per-leaf norms here")
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--reference-only", action="store_true",
                        help="leave the program out: only the controls and faults against the reference")
    args = parser.parse_args(argv)

    from benchmark import compare, files, harness, traffic

    cell, config = harness.load_cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell['chips']}"
    import jax

    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    settings = harness.settings_for(cell, config, args.rehearse_cpu)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    controls = [c for c in args.controls.split(",") if c]
    limits = cell["rehearse"]["limits"] if args.rehearse_cpu else cell["limits"]

    def judged(readings, want):
        found = compare.gaps(readings, want)
        correct, compared = compare.verdict(found["numbers"], limits)
        return {**found, "correct": correct,
                "failed": [n for n, p in compared.items() if p["value"] is None or not p["value"] <= p["limit"]]}

    out = open(args.out, "a") if args.out else None

    leaves_out = open(args.leaves_out, "a") if args.leaves_out else None

    def emit(record, readings=None):
        if leaves_out and readings is not None:
            leaves_out.write(json.dumps({"seed": record["seed"], "kind": record["kind"], **readings}) + "\n")
            leaves_out.flush()
        line = json.dumps(record)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in seeds:
        t0 = time.time()
        out_dir = os.path.join(files.ROOT, "benchmark_out", "calibrate", args.workload, f"seed{seed}")
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        if args.reference_only:  # what ``reference_for`` reads of a program, without one
            optim, train = settings["OPTIM"], settings["TRAIN"]
            program = types.SimpleNamespace(
                ref=files.load_module("reference", cell["config"]),
                opt=files.load_module("reference", f"optim_{optim['OPTIMIZER']}"), hp=optim,
                weights_key=harness.seed_key(seed), settings=settings,
                global_batch=train["BATCH_SIZE"] * train.get("ACCUM_STEPS", 1) * cell["chips"])
        else:
            program = harness.Program(cell, config, settings, seed, out_dir)
        pool = traffic.make_pool(config["input"], seed, compare.STEPS, program.global_batch, settings)
        if not args.reference_only:
            got = harness.first_steps(program, pool)
            program.end_run()
            program.free()
        t1 = time.time()
        want = harness.reference_for(program, pool, shards=cell["chips"])
        t2 = time.time()
        if not args.reference_only:
            emit({"workload": args.workload, "seed": seed, "kind": "program", **judged(got, want),
                  "loss": got["loss"], "ref_loss": want["loss"], "skipped": got["skipped"],
                  "program_s": t1 - t0, "reference_s": t2 - t1}, got)
        emit({"workload": args.workload, "seed": seed, "kind": "reference", "numbers": {}, "leaves": {}}, want)
        if seed in control_seeds:
            for control in controls:
                ctl = harness.reference_for(program, pool, shards=cell["chips"], precision=control)
                emit({"workload": args.workload, "seed": seed, "kind": f"control:{control}",
                      **judged(ctl, want), "loss": ctl["loss"]}, ctl)
            for fault in faults:
                bad = harness.reference_for(program, pool, shards=cell["chips"], fault=fault)
                emit({"workload": args.workload, "seed": seed, "kind": f"fault:{fault}",
                      **judged(bad, want), "loss": bad["loss"]}, bad)
            still = lambda norms: dict.fromkeys(norms, 0.0)
            left = {"unchanged": dict(want, delta_norm=still(want["delta_norm"]),
                                      stats_delta_norm=still(want["stats_delta_norm"]))}
            if want["stats_delta_norm"]:
                left["stats_unchanged"] = dict(want, stats_delta_norm=still(want["stats_delta_norm"]))
            for fault, bad in left.items():
                emit({"workload": args.workload, "seed": seed, "kind": f"fault:{fault}", **judged(bad, want)})
        del program
    return 0


if __name__ == "__main__":
    sys.exit(main())
