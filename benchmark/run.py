"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

One run of one cell of the benchmark. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last the numbers compared beside
their limits). It fails, and prints no result, unless JAX reports a TPU with
as many chips as the cell asks for; ``--rehearse-cpu`` is the sandbox
walk-through at tiny sizes, whose numbers carry the prefix ``cpu_rehearsal.``
because a number from a CPU never stands under a device metric's name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REHEARSAL_PREFIX = "cpu_rehearsal."


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import files

    cell = files.load_json("workloads", args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={cell['chips']}".strip()
    import jax

    devices = jax.devices()
    if not args.rehearse_cpu:
        if devices[0].platform != "tpu":
            print(f"benchmark: JAX reports platform {devices[0].platform!r}, not a TPU: no result", file=sys.stderr)
            return 2
        from benchmark import peaks

        peaks.lookup(devices[0].device_kind)  # an unknown device is an error before any work
    if len(devices) < cell["chips"]:
        print(f"benchmark: the cell asks for {cell['chips']} chips, JAX reports {len(devices)}: no result",
              file=sys.stderr)
        return 2

    from benchmark import harness

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), rehearse=args.rehearse_cpu,
        t_process_start=T_PROCESS_START,
    )
    prefix = REHEARSAL_PREFIX if args.rehearse_cpu else ""
    source = result["per_layer"] if args.trace else {
        "img_per_s_per_chip": {"value": result["end_to_end"]["img_per_s_per_chip"], "unit": "img/s/chip"},
        "setup_s": {"value": result["end_to_end"]["setup_s"], "unit": "s"},
    }
    line = {
        "correct": result["correct"], "attempted": result["attempted"], "failed": result["failed"],
        "metrics": {prefix + k: v for k, v in source.items()},
        "device": result["device"],
    }
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["window_s"] = result["window"]["seconds"]
    line["reference_s"] = result["reference_s"]
    line["host"] = result["host"]
    if "trace" in result:
        line["trace"] = result["trace"]
    line["compared"] = result["compared"]
    sys.stdout.flush()
    for name, pair in result["compared"].items():
        leaf = result["worst_leaves"].get(name)
        print(f"compared {name}: {pair['value']} limit {pair['limit']}" + (f" (leaf {leaf})" if leaf else ""),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
