"""XLA flag A/B sweep over the headline bench (VERDICT r2 #3 support).

XLA_FLAGS must be set before backend initialization, so each arm runs
``bench.py`` in a fresh subprocess with the arm's flags appended to the
inherited XLA_FLAGS. bench.py's own probe/watchdog machinery guards every
arm — a mid-sweep wedge costs one arm's timeout, not the sweep.

    python scripts/xla_flag_sweep.py                  # default arm list
    python scripts/xla_flag_sweep.py --arm big-vmem=--xla_tpu_scoped_vmem_limit_kib=98304

Prints a markdown table; arms that fail or regress
are data, not errors.
"""

import argparse
import json
import os
import subprocess
import sys

# Conservative default list for a single-chip conv workload: VMEM budget for
# fusion buffers (v5e has 128 MiB/core; the scoped default is smaller) and
# the latency-hiding scheduler toggle. Collective-related flags are pointless
# on one chip and excluded.
DEFAULT_ARMS = [
    ("baseline", ""),
    ("vmem-64m", "--xla_tpu_scoped_vmem_limit_kib=65536"),
    ("vmem-96m", "--xla_tpu_scoped_vmem_limit_kib=98304"),
    ("no-lhs", "--xla_tpu_enable_latency_hiding_scheduler=false"),
]


def run_arm(label: str, flags: str, timeout: float, cpu: bool = False) -> dict:
    env = dict(os.environ)
    if flags:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.join(repo, "bench.py")]
    if cpu:
        # harness smoke without a chip: the platform is pinned
        # programmatically on this box, so route through cpu_mesh_run
        cmd.insert(1, os.path.join(repo, "scripts", "cpu_mesh_run.py"))
        env.setdefault("DTPU_BENCH_BATCH", "4")
        env.setdefault("DTPU_BENCH_IM_SIZE", "32")
        env.setdefault("DTPU_CPU_DEVICES", "1")
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, env=env, cwd=repo,
        )
    except subprocess.TimeoutExpired:
        return {"label": label, "error": f"timeout {timeout:.0f}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if proc.returncode != 0:
            # bench.py's probe-abort/watchdog path: rc=2 with a 0.0 JSON
            # line whose metric string holds the reason — surface it as a
            # failure, not a measured zero
            return {"label": label, "error": f"rc={proc.returncode}: {rec.get('metric', '?')}"}
        rec["label"] = label
        return rec
    return {"label": label, "error": f"rc={proc.returncode}: {proc.stderr[-200:]}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--arm", action="append", default=[],
        help="label=FLAGS (repeatable); replaces the default arm list",
    )
    ap.add_argument("--timeout", type=float, default=700.0)
    ap.add_argument("--cpu", action="store_true", help="harness smoke on CPU")
    args = ap.parse_args()

    if args.arm:
        arms = []
        for a in args.arm:
            label, sep, flags = a.partition("=")
            if not sep:
                ap.error(f"--arm needs label=FLAGS (use '{a}=' for empty flags)")
            arms.append((label, flags))
    else:
        arms = DEFAULT_ARMS
    print("| arm | XLA flags | img/s/chip |")
    print("|---|---|---|")
    best = None
    for label, flags in arms:
        rec = run_arm(label, flags, args.timeout, cpu=args.cpu)
        if "error" in rec:
            # a flag the backend rejects kills the runtime at start-up: the
            # arm fails, the baseline arm (no flags) says whether the device
            # itself is fine
            print(f"| {label} | `{flags or '-'}` | FAILED: {rec['error']} |", flush=True)
            continue
        v = rec.get("value", 0.0)
        print(f"| {label} | `{flags or '-'}` | {v} |", flush=True)
        if v and (best is None or v > best[1]):
            best = (label, v)
    if best:
        print(f"\nbest arm: {best[0]} at {best[1]} img/s/chip")
    else:
        # every arm failed/aborted (e.g. mid-sweep wedge): exit nonzero so
        # the ladder's run_or_abort stops at THIS rung and the wedge log
        # attributes the wedge to its true cause time
        sys.exit(1)


if __name__ == "__main__":
    main()
