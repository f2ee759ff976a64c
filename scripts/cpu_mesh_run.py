"""Run any entry point on a virtual N-device CPU mesh (default 8).

The TPU-native analog of the reference's "multi-node on localhost" recipe
(`/root/reference/README.md:119-144`): all sharding/collective code runs for
real, just on partitioned host CPU devices. Usage:

    python scripts/cpu_mesh_run.py train_net.py --cfg config/resnet18.yaml ...
    DTPU_CPU_DEVICES=16 python scripts/cpu_mesh_run.py test_net.py ...

Equivalent to ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N
python <script>`` plus two things a CPU run of this repo wants: the shared
repo-local compile cache and the Pallas interpreter (`ops/interpret.py` — no
kernel picks it from the platform). The CLI tests re-exec this wrapper per
rank, so they do not depend on how pytest itself was launched.
"""

import os
import runpy
import sys


def main():
    n = os.environ.get("DTPU_CPU_DEVICES", "8")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    # share the repo-local persistent compile cache with the test suite: the
    # CLI tests re-exec this wrapper per rank, and identical programs should
    # compile once per machine, not once per process per run
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    set_pallas_interpret(True)

    if len(sys.argv) < 2:
        raise SystemExit("usage: cpu_mesh_run.py <script.py> [args...]")
    script = sys.argv[1]
    sys.argv = sys.argv[1:]
    # emulate `python script.py`: the script's directory leads sys.path
    sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
