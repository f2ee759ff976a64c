"""Supervised dtpu-serve replica for the serving chaos tests
(tests/test_serve.py) — NOT a pytest module.

Runs `serve.frontend.serve_main` under the dtpu-agent serving contract
(AGENT.SERVE, distribuuuu_tpu/agent.py): the replica's frontend port and
index arrive via DTPU_SERVE_PORT / DTPU_SERVE_REPLICA env vars, config via
the same --cfg/overrides argv as any entry point. Pins the CPU platform and
a single-device host explicitly (a supervised child must not depend on how
the test run was launched — see tests/conftest.py), which is why the chaos
tier substitutes it via AGENT.CMD instead of using the agent's built-in
``python -m distribuuuu_tpu.serve`` worker.

argv: ordinary config overrides (KEY VALUE ...), forwarded to serve_main.
"""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=1"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

from distribuuuu_tpu.serve.frontend import serve_main  # noqa: E402

if __name__ == "__main__":
    sys.exit(serve_main(sys.argv[1:]))
