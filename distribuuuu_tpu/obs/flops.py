"""Step FLOPs accounting and MFU arithmetic.

Model FLOPs utilization — achieved model FLOPs/s over the hardware's peak —
is the standard single-number efficiency instrument for large accelerator
runs (the PaLM-report convention). Two pieces live here:

- **Analytical step cost** (`lowered_step_cost`): the XLA cost model run on
  the *lowered, uncompiled* step (``jitted.lower(...).cost_analysis()``).
  Lowering is tracing + StableHLO emission — **no backend compile** — so the
  trainer can price its own step without adding a compile (CompileGuard
  stays at exactly 1; pinned in tests/test_obs.py). The lowered module is
  the pre-partitioning *global* program, so its flops are per global step.
- **Peak-FLOPs table + `mfu`**: per-device peak dense bf16 FLOPs by
  ``device_kind`` (a JAX "device" is a core on v2/v3 and a chip from v4 on —
  the table is per *device* so the arithmetic never needs to know). Unknown
  hardware (CPU smokes) yields ``None`` and MFU is simply omitted rather
  than fabricated; ``OBS.PEAK_TFLOPS_PER_DEVICE`` overrides for new chips.
"""

from __future__ import annotations

from typing import Any

from distribuuuu_tpu.logging import logger

# Peak dense bf16 TFLOP/s per JAX device (per core for v2/v3 — 2 devices per
# chip there; per chip from v4 on). Sources: Google Cloud TPU system specs.
_PEAK_BF16_TFLOPS: dict[str, float] = {
    "tpu v2": 22.5,
    "tpu v3": 61.5,
    "tpu v4": 275.0,
    "tpu v5 lite": 197.0,
    "tpu v5e": 197.0,
    "tpu v5": 459.0,
    "tpu v5p": 459.0,
    "tpu v6 lite": 918.0,
    "tpu v6e": 918.0,
}


def peak_flops_per_device(device=None, override_tflops: float = 0.0) -> float | None:
    """Peak dense FLOP/s for one JAX device, or None when unknown.

    ``override_tflops`` (``cfg.OBS.PEAK_TFLOPS_PER_DEVICE``) wins when > 0;
    else the static table (longest matching key, so "TPU v5 lite" resolves
    before "TPU v5"). CPU/unknown → None.
    """
    if override_tflops and override_tflops > 0:
        return float(override_tflops) * 1e12
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    best = None
    for key, tflops in _PEAK_BF16_TFLOPS.items():
        if key in kind and (best is None or len(key) > len(best[0])):
            best = (key, tflops)
    return best[1] * 1e12 if best else None


def _normalize_cost(costs: Any) -> dict[str, float] | None:
    """XLA cost_analysis output → ``{"flops", "bytes_accessed"}`` floats."""
    if not isinstance(costs, dict):
        return None
    flops = costs.get("flops")
    if flops is None or not flops == flops:  # missing or NaN
        return None
    return {
        "flops": float(flops),
        "bytes_accessed": float(costs.get("bytes accessed", float("nan"))),
    }


def lowered_step_cost(step_fn, *args, **kwargs) -> dict[str, float] | None:
    """FLOPs/bytes of one **global** step from the lowered (uncompiled) HLO.

    Costs tracing time once, never a backend compile. Returns None when the
    backend/jax version cannot price the module — callers omit MFU then.
    """
    try:
        lowered = step_fn.lower(*args, **kwargs)
        return _normalize_cost(lowered.cost_analysis())
    except Exception as exc:  # any backend/version gap: MFU is optional
        logger.info(f"step cost analysis unavailable ({exc!r}); MFU disabled")
        return None


def mfu(
    flops_per_step: float | None,
    step_time_s: float,
    device_count: int,
    peak_flops_per_dev: float | None,
) -> float | None:
    """Model FLOPs utilization in [0, 1]: achieved FLOP/s over fleet peak.

    ``flops_per_step`` is per *global* step (the lowered-module convention
    above); the fleet peak is ``device_count * peak_flops_per_dev``. Returns
    None when either the step cost or the hardware peak is unknown.
    """
    if not flops_per_step or not peak_flops_per_dev or step_time_s <= 0:
        return None
    if device_count <= 0:
        return None
    return (flops_per_step / step_time_s) / (device_count * peak_flops_per_dev)
