"""Shared helpers for benchmarking scripts (bench.py, scripts/perf_sweep.py,
scripts/profile_step.py): one A/B-arm policy and one synthetic batch, so
they all measure the same thing.
"""

from __future__ import annotations

import os


def s2d_default(arch: str) -> bool:
    """Space-to-depth stem exists for the resnet/botnet families (exact same
    function — tests assert equality) and is the shipped-recipe default there."""
    return arch.startswith(("resnet", "resnext", "wide_resnet", "botnet"))


def bench_arms():
    """Resolve the benched configuration from the A/B env opt-outs — ONE
    policy shared by every measurement tool so they all measure the same arm.

    Default arm = the shipped-best TPU recipe (bf16 BN boundaries, s2d stem
    where applicable); ``DTPU_BENCH_BNF32=1`` / ``DTPU_BENCH_S2D=0`` select
    the f32-boundary / plain-stem arms; ``DTPU_BENCH_ARCH`` picks the arch.
    Returns (arch, stem_s2d, bn_f32).
    """
    arch = os.environ.get("DTPU_BENCH_ARCH", "resnet50")
    s2d_env = os.environ.get("DTPU_BENCH_S2D")
    stem_s2d = (s2d_env == "1") if s2d_env is not None else s2d_default(arch)
    bn_f32 = os.environ.get("DTPU_BENCH_BNF32", "0") == "1"
    return arch, stem_s2d, bn_f32


def make_synthetic_batch(mesh, global_batch: int, im_size: int = 224, seed: int = 0):
    """Synthetic sharded train batch with the loader's exact field contract
    (raw u8 images — the real H2D payload; normalize runs inside the step)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(seed)
    return {
        "image": jax.device_put(
            rng.integers(0, 256, (global_batch, im_size, im_size, 3), dtype=np.uint8),
            NamedSharding(mesh, P("data", None, None, None)),
        ),
        "label": jax.device_put(
            rng.integers(0, 1000, global_batch).astype(np.int32),
            NamedSharding(mesh, P("data")),
        ),
        "weight": jax.device_put(
            np.ones((global_batch,), np.float32), NamedSharding(mesh, P("data"))
        ),
    }
