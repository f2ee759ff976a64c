"""Perf sweep on a healthy TPU: models × batch sizes, one table.

    python scripts/perf_sweep.py [--quick]

Measures the full SPMD train step with bench.py's methodology (3 warmup
steps for compile+autotune, then timing gated by a device_get metric fetch
every FETCH_EVERY steps — the production PRINT_FREQ cadence; steps chain
through `state`, so the final fetch bounds all device work) and prints a
markdown table.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CASES = [
    # (arch, per-chip batches, model kwargs, f32 BN boundaries?, row label)
    # Unlabeled rows are the shipped-best TPU recipe (bf16 BN boundaries,
    # s2d stem on the resnet/botnet families); "-x" rows are A/B opt-outs.
    ("resnet18", (256, 1024), {"stem_s2d": True}, False, ""),
    ("resnet50", (128, 512), {"stem_s2d": True}, False, ""),
    ("resnet50", (128, 512), {}, False, " -s2d"),
    ("resnet50", (128, 512), {"stem_s2d": True}, True, " -bn16"),
    ("botnet50", (128, 256), {"stem_s2d": True}, False, ""),
    ("efficientnet_b0", (256, 512), {}, False, ""),
    ("regnety_160", (64, 128), {}, False, ""),
]

WARMUP, ITERS, QUICK_ITERS, FETCH_EVERY = 3, 20, 10, 10


def main():
    quick = "--quick" in sys.argv
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu import optim
    from distribuuuu_tpu.benchutil import make_synthetic_batch
    from distribuuuu_tpu.models import build_model
    from distribuuuu_tpu.runtime import data_mesh
    from distribuuuu_tpu.trainer import create_train_state, make_train_step

    mesh = data_mesh(-1)
    n_chips = jax.device_count()
    print(f"devices: {jax.devices()}\n")
    print("| arch | batch/chip | ms/step | img/s/chip |")
    print("|---|---|---|---|")
    lr = jnp.asarray(0.1, jnp.float32)
    key = jax.random.PRNGKey(1)
    init_key = jax.random.PRNGKey(0)  # same init every rung, hoisted (DT002)
    iters = QUICK_ITERS if quick else ITERS

    from distribuuuu_tpu.models.layers import set_bn_compute_dtype

    for arch, batches, model_kw, bn_f32, label in CASES:
        # read at trace time (inside make_train_step's first call), so set
        # before any step of this case runs
        set_bn_compute_dtype(jnp.float32 if bn_f32 else jnp.bfloat16)
        model = build_model(arch, num_classes=1000, **model_kw)
        # tx is state-free; building the step does not allocate device memory
        step = make_train_step(model, optim.construct_optimizer(), mesh, topk=5)
        for B in batches[:1] if quick else batches:
            state = batch = None
            try:
                # state/batch construction inside the try: OOM at the larger
                # rungs happens here as readily as inside the step
                state, _ = create_train_state(model, init_key, mesh, 224)
                batch = make_synthetic_batch(mesh, B * n_chips)
                for _ in range(WARMUP):
                    state, m = step(state, batch, lr, key)
                    jax.device_get(m)
                t0 = time.perf_counter()
                for it in range(iters):
                    state, m = step(state, batch, lr, key)
                    if (it + 1) % FETCH_EVERY == 0:
                        jax.device_get(m)
                jax.device_get(m)
                dt = (time.perf_counter() - t0) / iters
                print(f"| {arch}{label} | {B} | {dt * 1000:.1f} | {B / dt:.1f} |", flush=True)
            except Exception as e:  # OOM etc: report and continue the sweep
                print(f"| {arch}{label} | {B} | FAILED: {type(e).__name__} | — |", flush=True)
            finally:
                # release device memory even on the failure path, or a single
                # OOM poisons every later row
                del state, batch


if __name__ == "__main__":
    main()
