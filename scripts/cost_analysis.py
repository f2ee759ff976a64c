"""XLA cost analysis of the compiled train step — the effective-TFLOPs ledger.

Prints the compiler's own cost model for the full SPMD train step (flops,
bytes accessed, arithmetic intensity) plus the model-math FLOPs estimate, so
a report can state measured img/s against the step's actual FLOP count
rather than a hand-wave. Runs on any backend (CPU gives the same HLO-level
counts; run on TPU for the emitter's real numbers).

    python scripts/cost_analysis.py [--arch resnet50] [--batch 128] [--s2d]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--batch", type=int, default=128, help="global batch")
    ap.add_argument("--im-size", type=int, default=224)
    ap.add_argument("--s2d", action="store_true", help="space-to-depth stem")
    ap.add_argument("--cpu", action="store_true", help="force CPU backend")
    args = ap.parse_args()

    if args.cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=1"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu import optim
    from distribuuuu_tpu.benchutil import make_synthetic_batch
    from distribuuuu_tpu.models import build_model
    from distribuuuu_tpu.runtime import data_mesh
    from distribuuuu_tpu.trainer import create_train_state, make_train_step

    mesh = data_mesh(-1)
    kw = {"stem_s2d": True} if args.s2d else {}
    model = build_model(args.arch, num_classes=1000, **kw)
    state, _ = create_train_state(model, jax.random.PRNGKey(0), mesh, args.im_size)
    step = make_train_step(model, optim.construct_optimizer(), mesh, topk=5)
    batch = make_synthetic_batch(mesh, args.batch)
    lr = jnp.asarray(0.1, jnp.float32)
    key = jax.random.PRNGKey(1)

    # shared cost-model plumbing with the in-run MFU accounting
    # (obs/flops.py journals the *lowered* cost per window; this script
    # compiles for the emitter's per-device numbers)
    from distribuuuu_tpu.obs import flops as obs_flops

    cost = obs_flops.compiled_step_cost(step, state, batch, lr, key)
    if cost is None:
        print("cost analysis unavailable on this backend/jax version", file=sys.stderr)
        raise SystemExit(1)
    flops = cost["flops"]
    bytes_acc = cost["bytes_accessed"]
    # the compiled module is the per-DEVICE SPMD program: it processes
    # batch/device_count images, so normalize by the per-device batch
    per_dev_imgs = args.batch / jax.device_count()
    per_img = flops / per_dev_imgs
    label = f"{args.arch}{' +s2d' if args.s2d else ''}"
    print(f"train step: {label}, global batch {args.batch}, {args.im_size}px, "
          f"{jax.device_count()} device(s) [{jax.devices()[0].platform}]")
    print(f"  XLA flops/device/step:   {flops:.3e}  ({per_img:.3e} per image)")
    print(f"  XLA bytes accessed/step: {bytes_acc:.3e}")
    if bytes_acc:
        print(f"  arithmetic intensity:    {flops / bytes_acc:.1f} flops/byte")
    print(f"  (at R img/s/chip, effective TFLOPs/chip = R * {per_img:.3e} / 1e12)")
    # registry-aware: peak_flops_per_device prefers a perfdb-measured matmul
    # ceiling (scripts/stage_roofline.py writes it) over the datasheet table
    peak = obs_flops.peak_flops_per_device()
    if peak:
        print(f"  device peak (measured ceiling or table): {peak / 1e12:.1f} TFLOP/s "
              f"-> MFU = R * {per_img:.3e} / {peak:.3e}")


if __name__ == "__main__":
    main()
