"""Per-stage conv roofline for resnet50 on one TPU chip (VERDICT r2 #3).

Times every distinct conv shape in the resnet50 train step (fwd-only and
fwd+bwd via jax.vjp with a random cotangent, so dgrad/wgrad can't be
simplified away), computes achieved TFLOPs per shape, and compares against
a plain bf16 matmul ceiling measured in the same session. The closing table
attributes the full measured step time: sum(count x measured conv ms) vs
whole-step ms — the gap is BN/relu/residual/optimizer/metrics + fusion
overhead. This either finds the stage to attack or proves "emitter-bound,
nothing left at this width" on paper.

    python scripts/stage_roofline.py [--batch 512] [--iters 10] \
        [--stage stem|s1|s2|s3|s4|mm|strided|step]

Methodology matches bench.py: timing gated by real device_get fetches,
steps chained through the carry, 3-step warmup after compile, hard-exit
watchdog so a hung device can't hang the ladder.
"""

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WATCHDOG_SECONDS = int(os.environ.get("DTPU_ROOFLINE_WATCHDOG", "1500"))

# resnet50 conv inventory, s2d-stem arm (the shipped/benched recipe).
# (stage, label, Hin, Win, k, stride, Cin, Cout, count) — count = occurrences
# per forward pass. Derived from models/resnet.py Bottleneck stacking
# ([3,4,6,3], v1.5 stride placement); the s2d stem row is the exact compute
# S2DStemConv emits: 4x4 VALID conv on the 2x2-blocked, (4,2)-padded input
# (115x115x12 -> 112x112x64), which executes 192 MACs/output vs the logical
# 7x7 stem's 147 — FLOPs below count what actually runs.
CONVS = [
    ("stem", "s2d 4x4/1 12->64", 115, 115, 4, 1, 12, 64, 1),
    # stage1, 56x56, blocks [1 + 2]
    ("s1", "1x1 64->64", 56, 56, 1, 1, 64, 64, 1),
    ("s1", "1x1 256->64", 56, 56, 1, 1, 256, 64, 2),
    ("s1", "3x3 64->64", 56, 56, 3, 1, 64, 64, 3),
    ("s1", "1x1 64->256", 56, 56, 1, 1, 64, 256, 3),
    ("s1", "ds 1x1 64->256", 56, 56, 1, 1, 64, 256, 1),
    # stage2, first block strides 56->28
    ("s2", "1x1 256->128", 56, 56, 1, 1, 256, 128, 1),
    ("s2", "3x3/2 128->128", 56, 56, 3, 2, 128, 128, 1),
    ("s2", "ds 1x1/2 256->512", 56, 56, 1, 2, 256, 512, 1),
    ("s2", "1x1 512->128", 28, 28, 1, 1, 512, 128, 3),
    ("s2", "3x3 128->128", 28, 28, 3, 1, 128, 128, 3),
    ("s2", "1x1 128->512", 28, 28, 1, 1, 128, 512, 4),
    # stage3, first block strides 28->14
    ("s3", "1x1 512->256", 28, 28, 1, 1, 512, 256, 1),
    ("s3", "3x3/2 256->256", 28, 28, 3, 2, 256, 256, 1),
    ("s3", "ds 1x1/2 512->1024", 28, 28, 1, 2, 512, 1024, 1),
    ("s3", "1x1 1024->256", 14, 14, 1, 1, 1024, 256, 5),
    ("s3", "3x3 256->256", 14, 14, 3, 1, 256, 256, 5),
    ("s3", "1x1 256->1024", 14, 14, 1, 1, 256, 1024, 6),
    # stage4, first block strides 14->7
    ("s4", "1x1 1024->512", 14, 14, 1, 1, 1024, 512, 1),
    ("s4", "3x3/2 512->512", 14, 14, 3, 2, 512, 512, 1),
    ("s4", "ds 1x1/2 1024->2048", 14, 14, 1, 2, 1024, 2048, 1),
    ("s4", "1x1 2048->512", 7, 7, 1, 1, 2048, 512, 2),
    ("s4", "3x3 512->512", 7, 7, 3, 1, 512, 512, 2),
    ("s4", "1x1 512->2048", 7, 7, 1, 1, 512, 2048, 3),
]


def _watchdog():
    print("ROOFLINE TIMED OUT: device wedged/unreachable", flush=True)
    os._exit(2)


def out_hw(h, k, s):
    # SAME padding for k>1 (stem row is VALID but pre-padded to land on 112)
    if k == 4:  # the s2d stem: VALID
        return (h - k) // s + 1
    return -(-h // s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--stage", default=None,
        help="stem|s1|s2|s3|s4 | mm | strided | step | all (default all)",
    )
    ap.add_argument(
        "--no-registry", action="store_true",
        help="do not write the measured matmul ceiling into the perfdb "
        "registry (the default write is what makes MFU use the achievable "
        "ceiling instead of the datasheet peak — obs/flops.py)",
    )
    args = ap.parse_args()

    # Inventory sanity line: 3x-fwd over all rows should land ~24.7 GF/img —
    # the XLA-measured 24.43 (scripts/cost_analysis.py) plus the stem dgrad
    # (~0.3 GF) that a real step never computes (no image gradients needed)
    # but the per-shape fwd+bwd microbench does. A bigger drift means the
    # table no longer matches models/resnet.py — fix it before trusting rows.
    inv = sum(
        3 * 2.0 * out_hw(h, k, s) * out_hw(w, k, s) * cout * k * k * cin * cnt
        for _, _, h, w, k, s, cin, cout, cnt in CONVS
    ) / 1e9
    print(f"inventory: {inv:.2f} GF/img train (XLA whole-step: 24.43 + ~0.3 stem dgrad)")

    timer = threading.Timer(WATCHDOG_SECONDS, _watchdog)
    timer.daemon = True
    timer.start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    B = args.batch
    iters = args.iters
    want = args.stage or "all"
    rng = np.random.default_rng(0)

    def timed(fn, carry, n=iters, warmup=3):
        """bench.py cadence: chained carry, fetch gates the timer."""
        out = None
        for _ in range(warmup):
            carry, out = fn(carry)
        jax.device_get(out)
        t0 = time.perf_counter()
        for _ in range(n):
            carry, out = fn(carry)
        jax.device_get(out)
        return (time.perf_counter() - t0) / n

    def make_fwdbwd(f):
        """fwd+bwd timing harness for a conv-like f(x, wt).

        Measurement-validity notes (each bit one smoke run): wt/ct are
        runtime ARGUMENTS, not closure constants — a closure ct+wt makes
        dgrad = conv(ct, rot(wt)) all-constant and XLA constant-folds it
        out of the timed program. The full dw reduction (not an element
        slice) keeps the wgrad entirely live, and the non-zero chain
        coefficients defeat the algebraic simplifier's mul-by-0 folding.
        """

        @jax.jit
        def fb(x, wt, ct):
            y, vjp = jax.vjp(f, x, wt)
            dx, dw = vjp(ct)
            return (
                x + jnp.bfloat16(1e-6) * dx,
                wt + jnp.bfloat16(1e-9) * dw,
                ct,
            ), jnp.sum(dw.astype(jnp.float32))

        return fb

    # --- matmul ceiling, same session -------------------------------------
    mm_tf = None
    if want in ("all", "mm"):
        M = 8192
        a = jnp.asarray(rng.standard_normal((M, M)), jnp.bfloat16)
        b = jnp.asarray(rng.standard_normal((M, M)), jnp.bfloat16)

        @jax.jit
        def mm(a):
            c = a @ b
            # scalar feedback chains the steps; the full-reduction + tiny
            # coefficient (not literal 0, which XLA's algebraic simplifier
            # would fold, dead-coding the matmul) keeps c fully live while
            # leaving a numerically unchanged under bf16 rounding
            s = jnp.sum(c.astype(jnp.float32))
            return a * (1 + jnp.bfloat16(1e-12) * s.astype(jnp.bfloat16)), s

        dt = timed(mm, a)
        mm_tf = 2 * M**3 / dt / 1e12
        print(f"matmul ceiling: bf16 {M}^3 = {mm_tf:.1f} TFLOPs ({dt*1e3:.2f} ms)\n", flush=True)
        if not args.no_registry and jax.devices()[0].platform == "tpu":
            # persist the achievable ceiling for this device_kind; MFU and
            # the summarize roofline prefer it over the datasheet peak. CPU
            # runs never write — a host "ceiling" would poison every MFU.
            try:
                from distribuuuu_tpu.obs import perfdb

                perfdb.PerfDB().record_ceiling(mm_tf, source="stage_roofline")
                print(f"(perfdb: recorded {mm_tf:.1f} TF ceiling for "
                      f"{jax.devices()[0].device_kind})", flush=True)
            except ValueError:
                pass  # DTPU_PERFDB=0: registry disabled
            except Exception as e:
                print(f"(perfdb ceiling write skipped: {e!r})", flush=True)

    # --- per-shape conv microbench ----------------------------------------
    rows = []
    if want in ("all", "stem", "s1", "s2", "s3", "s4"):
        print(f"| stage | conv | count | fwd ms | f+b ms | f+b TF | GF/img (train) |")
        print(f"|---|---|---|---|---|---|---|", flush=True)
        for stage, label, h, w, k, s, cin, cout, count in CONVS:
            if want not in ("all", stage):
                continue
            ho, wo = out_hw(h, k, s), out_hw(w, k, s)
            fwd_flops = 2.0 * B * ho * wo * cout * k * k * cin
            pad = "VALID" if k == 4 else "SAME"
            x = jnp.asarray(rng.standard_normal((B, h, w, cin)) * 0.1, jnp.bfloat16)
            wt = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * 0.05, jnp.bfloat16)
            ct = jnp.asarray(rng.standard_normal((B, ho, wo, cout)) * 0.1, jnp.bfloat16)

            def conv(x, wt):
                return jax.lax.conv_general_dilated(
                    x, wt, window_strides=(s, s), padding=pad,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )

            # see make_fwdbwd for the measurement-validity rationale; fwd's
            # full y reduction + non-zero chain coefficient follow the same
            # rules
            @jax.jit
            def fwd(x, wt):
                y = conv(x, wt)
                s = jnp.sum(y.astype(jnp.float32))
                return (
                    x * (1 + jnp.bfloat16(1e-12) * s.astype(jnp.bfloat16)),
                    wt,
                ), s

            fwdbwd = make_fwdbwd(conv)
            try:
                dt_f = timed(lambda c: fwd(*c), (x, wt))
                dt_fb = timed(lambda c: fwdbwd(*c), (x, wt, ct))
            except Exception as e:
                print(f"| {stage} | {label} | {count} | FAILED {type(e).__name__} | | | |", flush=True)
                continue
            tf_fb = 3 * fwd_flops / dt_fb / 1e12
            rows.append((stage, label, count, dt_f, dt_fb, tf_fb, fwd_flops))
            print(
                f"| {stage} | {label} | {count} | {dt_f*1e3:.2f} | {dt_fb*1e3:.2f} "
                f"| {tf_fb:.1f} | {3*fwd_flops/B/1e9:.2f} |",
                flush=True,
            )
            del x, wt, ct

    # --- strided-conv alternatives: the candidate MFU lever ----------------
    # Stride-2 convs are the classic TPU soft spot (their dgrad is a
    # transposed strided conv). Same transform as the stem: zero-pad the 3x3
    # kernel to 4x4 (top/left), 2x2-block kernel and activations, run the
    # exact-equivalent 2x2 STRIDE-1 conv on (H/2, W/2, 4C) — dgrad becomes a
    # stride-1 dgrad. 1x1/2 convs become slice + 1x1. Equality asserted in
    # f32 before timing; the 3x3 alt executes 16/9 the MACs (zero taps), so
    # compare ms, not TF. Measure-first: models/ only adopts this if it wins.
    if want in ("all", "strided"):
        print("\n| strided conv | direct f+b ms | s2d f+b ms | speedup |")
        print("|---|---|---|---|", flush=True)
        for stage, label, h, w, k, s, cin, cout, count in CONVS:
            if s != 2:
                continue
            ho, wo = out_hw(h, k, s), out_hw(w, k, s)

            def direct_fn(x, wt, s=s):
                return jax.lax.conv_general_dilated(
                    x, wt, window_strides=(s, s), padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )

            if k == 3:

                def alt_fn(x, wt, cin=cin, cout=cout):
                    wp = jnp.pad(wt, ((1, 0), (1, 0), (0, 0), (0, 0)))
                    wp = (
                        wp.reshape(2, 2, 2, 2, cin, cout)
                        .transpose(0, 2, 1, 3, 4, 5)
                        .reshape(2, 2, 4 * cin, cout)
                    )
                    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
                    n, hp, wpx, c = xp.shape
                    xs = (
                        xp.reshape(n, hp // 2, 2, wpx // 2, 2, c)
                        .transpose(0, 1, 3, 2, 4, 5)
                        .reshape(n, hp // 2, wpx // 2, 4 * c)
                    )
                    return jax.lax.conv_general_dilated(
                        xs, wp, window_strides=(1, 1), padding="VALID",
                        dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    )

            else:  # 1x1 stride 2 == slice even pixels + 1x1

                def alt_fn(x, wt):
                    return jax.lax.conv_general_dilated(
                        x[:, ::2, ::2, :], wt, window_strides=(1, 1),
                        padding="VALID",
                        dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    )

            # exact-math check in f32 on a small batch before timing; a
            # mismatch fails THIS row and the sweep continues, like every
            # other per-row failure in the script
            try:
                xf = jnp.asarray(rng.standard_normal((2, h, w, cin)), jnp.float32)
                wf = jnp.asarray(
                    rng.standard_normal((k, k, cin, cout)) * 0.05, jnp.float32
                )
                np.testing.assert_allclose(
                    np.asarray(direct_fn(xf, wf)), np.asarray(alt_fn(xf, wf)),
                    rtol=1e-4, atol=1e-4, err_msg=label,
                )
                del xf, wf
            except AssertionError:
                print(f"| {label} | MISMATCH (s2d != direct) | | |", flush=True)
                continue

            x = jnp.asarray(rng.standard_normal((B, h, w, cin)) * 0.1, jnp.bfloat16)
            wt = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * 0.05, jnp.bfloat16)
            ct = jnp.asarray(rng.standard_normal((B, ho, wo, cout)) * 0.1, jnp.bfloat16)

            try:
                fb_d = make_fwdbwd(direct_fn)
                fb_a = make_fwdbwd(alt_fn)
                dt_d = timed(lambda c: fb_d(*c), (x, wt, ct))
                dt_a = timed(lambda c: fb_a(*c), (x, wt, ct))
            except Exception as e:
                print(f"| {label} | FAILED {type(e).__name__} | | |", flush=True)
                continue
            print(
                f"| {label} | {dt_d*1e3:.2f} | {dt_a*1e3:.2f} "
                f"| {dt_d/dt_a:.2f}x |",
                flush=True,
            )
            del x, wt, ct

    # --- whole measured step, same session --------------------------------
    step_ms = None
    if want in ("all", "step"):
        from distribuuuu_tpu import optim
        from distribuuuu_tpu.benchutil import make_synthetic_batch
        from distribuuuu_tpu.models import build_model
        from distribuuuu_tpu.models.layers import set_bn_compute_dtype
        from distribuuuu_tpu.runtime import data_mesh
        from distribuuuu_tpu.trainer import create_train_state, make_train_step

        mesh = data_mesh(-1)
        set_bn_compute_dtype(jnp.bfloat16)
        model = build_model("resnet50", num_classes=1000, stem_s2d=True)
        step = make_train_step(model, optim.construct_optimizer(), mesh, topk=5)
        state, _ = create_train_state(model, jax.random.PRNGKey(0), mesh, 224)
        batch = make_synthetic_batch(mesh, B * jax.device_count())
        lr = jnp.asarray(0.1, jnp.float32)
        key = jax.random.PRNGKey(1)

        def one(carry):
            st, _ = carry
            st, m = step(st, batch, lr, key)
            return (st, m), m

        step_ms = timed(one, (state, None), n=iters) * 1e3
        print(f"\nwhole train step: {step_ms:.1f} ms ({B/step_ms*1e3:.0f} img/s/chip)", flush=True)

    # --- attribution -------------------------------------------------------
    if rows and step_ms:
        # the share arithmetic goes through obs/attribution.py so this
        # script's by-name buckets and the trace-walking step_attribution
        # records classify with the same markers and cannot drift apart
        from distribuuuu_tpu.obs.attribution import attribute_parts

        buckets = attribute_parts({
            f"conv {stage} {label}": c * dt_fb * 1e3
            for stage, label, c, _, dt_fb, _, _ in rows
        })
        conv_ms = buckets["matmul"]
        total_gf = sum(3 * c * f for _, _, c, _, _, _, f in rows) / 1e9
        print(f"\nconv-only (sum count x f+b ms): {conv_ms:.1f} ms "
              f"({total_gf/ (conv_ms/1e3) / 1e3:.1f} TF achieved on convs alone)")
        print(f"non-conv + fusion overhead: {step_ms - conv_ms:.1f} ms "
              f"({(step_ms - conv_ms) / step_ms * 100:.0f}% of step)")
        if mm_tf:
            print(f"matmul ceiling for reference: {mm_tf:.1f} TF")
        # per-stage share: where would a 10% conv speedup buy the most?
        by_stage = {}
        for stage, _, c, _, dt_fb, _, _ in rows:
            by_stage[stage] = by_stage.get(stage, 0.0) + c * dt_fb * 1e3
        print("per-stage conv ms: " + ", ".join(f"{k}={v:.1f}" for k, v in by_stage.items()))

    timer.cancel()


if __name__ == "__main__":
    main()
