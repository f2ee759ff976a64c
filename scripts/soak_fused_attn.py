"""On-chip soak for the fused kernels (run when a TPU is healthy).

Validates ops/attention.py against the XLA path on real hardware at BoTNet
shapes (fwd values, gradients, and speed), then prints the verdict. PASS
means the numerics hold; the speedup line is the flip/keep signal for
DTPU_FUSED_ATTN. 2026-07-31 measured verdict: 0.771x — XLA wins at these
shapes, default stays off.

    python scripts/soak_fused_attn.py

``--moe`` soaks the fused MoE dispatch/combine kernels
(ops/moe_kernel.py) against the einsum formulation instead — fwd + grad
numerics plus the dispatch/combine microbench that is the flip/keep
signal for DTPU_FUSED_MOE. Off-TPU the kernels run in the Pallas
interpreter: numerics still hold (the CI kernels-smoke job asserts this
runs), timings are meaningless there.

``--epilogue`` soaks the fused conv-epilogue kernels (ops/epilogue.py)
against the unfused BN→(+residual)→ReLU formulation at resnet50
hot-block shapes — fwd + grad numerics plus the fwd+bwd microbench that
is the flip/keep signal for DTPU_FUSED_EPILOGUE / MODEL.FUSED_EPILOGUE.
Same interpreter caveat off-TPU; the docs/PERFORMANCE.md attention row
is the reason every kernel measures before any default flips.

``--seq`` soaks the LARGE-L regime (ISSUE 15): the blockwise fused
attention kernels at L=1024 against the XLA path (fwd + grad numerics,
fwd+bwd microbench — the flip/keep signal for DTPU_FUSED_ATTN at large
L, where the small-L measured loss no longer applies), plus ring vs
Ulysses vs dense attention over a seq mesh. Emits ONE JSON verdict line
(docs/PERFORMANCE.md "Large-L kernels"); off-TPU the timings are
interpreter/CPU noise and the verdict field says so.

Every mode now WRITES its verdict through the perfdb registry
(obs/perfdb.py) as well as printing it: one typed ``kernel_verdict``
journal record per measurement, keyed (device_kind, family, shape-class)
— this is how switch_* defaults flip themselves on a measured on-chip
>1× and unflip on regression, instead of a human copying JSON off
stdout. ``--registry``/``--journal`` redirect the writes (ALWAYS point
them at /tmp for experimental runs — the default path is the committed
registry), ``--no-registry`` restores print-only behavior,
``--trust-interpret`` lets interpreter timings count as flips (CI
fixtures only — never trust interpreter speed), and ``--autotune``
additionally sweeps the estimator-priced candidate tilings and caches
the measured winner in the registry (attention-blockwise under --seq,
epilogue row tiles under --epilogue).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _registry_db(args):
    """The PerfDB writer the flags select, or None for print-only runs."""
    if args.no_registry:
        return None
    from distribuuuu_tpu.obs import perfdb

    try:
        return perfdb.PerfDB(args.registry)
    except ValueError:  # DTPU_PERFDB=0 and no explicit --registry
        print("(perfdb disabled: verdict printed only)", flush=True)
        return None


def _write_verdict(args, family, dims, *, speedup, fused_ms, baseline_ms,
                   interpret, numerics, block=None, extra=None):
    """Print one JSON verdict line AND persist it through the registry.

    The printed line carries the same device_kind/shape_class key the
    registry entry is stored under, so a human and the machinery read the
    same verdict. Returns the registry entry (with its flip/unflip
    transition) or None when the registry is off.
    """
    import json

    import jax

    from distribuuuu_tpu.obs import perfdb

    device_kind = jax.devices()[0].device_kind
    shape_cls = perfdb.shape_class(**dims)
    line = {
        "metric": "kernel_verdict",
        "kernel_family": family,
        "device_kind": device_kind,
        "shape_class": shape_cls,
        "speedup": round(float(speedup), 3),
        "fused_ms": round(float(fused_ms), 3),
        "baseline_ms": round(float(baseline_ms), 3),
        "interpret": bool(interpret),
        "numerics": numerics,
    }
    if block is not None:
        line["block"] = int(block)
    if extra:
        line.update(extra)
    entry = None
    db = _registry_db(args)
    if db is not None:
        entry = db.record_verdict(
            family,
            shape_cls,
            speedup=float(speedup),
            device_kind=device_kind,
            fused_ms=float(fused_ms),
            baseline_ms=float(baseline_ms),
            interpret=bool(interpret),
            trust_interpret=args.trust_interpret,
            numerics=numerics,
            source="soak",
            block=block,
            journal=args.journal if args.journal else True,
        )
        line["flip"] = entry["flip"]
        line["transition"] = entry["transition"]
    else:
        line["flip"] = bool(
            (not interpret or args.trust_interpret)
            and float(speedup) > 1.0
            and numerics == "pass"
        )
    print(json.dumps(line), flush=True)
    return entry


def main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.attention import (
        fused_attention,
        fused_attention_abs,
        xla_attention,
    )

    print(f"devices: {jax.devices()}", flush=True)
    rng = np.random.default_rng(0)
    B, N, L, D = 64, 4, 196, 128  # botnet50 stage-4 shapes, batch 64
    q = jnp.asarray(rng.standard_normal((B, N, L, D)) * 0.1, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, N, L, D)) * 0.1, jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, N, L, D)), jnp.bfloat16)
    bias = jnp.asarray(rng.standard_normal((B, N, L, L)), jnp.float32)

    # jitted callables bound ONCE up front (not jit-then-call per use): the
    # compile cache stays keyed on stable function objects — dtpu-lint DT003
    jit_fused = jax.jit(fused_attention)
    jit_xla = jax.jit(xla_attention)

    # 1) forward parity
    out_f = jax.device_get(jit_fused(q, k, v, bias))
    out_x = jax.device_get(jit_xla(q, k, v, bias))
    fwd_diff = np.max(np.abs(out_f.astype(np.float32) - out_x.astype(np.float32)))
    print(f"fwd max|diff| = {fwd_diff:.4f} (bf16 tolerance ~0.05)", flush=True)

    # 2) gradient parity
    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    grad_fused = jax.jit(jax.grad(loss(fused_attention), argnums=(0, 1, 2, 3)))
    grad_xla = jax.jit(jax.grad(loss(xla_attention), argnums=(0, 1, 2, 3)))
    gf = jax.device_get(grad_fused(q, k, v, bias))
    gx = jax.device_get(grad_xla(q, k, v, bias))
    grad_diff = max(
        float(np.max(np.abs(a.astype(np.float32) - b.astype(np.float32))))
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gx))
    )
    print(f"grad max|diff| = {grad_diff:.4f}", flush=True)

    # 3) speed (jits built in the iter expression: evaluated once, not per tick)
    for name, f in [("fused", jax.jit(loss(fused_attention))), ("xla", jax.jit(loss(xla_attention)))]:
        jax.device_get(f(q, k, v, bias))
        t0 = time.perf_counter()
        for _ in range(10):
            jax.device_get(f(q, k, v, bias))
        print(f"{name}: {(time.perf_counter() - t0) / 10 * 1000:.2f} ms", flush=True)

    # 4) abs-table path (botnet50's default position bias): the fused arm
    # forms q·embᵀ in VMEM; the fair XLA arm must therefore INCLUDE the
    # bias matmul + [B,N,L,L] materialization it absorbs
    emb = jnp.asarray(rng.standard_normal((L, D)) * 0.1, jnp.float32)

    def loss_abs_fused(q, k, v, emb):
        return jnp.sum(fused_attention_abs(q, k, v, emb).astype(jnp.float32) ** 2)

    def loss_abs_xla(q, k, v, emb):
        bias_ = jnp.einsum("bnid,jd->bnij", q, emb.astype(q.dtype))
        return jnp.sum(xla_attention(q, k, v, bias_).astype(jnp.float32) ** 2)

    jit_abs_fused = jax.jit(loss_abs_fused)
    jit_abs_xla = jax.jit(loss_abs_xla)
    oaf = jax.device_get(jit_abs_fused(q, k, v, emb))
    oax = jax.device_get(jit_abs_xla(q, k, v, emb))
    abs_fwd_rel = float(abs(oaf - oax) / max(abs(oax), 1e-6))
    print(f"abs fwd rel|diff| = {abs_fwd_rel:.5f}", flush=True)
    grad_abs_fused = jax.jit(jax.grad(loss_abs_fused, argnums=(0, 1, 2, 3)))
    grad_abs_xla = jax.jit(jax.grad(loss_abs_xla, argnums=(0, 1, 2, 3)))
    gaf = jax.device_get(grad_abs_fused(q, k, v, emb))
    gax = jax.device_get(grad_abs_xla(q, k, v, emb))
    abs_grad_diff = max(
        float(np.max(np.abs(a.astype(np.float32) - b.astype(np.float32))))
        for a, b in zip(jax.tree.leaves(gaf), jax.tree.leaves(gax))
    )
    print(f"abs grad max|diff| = {abs_grad_diff:.4f}", flush=True)
    abs_ms = {}
    for name, f in [("abs-fused", jax.jit(jax.grad(loss_abs_fused))),
                    ("abs-xla", jax.jit(jax.grad(loss_abs_xla)))]:
        jax.device_get(f(q, k, v, emb))
        t0 = time.perf_counter()
        for _ in range(10):
            jax.device_get(f(q, k, v, emb))
        abs_ms[name] = (time.perf_counter() - t0) / 10 * 1000
        print(f"{name} (fwd+bwd): {abs_ms[name]:.2f} ms", flush=True)
    print(
        f"abs speedup: {abs_ms['abs-xla'] / abs_ms['abs-fused']:.3f}x "
        f"(>1 = fused wins)", flush=True,
    )

    ok = fwd_diff < 0.1 and grad_diff < 1.0 and abs_fwd_rel < 0.02 and abs_grad_diff < 1.0
    interpret = jax.devices()[0].platform != "tpu"
    _write_verdict(
        args, "attention", {"l": L, "d": D, "dv": D},
        speedup=abs_ms["abs-xla"] / abs_ms["abs-fused"],
        fused_ms=abs_ms["abs-fused"], baseline_ms=abs_ms["abs-xla"],
        interpret=interpret, numerics="pass" if ok else "fail",
    )
    print(
        "SOAK",
        "PASS (numerics hold; see the speedup line for the flip/keep verdict)"
        if ok
        else "FAIL",
        flush=True,
    )
    sys.exit(0 if ok else 1)


def main_moe(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.moe_kernel import (
        fused_moe_combine,
        fused_moe_dispatch,
        oracle_combine,
        oracle_dispatch,
    )

    print(f"devices: {jax.devices()}", flush=True)
    interpret = jax.devices()[0].platform != "tpu"
    if interpret:
        print("(no TPU: Pallas interpreter — numerics only, ignore timings)", flush=True)
    rng = np.random.default_rng(0)
    # a realistic per-device shard: 8k tokens, E=8 experts, C=1.25n/E. D=128
    # keeps the [E, C, D] buffer + [T, E·C] mask inside the kernels' VMEM
    # budget — larger shards trip the guard and fall back to the einsum
    # formulation (the soak would then time einsum vs einsum and say nothing)
    N, D, E = 8192, 128, 8
    C = int(1.25 * N / E)
    x = jnp.asarray(rng.standard_normal((N, D)) * 0.5, jnp.float32)
    gate = jnp.asarray(rng.standard_normal((D, E)) * 0.1, jnp.float32)

    # 1) dispatch parity (send buffer + routing metadata + aux sums).
    # jitted callables bound ONCE up front (not jit-then-call per use): the
    # compile cache stays keyed on stable function objects — dtpu-lint DT003
    jit_dispatch = jax.jit(
        lambda x_, g_: fused_moe_dispatch(x_, g_, capacity=C, interpret=interpret)
    )
    jit_oracle_dispatch = jax.jit(lambda x_, g_: oracle_dispatch(x_, g_, C))
    send_f, top_f, pos_f, w_f, fp_f = jax.device_get(jit_dispatch(x, gate))
    send_o, top_o, pos_o, w_o, fp_o = jax.device_get(
        jit_oracle_dispatch(x, gate)
    )
    send_diff = float(np.max(np.abs(send_f - send_o)))
    meta_ok = bool(np.array_equal(top_f, top_o) and np.array_equal(pos_f, pos_o))
    w_diff = float(np.max(np.abs(w_f - w_o)))
    print(f"dispatch max|Δsend| = {send_diff:.2e}, metadata equal = {meta_ok}, "
          f"max|Δw| = {w_diff:.2e}", flush=True)

    # 2) combine parity
    back = jnp.asarray(rng.standard_normal((E, C, D)), jnp.float32)
    jit_combine = jax.jit(
        lambda b_, t_, p_, w_: fused_moe_combine(b_, t_, p_, w_, interpret=interpret)
    )
    jit_oracle_combine = jax.jit(oracle_combine)
    out_f = jax.device_get(jit_combine(back, top_f, pos_f, w_f))
    out_o = jax.device_get(jit_oracle_combine(back, top_o, pos_o, w_o))
    out_diff = float(np.max(np.abs(out_f - out_o)))
    print(f"combine max|Δout| = {out_diff:.2e}", flush=True)

    # 3) grad parity through dispatch -> (stand-in expert) -> combine
    def loss(dispatch, combine):
        def f(x_, g_, b0):
            send, top, pos, w, fp = dispatch(x_, g_)
            out = combine(jnp.tanh(send) + b0, top, pos, w)
            return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * jnp.sum(fp[0] * fp[1])
        return f

    fused_loss = loss(
        lambda x_, g_: fused_moe_dispatch(x_, g_, capacity=C, interpret=interpret),
        lambda b_, t_, p_, w_: fused_moe_combine(b_, t_, p_, w_, interpret=interpret),
    )
    oracle_loss = loss(lambda x_, g_: oracle_dispatch(x_, g_, C), oracle_combine)
    grad_fused = jax.jit(jax.grad(fused_loss, argnums=(0, 1, 2)))
    grad_oracle = jax.jit(jax.grad(oracle_loss, argnums=(0, 1, 2)))
    gf = jax.device_get(grad_fused(x, gate, back))
    go = jax.device_get(grad_oracle(x, gate, back))
    grad_diff = max(
        float(np.max(np.abs(a - b))) for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(go))
    )
    print(f"grad max|diff| = {grad_diff:.2e}", flush=True)

    # 4) microbench: the dispatch+combine round trip both ways (the einsum
    # arm materializes the [n, E, C] mask in HBM twice; the fused arm keeps
    # it VMEM-resident — the whole point)
    ms = {}
    for name, f in [("fused", jax.jit(fused_loss)), ("einsum", jax.jit(oracle_loss))]:
        jax.device_get(f(x, gate, back))
        t0 = time.perf_counter()
        for _ in range(10):
            jax.device_get(f(x, gate, back))
        ms[name] = (time.perf_counter() - t0) / 10 * 1000
        print(f"{name} dispatch+combine (fwd+bwd): {ms[name]:.2f} ms", flush=True)
    print(
        f"moe speedup: {ms['einsum'] / ms['fused']:.3f}x (>1 = fused wins"
        f"{'; interpreter — not meaningful' if interpret else ''})",
        flush=True,
    )

    ok = send_diff < 1e-4 and meta_ok and w_diff < 1e-6 and out_diff < 1e-4 and grad_diff < 1e-3
    _write_verdict(
        args, "moe", {"n": N, "d": D, "e": E, "c": C},
        speedup=ms["einsum"] / ms["fused"],
        fused_ms=ms["fused"], baseline_ms=ms["einsum"],
        interpret=interpret, numerics="pass" if ok else "fail",
    )
    print("SOAK", "PASS (numerics hold; see the speedup line for the "
          "flip/keep verdict)" if ok else "FAIL", flush=True)
    sys.exit(0 if ok else 1)


def main_epilogue(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.epilogue import fused_conv_epilogue, oracle_epilogue

    print(f"devices: {jax.devices()}", flush=True)
    interpret = jax.devices()[0].platform != "tpu"
    if interpret:
        print("(no TPU: Pallas interpreter — numerics only, ignore timings)", flush=True)
    rng = np.random.default_rng(0)
    # resnet50 stage-3 hot-block epilogue at batch 64: the conv output is
    # bf16, the BN boundary bf16 (the shipped-best recipe), residual in the
    # boundary dtype — [64·14·14, 1024] rows×channels per pass
    B, H, C = 64, 14, 1024
    bn_dtype = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((B, H, H, C)) * 0.5, jnp.bfloat16)
    identity = jnp.asarray(rng.standard_normal((B, H, H, C)), bn_dtype)
    mean = jnp.asarray(rng.standard_normal(C), jnp.float32)
    var = jnp.asarray(np.abs(rng.standard_normal(C)) + 0.1, jnp.float32)
    scale = jnp.asarray(rng.standard_normal(C), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(C), jnp.float32)
    mul = jax.lax.rsqrt(var + 1e-5) * scale

    def fused(x_, me, mu, bi, id_):
        return fused_conv_epilogue(
            x_, me, mu, bi, id_, relu=True, bn_dtype=bn_dtype, interpret=interpret
        )

    def unfused(x_, me, mu, bi, id_):
        return oracle_epilogue(x_, me, mu, bi, id_, relu=True, bn_dtype=bn_dtype)

    # jitted callables bound ONCE up front (not jit-then-call per use): the
    # compile cache stays keyed on stable function objects — dtpu-lint DT003
    jit_fused = jax.jit(fused)
    jit_unfused = jax.jit(unfused)

    # 1) forward parity (tolerance = XLA's FMA liberty at bf16 output scale)
    out_f = jax.device_get(jit_fused(x, mean, mul, bias, identity))
    out_u = jax.device_get(jit_unfused(x, mean, mul, bias, identity))
    fwd_diff = float(np.max(np.abs(out_f.astype(np.float32) - out_u.astype(np.float32))))
    print(f"fwd max|diff| = {fwd_diff:.5f} (bf16 boundary tolerance ~0.05)", flush=True)

    # 2) gradient parity through the custom VJP (the oracle recompute)
    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    grad_fused = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2, 3, 4)))
    grad_unfused = jax.jit(jax.grad(loss(unfused), argnums=(0, 1, 2, 3, 4)))
    gf = jax.device_get(grad_fused(x, mean, mul, bias, identity))
    gu = jax.device_get(grad_unfused(x, mean, mul, bias, identity))
    grad_diff = max(
        float(np.max(np.abs(a.astype(np.float32) - b.astype(np.float32))))
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gu))
    )
    print(f"grad max|diff| = {grad_diff:.5f}", flush=True)

    # 3) microbench: the epilogue fwd+bwd both ways — the unfused arm is
    # what XLA's own fusion emitter does with the BN/add/relu edges today,
    # so >1x here is the flip signal for the HBM-round-trip argument
    ms = {}
    for name, f in [
        ("fused", jax.jit(jax.grad(loss(fused)))),
        ("unfused", jax.jit(jax.grad(loss(unfused)))),
    ]:
        jax.device_get(f(x, mean, mul, bias, identity))
        t0 = time.perf_counter()
        for _ in range(10):
            jax.device_get(f(x, mean, mul, bias, identity))
        ms[name] = (time.perf_counter() - t0) / 10 * 1000
        print(f"{name} epilogue (fwd+bwd): {ms[name]:.2f} ms", flush=True)
    print(
        f"epilogue speedup: {ms['unfused'] / ms['fused']:.3f}x (>1 = fused wins"
        f"{'; interpreter — not meaningful' if interpret else ''})",
        flush=True,
    )

    ok = fwd_diff < 0.05 and grad_diff < 1.0
    rows = B * H * H
    best_rows = None
    if args.autotune:
        # sweep the estimator-priced row tiles on this device and cache the
        # winner; each candidate is a distinct static block_rows, so one jit
        # bind per candidate (not jit-then-call per tick — dtpu-lint DT003)
        from distribuuuu_tpu.obs import perfdb
        from distribuuuu_tpu.ops.epilogue import candidate_block_rows

        itemsize = np.dtype(jnp.bfloat16).itemsize
        cands = candidate_block_rows(rows, C, itemsize, itemsize, itemsize)
        db = _registry_db(args)

        def measure(t):
            f = jax.jit(
                jax.grad(loss(lambda *a: fused_conv_epilogue(
                    *a, relu=True, bn_dtype=bn_dtype, block_rows=t,
                    interpret=interpret,
                )))
            )
            jax.device_get(f(x, mean, mul, bias, identity))
            t0 = time.perf_counter()
            for _ in range(5):
                jax.device_get(f(x, mean, mul, bias, identity))
            return (time.perf_counter() - t0) / 5 * 1000

        if db is not None and cands:
            best_rows, cached = perfdb.autotune(
                db, "epilogue", perfdb.shape_class(r=rows, c=C), cands, measure,
                journal=args.journal if args.journal else True,
            )
            print(
                f"autotune block_rows: winner {best_rows} over {cands}"
                f"{' (registry cache hit)' if cached else ''}",
                flush=True,
            )
    _write_verdict(
        args, "epilogue", {"r": rows, "c": C},
        speedup=ms["unfused"] / ms["fused"],
        fused_ms=ms["fused"], baseline_ms=ms["unfused"],
        interpret=interpret, numerics="pass" if ok else "fail",
        block=best_rows,
    )
    print("SOAK", "PASS (numerics hold; see the speedup line for the "
          "flip/keep verdict)" if ok else "FAIL", flush=True)
    sys.exit(0 if ok else 1)


def main_seq(args):
    """--seq: the large-L verdict. Blockwise fused attention vs XLA at
    L=1024 (numerics + fwd+bwd microbench) and ring/Ulysses/dense attention
    over a seq mesh. Prints one JSON verdict line; `flip` is meaningful
    ON-CHIP only (the `interpret` field marks CPU runs)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distribuuuu_tpu.ops import attention as att
    from distribuuuu_tpu.parallel.seq import seq_attention
    from distribuuuu_tpu.runtime import create_mesh

    interpret = jax.devices()[0].platform != "tpu"
    print(f"devices: {jax.devices()}", flush=True)
    rng = np.random.default_rng(0)
    # L=1024: past the single-tile VMEM budget, the regime the blockwise
    # re-tiling exists for. Small batch off-TPU (interpreter grids are
    # python loops); ViT-B head shapes on chip.
    B, N, L, D = (1, 2, 1024, 64) if interpret else (8, 12, 1024, 64)
    dt = jnp.float32 if interpret else jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((B, N, L, D)) * 0.1, dt)
    k = jnp.asarray(rng.standard_normal((B, N, L, D)) * 0.1, dt)
    v = jnp.asarray(rng.standard_normal((B, N, L, D)), dt)
    bias = jnp.asarray(rng.standard_normal((B, N, L, L)) * 0.1, jnp.float32)

    fused = functools.partial(att.fused_attention, interpret=interpret)
    fallbacks_before = att._VMEM_GUARD.fallbacks

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    # jitted callables bound once up front (not jit-then-call per use): the
    # compile cache stays keyed on stable function objects — dtpu-lint DT003
    jit_fused = jax.jit(fused)
    jit_xla = jax.jit(att.xla_attention)
    jit_grad_fused = jax.jit(jax.grad(loss(fused), argnums=(0, 3)))
    jit_grad_xla = jax.jit(jax.grad(loss(att.xla_attention), argnums=(0, 3)))
    out_f = jax.device_get(jit_fused(q, k, v, bias))
    out_x = jax.device_get(jit_xla(q, k, v, bias))
    fwd_diff = float(np.max(np.abs(out_f.astype(np.float32) - out_x.astype(np.float32))))
    gf = jax.device_get(jit_grad_fused(q, k, v, bias))
    gx = jax.device_get(jit_grad_xla(q, k, v, bias))
    grad_diff = max(
        float(np.max(np.abs(a.astype(np.float32) - b.astype(np.float32))))
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gx))
    )
    assert att._VMEM_GUARD.fallbacks == fallbacks_before, (
        "blockwise dispatch fell back to XLA — the soak measured nothing"
    )

    ms = {}
    for name, f in [("fused", jax.jit(jax.grad(loss(fused)))),
                    ("xla", jax.jit(jax.grad(loss(att.xla_attention))))]:
        jax.device_get(f(q, k, v, bias))
        t0 = time.perf_counter()
        for _ in range(3 if interpret else 10):
            jax.device_get(f(q, k, v, bias))
        ms[name] = (time.perf_counter() - t0) / (3 if interpret else 10) * 1000

    # ring vs Ulysses vs dense over a seq mesh (fwd+bwd of sum-of-squares)
    n_dev = jax.device_count()
    p = 1
    for cand in (8, 4, 2):
        if n_dev % cand == 0 and N % cand == 0 and L % cand == 0:
            p = cand
            break
    seq_ms = {}
    if p > 1:
        mesh = create_mesh({"seq": p}, devices=jax.devices()[:p])
        spec = P(None, None, "seq", None)

        def arm(impl):
            def member(q_, k_, v_):
                if impl == "dense":
                    s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_,
                                   preferred_element_type=jnp.float32)
                    w = jax.nn.softmax(s * (D ** -0.5), axis=-1)
                    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v_.dtype), v_)
                else:
                    out = seq_attention(q_, k_, v_, impl=impl)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            in_specs = (P(),) * 3 if impl == "dense" else (spec,) * 3
            mapped = jax.shard_map(member, mesh=mesh, in_specs=in_specs,
                                   out_specs=P(), check_vma=False)
            return jax.jit(jax.grad(lambda a, b, c: mapped(a, b, c), argnums=0))

        for impl in ("dense", "ring", "ulysses"):
            f = arm(impl)
            jax.device_get(f(q, k, v))
            t0 = time.perf_counter()
            for _ in range(3):
                jax.device_get(f(q, k, v))
            seq_ms[f"{impl}_ms"] = round((time.perf_counter() - t0) / 3 * 1000, 2)

    tol = 0.05 if dt == jnp.bfloat16 else 1e-3
    ok = fwd_diff < tol and grad_diff < (1.0 if dt == jnp.bfloat16 else 0.05)
    speedup = ms["xla"] / ms["fused"]

    best_blk = None
    if args.autotune:
        # sweep the estimator-priced blockwise window sizes and cache the
        # measured winner under family "attention_blk" — _pick_block consults
        # it before its own largest-fits heuristic. One jit bind per
        # candidate block (static nondiff arg), not per tick — dtpu-lint DT003
        from distribuuuu_tpu.obs import perfdb

        cands = att.candidate_blocks(L, D, D, q.dtype.itemsize, True)
        db = _registry_db(args)

        def measure(blk):
            f = jax.jit(jax.grad(loss(functools.partial(
                att._fused_attention_blk, block=blk, interpret=interpret))))
            jax.device_get(f(q, k, v, bias))
            reps = 2 if interpret else 5
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.device_get(f(q, k, v, bias))
            return (time.perf_counter() - t0) / reps * 1000

        if db is not None and cands:
            best_blk, cached = perfdb.autotune(
                db, "attention_blk", perfdb.shape_class(l=L, d=D, dv=D),
                cands, measure,
                journal=args.journal if args.journal else True,
            )
            print(
                f"autotune block: winner {best_blk} over {cands}"
                f"{' (registry cache hit)' if cached else ''}",
                flush=True,
            )

    # one JSON verdict line — the registry write and the printed line share
    # the (device_kind, family, shape_class) key; `metric`/`fused_speedup`
    # stay for the docs/PERFORMANCE.md "Large-L kernels" contract
    _write_verdict(
        args, "attention", {"l": L, "d": D, "dv": D},
        speedup=speedup,
        fused_ms=ms["fused"], baseline_ms=ms["xla"],
        interpret=interpret, numerics="pass" if ok else "fail",
        block=best_blk,
        extra={
            "metric": "seq_soak",
            "l": L,
            "heads": N,
            "batch": B,
            "xla_ms": round(ms["xla"], 2),
            "fused_speedup": round(speedup, 3),
            "seq": p,
            "fwd_maxdiff": round(fwd_diff, 5),
            "grad_maxdiff": round(grad_diff, 5),
            **seq_ms,
        },
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    which = parser.add_mutually_exclusive_group()
    which.add_argument(
        "--moe", action="store_true",
        help="soak the fused MoE dispatch/combine kernels instead of attention",
    )
    which.add_argument(
        "--epilogue", action="store_true",
        help="soak the fused conv-epilogue kernels instead of attention",
    )
    which.add_argument(
        "--seq", action="store_true",
        help="soak the large-L blockwise attention + ring/Ulysses arms; "
        "emits the flip/keep verdict JSON",
    )
    parser.add_argument(
        "--registry", default=None,
        help="perfdb registry path to write the verdict into (default: the "
        "committed perfdb/registry.json — point at /tmp for experiments)",
    )
    parser.add_argument(
        "--journal", default=None,
        help="journal path for the kernel_verdict record (default: "
        "verdicts.jsonl next to the registry)",
    )
    parser.add_argument(
        "--no-registry", action="store_true",
        help="print the verdict only; do not touch any registry",
    )
    parser.add_argument(
        "--trust-interpret", action="store_true",
        help="let interpreter timings count toward the flip decision "
        "(CI fixtures only — interpreter speed is not chip speed)",
    )
    parser.add_argument(
        "--autotune", action="store_true",
        help="also sweep candidate tilings and cache the measured winner "
        "(--seq: attention block; --epilogue: block_rows)",
    )
    args = parser.parse_args()
    if args.moe:
        main_moe(args)
    elif args.epilogue:
        main_epilogue(args)
    elif args.seq:
        main_seq(args)
    else:
        main(args)
