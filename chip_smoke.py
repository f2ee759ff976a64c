"""Quickest proof that the trainer still starts on the chip.

    python chip_smoke.py                  # one TPU chip: train, eval, kernels
    python chip_smoke.py --chips 4        # four chips: data-parallel vs one-chip only

One process, which is the one that holds the chip; it starts no child. It
fails at once unless JAX reports a TPU. The phases drive the entry points a
user calls — `load_cfg_fom_args` + `trainer.train_model` exactly as
`train_net.main` does, `trainer.test_model` as `test_net.py` does — with
`config/resnet50.yaml` as shipped (full-width resnet50, 224 px, bf16, s2d
stem, batch 32 per device) on the reference's own seeded `DummyDataset`,
weights from ``--seed``. Each phase prints one JSON line; a phase that
fails raises, so the script exits non-zero and prints no result. The last
line of stdout is the result: ``{"ok": true, "device": {...}}``.

``--rehearse-cpu`` is for the sandbox that has no chip: it lifts the device
check, asks for the Pallas interpreter and shrinks every size, so the
control flow can be walked on `JAX_PLATFORMS=cpu` (add
`XLA_FLAGS=--xla_force_host_platform_device_count=4` for ``--chips 4``).
Nothing a rehearsal prints is a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# resnet50's conv-output shapes at batch 64 (rows = B·H·W, channels): what
# `models/layers.bn_epilogue` hands the fused epilogue, one per stage width
EPILOGUE_SHAPES = [
    (64, 56, 56, 64),
    (64, 56, 56, 256),
    (64, 28, 28, 512),
    (64, 14, 14, 1024),
    (64, 7, 7, 2048),
]
# (batch, heads, L, d): botnet50's MHSA at 224 px, and the 4x-token case
ATTENTION_SHAPES = [(8, 4, 196, 128), (4, 4, 784, 64)]
# (batch, L, heads, hd) of the packed-qkv pair: vit_b16.train's own shape,
# then MAE's 50-token encoder and its hd-32 decoder (four heads a lane group)
SELF_ATTENTION_SHAPES = [(128, 197, 12, 64), (8, 50, 12, 64), (8, 197, 16, 32)]

# max|fused - ref| over max|ref|, per output and per gradient. Epilogue: one
# bf16 ulp at the top of the range forward (elementwise, both round once at
# the same place; XLA may contract the multiply-add or keep excess precision
# across the bf16 cast inside its own fusion), two for the gradient (its
# cotangent is made from that output and rounds once more). Attention: the
# repo's own bf16 tolerance (tests/test_ops_attention.py) — several bf16
# matmuls deep.
EPILOGUE_TOL = 2.0**-7
EPILOGUE_GRAD_TOL = 2.0**-6
ATTENTION_TOL = 2e-2
# three bf16 resnet50 steps from one state, fused against unfused epilogue
FUSED_LOSS_RTOL = 2e-2
# four-chip against one-chip data parallel: same global batch, SyncBN, seed
DP_LOSS_RTOL = 5e-2
DP_PARAM_REL_L2 = 5e-2
# both arms train at a tenth of the shipped LR: on all-zero labels the shipped
# one drives the loss to 0 within a step or two, and a comparison of two
# trajectories through that jump measures bf16 noise, not the collectives
DP_BASE_LR = 0.02


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--out", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
        help="output directory (emptied first)",
    )
    p.add_argument(
        "--rehearse-cpu", action="store_true",
        help="sandbox rehearsal: no device check, Pallas interpreter, tiny sizes",
    )
    args = p.parse_args(argv)
    args.out = os.path.abspath(args.out)
    return args


def device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


class Phase:
    """Clock and compile counters around one phase; `line` prints its record."""

    def __init__(self, name: str, bridge):
        self.name = name
        self._bridge = bridge
        self._since = bridge.snapshot()
        self._tic = time.time()

    def line(self, **checked) -> None:
        from distribuuuu_tpu.obs.monitors import BACKEND_COMPILE_EVENT

        delta = self._bridge.delta(self._bridge.snapshot(), self._since)
        compiles = delta["durations"].get(BACKEND_COMPILE_EVENT, {"count": 0, "total_s": 0.0})
        dev = device_record()
        record = {
            "phase": self.name,
            "platform": dev["platform"],
            "device_kind": dev["kind"],
            "device_count": dev["count"],
            "wall_s": round(time.time() - self._tic, 3),
            "compile_s": round(compiles["total_s"], 3),
            "backend_compiles": compiles["count"],
            **checked,
        }
        print(json.dumps(record), flush=True)


def load_cfg(argv: list[str]) -> None:
    """What `train_net.main`/`test_net.main` do before calling the trainer."""
    from distribuuuu_tpu import config

    config.reset_cfg()
    config.load_cfg_fom_args("chip_smoke", argv=argv)
    config.cfg.freeze()


def base_argv(args, out_dir: str, *extra) -> list[str]:
    argv = [
        "--cfg", os.path.join(ROOT, "config", "resnet50.yaml"),
        "MODEL.DUMMY_INPUT", "True",
        "OPTIM.MAX_EPOCH", "1",
        "RNG_SEED", str(args.seed),
        "OUT_DIR", out_dir,
        *(str(e) for e in extra),
    ]
    if args.rehearse_cpu:
        # sandbox sizes: resnet50's graph, nothing else kept
        argv += [
            "TRAIN.IM_SIZE", "64", "TEST.IM_SIZE", "72", "TEST.CROP_SIZE", "64",
            "MODEL.NUM_CLASSES", "10", "TRAIN.DUMMY_EPOCH_SAMPLES", "64",
        ]
        if "TRAIN.BATCH_SIZE" not in argv:
            argv += ["TRAIN.BATCH_SIZE", "8"]
        if "TEST.BATCH_SIZE" not in argv:
            argv += ["TEST.BATCH_SIZE", "8"]
    return argv


def run_train(argv: list[str]):
    """`train_net.main` in this process: returns (final state, journal records)."""
    from distribuuuu_tpu import obs, resilience, trainer
    from distribuuuu_tpu.config import cfg

    load_cfg(argv)
    code, result = resilience.call_with_poison_exit(trainer.train_model)
    check(code == 0, f"train_model exited with the poison code {code}")
    state, _best = result
    records = list(obs.read_journal(obs.journal_path(cfg.OUT_DIR)))
    return state, records


def journal_checks(records: list[dict], platform: str) -> dict:
    """What every training run of this script must show in its journal."""
    import math

    starts = [r for r in records if r.get("kind") == "run_start"]
    check(len(starts) == 1, f"expected one run_start record, found {len(starts)}")
    check(
        starts[0]["platform"] == platform,
        f"journal run_start says platform {starts[0]['platform']!r}, JAX says {platform!r}",
    )
    windows = [r for r in records if r.get("kind") == "window"]
    check(windows, "the journal holds no window record")
    losses = [w["loss"] for w in windows]
    check(
        all(l is not None and math.isfinite(l) for l in losses),
        f"a logged loss is not finite: {losses}",
    )
    skipped = sum(w["skipped"] for w in windows)
    check(skipped == 0, f"the non-finite guard skipped {skipped} step(s)")
    evals = [r for r in records if r.get("kind") == "eval"]
    check(len(evals) == 1, f"expected one epoch-end eval record, found {len(evals)}")
    steps = sum(w["steps"] for w in windows)
    return {
        "steps": steps,
        "losses": losses,
        "skipped_steps": skipped,
        "eval": {k: evals[0][k] for k in ("acc1", "acck", "loss", "samples")},
        "journal_platform": starts[0]["platform"],
    }


def params_to_host(state):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, state.params)


def rel_l2(a_tree, b_tree) -> float:
    import jax
    import numpy as np

    num = den = 0.0
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
        num += float(np.sum((a64 - b64) ** 2))
        den += float(np.sum(b64**2))
    return (num / max(den, 1e-30)) ** 0.5


def cache_counters(bridge) -> dict:
    snap = bridge.snapshot()
    out = {k: v for k, v in snap["counters"].items() if k.startswith("/jax/compilation_cache/")}
    for k, v in snap["durations"].items():
        if k.startswith("/jax/compilation_cache/"):
            out[k] = round(v["total_s"], 3)
    return out


# ---------------------------------------------------------------------------
# phases on one chip
# ---------------------------------------------------------------------------

def phase_train(args, bridge) -> tuple[str, dict]:
    """`train_net.py --cfg config/resnet50.yaml MODEL.DUMMY_INPUT True
    OPTIM.MAX_EPOCH 1 OUT_DIR ...`; returns the checkpoint it wrote and its
    epoch-end eval."""
    import jax

    from distribuuuu_tpu import checkpoint as ckpt
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.runtime import data_mesh

    ph = Phase("train", bridge)
    out_dir = os.path.join(args.out, "train")
    state, records = run_train(base_argv(args, out_dir))
    checked = journal_checks(records, jax.devices()[0].platform)

    # parameters changed: against the init this seed gives (same key split as
    # train_model's; the init program is a compile-cache hit)
    init_key, _ = jax.random.split(jax.random.PRNGKey(args.seed))
    mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
    init_state, _ = trainer._model_globals_scoped(
        lambda: trainer.create_train_state(
            trainer._build_cfg_model(), init_key, mesh, cfg.TRAIN.IM_SIZE
        )
    )()
    moved = rel_l2(params_to_host(state), params_to_host(init_state))
    check(moved > 0.0, "parameters did not change from their init")

    path = ckpt.get_last_checkpoint(out_dir)
    status, errors = ckpt.verify_checkpoint(path)
    check(status == "ok", f"checkpoint {path} does not verify: {status} {errors}")

    ph.line(
        **checked,
        params_rel_l2_from_init=moved,
        checkpoint=os.path.relpath(path, args.out),
        checkpoint_status=status,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        compile_cache_counters=cache_counters(bridge),
    )
    return path, checked["eval"]


def phase_eval(args, bridge, weights: str, train_eval: dict) -> None:
    """`test_net.py --cfg config/resnet50.yaml MODEL.WEIGHTS <checkpoint>`."""
    import math

    from distribuuuu_tpu import trainer

    ph = Phase("eval", bridge)
    load_cfg(base_argv(args, os.path.join(args.out, "eval"), "MODEL.WEIGHTS", weights))
    acc1, acck = trainer.test_model()
    check(math.isfinite(acc1) and math.isfinite(acck), f"top-k not finite: {acc1}, {acck}")
    check(0.0 <= acc1 <= acck <= 100.0, f"top-k out of range: {acc1}, {acck}")
    # the dummy val set is `DummyDataset(seed=0)` in both runs and the
    # checkpoint round-trips exactly, so the two evals are one computation
    check(
        (acc1, acck) == (train_eval["acc1"], train_eval["acck"]),
        f"test_model ({acc1}, {acck}) != the train run's epoch-end eval "
        f"({train_eval['acc1']}, {train_eval['acck']})",
    )
    ph.line(
        acc1=acc1, acck=acck, train_run_acc1=train_eval["acc1"],
        train_run_acck=train_eval["acck"], same_val_seed=True, equal=True,
    )


class Comparisons:
    """Every fused-against-reference comparison of the kernels phase: the
    worst max|got - want| / max|want| per name, and which ones missed their
    tolerance. Collected first and judged after the phase's line is printed,
    so one run shows every number."""

    def __init__(self, interpret: bool):
        self.interpret = interpret
        self.worst: dict[str, float] = {}
        self.missed: list[str] = []

    def _run(self, fn, xs):
        """Compile `fn` for the attached device and run it; on the chip the
        program must hold the Mosaic call (a guard that quietly took the XLA
        path is a fail)."""
        import jax

        compiled = jax.jit(fn).lower(*xs).compile()
        if not self.interpret:
            check("tpu_custom_call" in compiled.as_text(), "no tpu_custom_call in the program")
        return compiled(*xs)

    def _add(self, name: str, got, want, tol: float) -> None:
        import jax
        import jax.numpy as jnp

        for i, (g, w) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
            check(g.shape == w.shape and g.dtype == w.dtype, f"{name}: shape/dtype differ")
            g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
            check(bool(jnp.all(jnp.isfinite(g32))), f"{name}: non-finite output")
            err = float(jnp.max(jnp.abs(g32 - w32))) / max(float(jnp.max(jnp.abs(w32))), 1e-6)
            self.worst[name] = max(self.worst.get(name, 0.0), err)
            if err > tol:
                self.missed.append(f"{name}[{i}] {tuple(g.shape)}: {err:.3e} > {tol:.3e}")

    def case(self, tag: str, fused, ref, xs, fwd_tol: float, grad_tol: float) -> None:
        """Output, and gradient of the squared sum in every argument, of the
        compiled `fused` against `ref` on the same inputs."""
        import jax
        import jax.numpy as jnp

        def grad_of(f):
            return jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2), tuple(range(len(xs)))
            )

        self._add(f"{tag}.fwd", self._run(fused, xs), jax.jit(ref)(*xs), fwd_tol)
        self._add(
            f"{tag}.grad", self._run(grad_of(fused), xs), jax.jit(grad_of(ref))(*xs), grad_tol
        )


def kernels_epilogue(shapes, seed: int, cmp: Comparisons) -> None:
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.ops import fused_conv_epilogue, oracle_epilogue

    def fused(*a):
        return fused_conv_epilogue(*a, relu=True, bn_dtype=jnp.bfloat16, interpret=cmp.interpret)

    def oracle(*a):
        return oracle_epilogue(*a, relu=True, bn_dtype=jnp.bfloat16)

    key = jax.random.PRNGKey(seed)
    for shape in shapes:
        c = shape[-1]
        kx, km, ks, kb, ki, key = jax.random.split(key, 6)
        x = jax.random.normal(kx, shape, jnp.bfloat16)
        mean = 0.1 * jax.random.normal(km, (c,), jnp.float32)
        mul = 1.0 + 0.1 * jax.random.normal(ks, (c,), jnp.float32)
        bias = 0.1 * jax.random.normal(kb, (c,), jnp.float32)
        identity = jax.random.normal(ki, shape, jnp.bfloat16)
        cmp.case("epilogue", fused, oracle, (x, mean, mul, bias), EPILOGUE_TOL, EPILOGUE_GRAD_TOL)
        cmp.case(
            "epilogue+res", fused, oracle, (x, mean, mul, bias, identity),
            EPILOGUE_TOL, EPILOGUE_GRAD_TOL,
        )


def kernels_attention(shapes, seed: int, cmp: Comparisons) -> None:
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.ops import fused_attention, fused_attention_abs, xla_attention

    def abs_ref(q, k, v, emb):
        pos = jnp.einsum(
            "bnid,jd->bnij", q, emb.astype(q.dtype), preferred_element_type=jnp.float32
        )
        return xla_attention(q, k, v, pos)

    key = jax.random.PRNGKey(seed + 1)
    for b, n, l, d in shapes:
        kq, kk, kv, kb, ke, key = jax.random.split(key, 6)
        q = (jax.random.normal(kq, (b, n, l, d), jnp.float32) * d**-0.5).astype(jnp.bfloat16)
        k = jax.random.normal(kk, (b, n, l, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, n, l, d), jnp.bfloat16)
        bias = jax.random.normal(kb, (b, n, l, l), jnp.float32)
        emb = jax.random.normal(ke, (l, d), jnp.float32)
        cmp.case(
            f"attention.L{l}d{d}",
            lambda *a: fused_attention(*a, interpret=cmp.interpret),
            xla_attention, (q, k, v, bias), ATTENTION_TOL, ATTENTION_TOL,
        )
        cmp.case(
            f"attention_abs.L{l}d{d}",
            lambda *a: fused_attention_abs(*a, interpret=cmp.interpret),
            abs_ref, (q, k, v, emb), ATTENTION_TOL, ATTENTION_TOL,
        )


def kernels_self_attention(shapes, seed: int, cmp: Comparisons) -> None:
    """The bias-free pair the ViT block takes on the chip (`dtpu_attn_fwd`,
    `dtpu_attn_bwd`) against the einsum route, output and all of d_qkv."""
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.ops.attention import fused_self_attention, xla_self_attention

    key = jax.random.PRNGKey(seed + 2)
    for b, l, h, hd in shapes:
        kq, key = jax.random.split(key)
        qkv = jax.random.normal(kq, (b, l, 3 * h * hd), jnp.float32).astype(jnp.bfloat16)
        cmp.case(
            f"self_attention.L{l}h{h}d{hd}",
            lambda x: fused_self_attention(x, h, cmp.interpret),
            lambda x: xla_self_attention(x, h), (qkv,), ATTENTION_TOL, ATTENTION_TOL,
        )


def fused_train_steps(args, interpret: bool, n_steps: int = 3) -> dict:
    """resnet50 train steps with `MODEL.FUSED_EPILOGUE True` against the
    unfused steps from the same state and batch."""
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu import optim, trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.data import construct_train_loader, prefetch_to_device
    from distribuuuu_tpu.runtime import data_mesh

    init_key, step_key = jax.random.split(jax.random.PRNGKey(args.seed))
    out: dict = {}
    for fused in (False, True):
        load_cfg(base_argv(
            args, os.path.join(args.out, "kernels"), "MODEL.FUSED_EPILOGUE", fused
        ))

        def build_and_run():
            mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
            model = trainer._build_cfg_model()  # MODEL.FUSED_EPILOGUE lands here
            state, tx = trainer.create_train_state(model, init_key, mesh, cfg.TRAIN.IM_SIZE)
            step = trainer.make_train_step(model, tx, mesh, cfg.TRAIN.TOPK)
            loader = construct_train_loader(mesh)
            loader.set_epoch(0)
            batch = next(iter(prefetch_to_device(loader, mesh, 1)))
            lr = jnp.asarray(optim.get_epoch_lr(0), jnp.float32)
            compiled = step.lower(state, batch, lr, step_key).compile()
            calls = compiled.as_text().count("tpu_custom_call")
            losses = []
            for i in range(n_steps):
                state, m = compiled(state, batch, lr, jax.random.fold_in(step_key, i))
                m = jax.device_get(m)
                check(m["skipped"] < 0.5, f"fused={fused}: step {i} was skipped as non-finite")
                losses.append(float(m["loss_sum"] / m["n"]))
            mem = compiled.memory_analysis()
            return losses, calls, {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            }

        losses, calls, mem = trainer._model_globals_scoped(build_and_run)()
        out["fused" if fused else "unfused"] = {
            "losses": losses, "tpu_custom_calls": calls, "memory_analysis": mem,
        }
    if not interpret:
        check(out["fused"]["tpu_custom_calls"] > 0, "the fused step holds no Pallas call")
        check(out["unfused"]["tpu_custom_calls"] == 0, "the unfused step holds a Pallas call")
    out["missed"] = [
        f"step {i}: fused loss {a} vs unfused {b} (rtol {FUSED_LOSS_RTOL})"
        for i, (a, b) in enumerate(zip(out["fused"]["losses"], out["unfused"]["losses"]))
        if abs(a - b) > FUSED_LOSS_RTOL * max(1.0, abs(b))
    ]
    return out


def phase_kernels(args, bridge) -> None:
    from distribuuuu_tpu.ops.interpret import pallas_interpret

    interpret = pallas_interpret()
    # compiled, asserted, never defaulted: only the rehearsal asks for the
    # interpreter, and it lifted the device check to do so
    check(interpret == args.rehearse_cpu, "Pallas interpret mode was switched on by something")
    epi_shapes, attn_shapes, self_shapes = EPILOGUE_SHAPES, ATTENTION_SHAPES, SELF_ATTENTION_SHAPES
    if args.rehearse_cpu:
        epi_shapes = [(2, 8, 8, 64), (2, 4, 4, 128)]
        attn_shapes = [(1, 2, 16, 128)]
        self_shapes = [(2, 21, 2, 64)]
    ph = Phase("kernels", bridge)
    cmp = Comparisons(interpret)
    kernels_epilogue(epi_shapes, args.seed, cmp)
    kernels_attention(attn_shapes, args.seed, cmp)
    kernels_self_attention(self_shapes, args.seed, cmp)
    steps = fused_train_steps(args, interpret)
    missed = cmp.missed + steps.pop("missed")
    ph.line(
        interpret=interpret,
        epilogue_shapes=epi_shapes,
        attention_shapes_bnld=attn_shapes,
        self_attention_shapes_blhd=self_shapes,
        tolerance=(
            f"max|fused-ref|/max|ref|: epilogue forward <= {EPILOGUE_TOL} (one bf16 "
            f"ulp), gradient <= {EPILOGUE_GRAD_TOL} (two); attention <= {ATTENTION_TOL}"
        ),
        worst_rel_err=cmp.worst,
        fused_epilogue_train_steps=steps,
        fused_loss_rtol=FUSED_LOSS_RTOL,
        missed_tolerance=missed,
    )
    check(not missed, f"kernels outside tolerance: {missed}")


# ---------------------------------------------------------------------------
# --chips 4: data parallel across four chips against the same batch on one
# ---------------------------------------------------------------------------

def assert_four_shards() -> list[dict]:
    """Every device of the MESH.DATA 4 mesh holds its slice of the loader's
    batch, as `prefetch_to_device` placed it."""
    import numpy as np

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.data import construct_train_loader, prefetch_to_device
    from distribuuuu_tpu.runtime import data_mesh

    mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
    loader = construct_train_loader(mesh)
    loader.set_epoch(0)
    host = next(iter(loader))
    batch = next(iter(prefetch_to_device(loader, mesh, 1)))
    shards = batch["image"].addressable_shards
    check(len(shards) == 4, f"the batch has {len(shards)} shards, not 4")
    check(
        len({s.device.id for s in shards}) == 4,
        f"shards sit on {sorted(s.device.id for s in shards)}: not four devices",
    )
    per = cfg.TRAIN.BATCH_SIZE
    seen = []
    for s in shards:
        rows = s.index[0]
        check(s.data.shape[0] == per, f"device {s.device.id} holds {s.data.shape[0]} rows, not {per}")
        check(
            np.array_equal(np.asarray(s.data), host["image"][rows]),
            f"device {s.device.id} does not hold rows {rows} of the host batch",
        )
        seen.append({"device": s.device.id, "rows": [rows.start or 0, rows.stop]})
    return sorted(seen, key=lambda r: r["rows"])


def phase_dp4(args, bridge) -> None:
    import jax

    ph = Phase("dp4", bridge)
    check(len(jax.devices()) >= 4, f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")
    per_dev = 2 if args.rehearse_cpu else 32
    test_per_dev = 2 if args.rehearse_cpu else 50
    runs = {}
    shards = None
    for name, n_dev in (("data4", 4), ("data1", 1)):
        argv = base_argv(
            args, os.path.join(args.out, name),
            "MESH.DATA", n_dev,
            "TRAIN.BATCH_SIZE", per_dev * 4 // n_dev,
            "TEST.BATCH_SIZE", test_per_dev * 4 // n_dev,
            "MODEL.SYNCBN", "True",
            "OPTIM.BASE_LR", DP_BASE_LR,
            "TRAIN.PRINT_FREQ", "1",
        )
        if n_dev == 4:
            load_cfg(argv)
            shards = assert_four_shards()
        state, records = run_train(argv)
        checked = journal_checks(records, jax.devices()[0].platform)
        runs[name] = (params_to_host(state), checked)
    (p4, c4), (p1, c1) = runs["data4"], runs["data1"]
    drift = rel_l2(p4, p1)
    # the line first, the verdict after: a run that misses a tolerance still
    # shows what it measured (and then fails)
    ph.line(
        global_batch=per_dev * 4,
        base_lr=DP_BASE_LR,
        steps=c4["steps"],
        losses_data4=c4["losses"],
        losses_data1=c1["losses"],
        loss_tolerance=f"|a-b| <= {DP_LOSS_RTOL} * max(1, |b|)",
        params_rel_l2=drift,
        params_rel_l2_limit=DP_PARAM_REL_L2,
        eval_data4=c4["eval"],
        eval_data1=c1["eval"],
        batch_shards=shards,
    )
    check(c4["steps"] == c1["steps"] and c4["steps"] >= 3, f"steps: {c4['steps']} vs {c1['steps']}")
    for i, (a, b) in enumerate(zip(c4["losses"], c1["losses"])):
        check(
            abs(a - b) <= DP_LOSS_RTOL * max(1.0, abs(b)),
            f"step {i}: loss on four chips {a} vs on one {b} (rtol {DP_LOSS_RTOL})",
        )
    check(drift <= DP_PARAM_REL_L2, f"final params differ by rel-L2 {drift} > {DP_PARAM_REL_L2}")


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if args.rehearse_cpu:
        if dev.platform != "cpu":
            print(f"--rehearse-cpu is for the CPU; JAX reports {dev.platform}", file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(
            f"chip_smoke: JAX reports platform {dev.platform!r} ({dev.device_kind}), "
            f"not a TPU; nothing was run",
            file=sys.stderr,
        )
        return 2

    sys.path.insert(0, ROOT)
    from distribuuuu_tpu.obs import MonitoringBridge

    if args.rehearse_cpu:
        from distribuuuu_tpu.ops.interpret import set_pallas_interpret

        set_pallas_interpret(True)
    shutil.rmtree(args.out, ignore_errors=True)  # AUTO_RESUME must find nothing
    os.makedirs(args.out)
    bridge = MonitoringBridge().install()
    try:
        if args.chips == 4:
            phase_dp4(args, bridge)
        else:
            weights, train_eval = phase_train(args, bridge)
            phase_eval(args, bridge, weights, train_eval)
            phase_kernels(args, bridge)
    finally:
        # journals, logs and configs stay for reading; the checkpoints
        # (about 200 MB a run) served their phase and would not come back
        for run in os.listdir(args.out):
            shutil.rmtree(os.path.join(args.out, run, "checkpoints"), ignore_errors=True)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
