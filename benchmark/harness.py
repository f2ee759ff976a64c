"""Drive one cell: build the program as ``trainer.train_model`` does, prove its first
three steps against the reference, time a window of ``trainer.train_epoch``.

From the program this takes the system under test (``distribuuuu_tpu.trainer``
and what it builds) and its journal; everything that measures or judges lives
in this directory.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import threading
import time

from benchmark import compare, files, hlo, roofline, traffic, xplane

FIRST_STEPS = compare.STEPS
_T0 = time.time()


def phase(name: str, t_start: float | None = None) -> None:
    """One line on standard error saying when a phase of the run began."""
    import sys

    print(f"[bench] {time.time() - (t_start or _T0):8.2f}s {name}", file=sys.stderr, flush=True)


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip: the allocator's peak in use plus the peak it
    reserved. On this runtime a program's temporaries (9.1 GB of resnet50's step) are
    *reserved*, not counted as in use, so ``peak_bytes_in_use`` alone reads 0.8 GB there."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        reserved = stats.get("peak_bytes_reserved", stats.get("bytes_reserved", 0))
        peaks.append(int(stats.get("peak_bytes_in_use", 0)) + int(reserved))
    return max(peaks)


TRACE_START_SHARE = 0.35  # of the window, after which the trace starts
# While the profiler runs the device stands still for some tenths of a second, from about 0.9 s after
# ``start_trace`` returns (PERF.md section 5; the cause lies in the profiler and is not the number of
# events). So the span that the reduction reads starts as soon as ``start_trace`` returns and ends,
# with the trace, before that: the cell's file says after how long (``trace_seconds``), since it knows
# how long its step is. A cell that does not say gets the span that holds 6 steps of resnet50.
TRACE_SECONDS_SHARE, TRACE_SECONDS_DEFAULT = 0.3, 0.6


# --------------------------------------------------------------------------
# the cell's settings
# --------------------------------------------------------------------------

def load_cell(name: str) -> tuple[dict, dict]:
    """The cell's file with its traffic mix's parameters under ``mix``, and its configuration's file."""
    cell = files.load_json("workloads", name)
    cell["mix"] = files.load_json("mixes", cell["traffic"])
    config = files.load_json("configs", cell["config"])
    return cell, config


def settings_for(cell: dict, config: dict, rehearse: bool) -> dict:
    """The keys merged into the program's ``cfg``: the configuration's own, the cell's mesh."""
    settings = copy.deepcopy(config["cfg"])
    settings["MESH"] = dict(cell["mesh"])
    if rehearse:
        for dotted, value in cell["rehearse"]["cfg"].items():
            section, key = dotted.split(".")
            settings[section][key] = value
    return settings


def merge_into_cfg(settings: dict, out_dir: str, seed: int) -> None:
    from distribuuuu_tpu.config import cfg, reset_cfg

    reset_cfg()
    flat: list = []
    for section, keys in settings.items():
        for key, value in keys.items():
            flat += [f"{section}.{key}", value]
    cfg.merge_from_list(flat + ["OUT_DIR", out_dir, "RNG_SEED", int(seed) % (2**31)])


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31)


# --------------------------------------------------------------------------
# the program, built as train_model builds it
# --------------------------------------------------------------------------

class Program:
    """mesh, model, state, optimizer and jitted step of one cell, and the loop that drives them."""

    def __init__(self, cell: dict, config: dict, settings: dict, seed: int, out_dir: str,
                 step_wrapper=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distribuuuu_tpu import obs, resilience, trainer
        from distribuuuu_tpu.config import cfg, dump_cfg
        from distribuuuu_tpu.logging import setup_logger
        from distribuuuu_tpu.runtime import data_mesh, setup_distributed, setup_seed
        from distribuuuu_tpu.runtime.seeding import configure_determinism

        merge_into_cfg(settings, out_dir, seed)
        configure_determinism(cfg.CUDNN.DETERMINISTIC)
        trainer._enable_compile_cache()
        info = setup_distributed()
        key = setup_seed(cfg.RNG_SEED, info.process_index)
        dump_cfg()
        setup_logger(cfg.OUT_DIR, info.process_index,
                     journal_path=obs.journal_path(cfg.OUT_DIR) if cfg.OBS.ENABLED else None)
        resilience.reset_run_stats()
        resilience.clear_preemption()
        if cfg.FAULT.HANDLE_SIGNALS:
            resilience.install_preemption_handler()
        obs.start_run(cfg.OUT_DIR, is_primary=info.is_primary)
        self.journal_path = obs.journal_path(cfg.OUT_DIR)
        self.mesh = data_mesh(cfg.MESH.DATA, cfg.MESH.FSDP, cfg.MESH.SEQ)
        self.model = trainer._build_cfg_model()
        _, self.dropout_key = jax.random.split(key)
        phase("program: model built, creating the train state")
        state, self.tx = trainer.create_train_state(self.model, key, self.mesh, cfg.TRAIN.IM_SIZE)
        phase("program: train state created")

        # The weights are the benchmark's, made on the device from the seed by
        # the reference's own initialiser, so that the reference takes nothing
        # the program has made. The optimizer state starts at zero either way.
        self.ref = files.load_module("reference", cell["config"])
        self.opt = files.load_module("reference", f"optim_{settings['OPTIM']['OPTIMIZER']}")
        self.settings = settings
        self.hp = settings["OPTIM"]
        self.weights_key = seed_key(seed)
        replicated = NamedSharding(self.mesh, P())
        ref = self.ref
        make = lambda k: ref.to_program(ref.init(k, settings), ref.init_stats(settings))
        # twice in one call: the step donates its state, and the readings need the start
        weights = jax.jit(lambda k: (make(k), make(k)), out_shardings=replicated)
        (params, stats), (self.params0, self.stats0) = weights(self.weights_key)
        if jax.tree.structure(params) != jax.tree.structure(state.params):
            raise ValueError("the reference's parameter tree does not match the program's")
        self.state = state.replace(params=params, batch_stats=stats)
        del state

        phase("program: weights made")
        trainer._journal_state_bytes(self.state, self.mesh)
        trainer._journal_activation_bytes(self.model, self.mesh)
        self.train_step = trainer.make_train_step(
            self.model, self.tx, self.mesh, cfg.TRAIN.TOPK, accum_steps=cfg.TRAIN.ACCUM_STEPS,
            state_specs=None,
        )
        self.timed_step = step_wrapper(self.train_step) if step_wrapper else self.train_step
        self.global_batch = cfg.TRAIN.BATCH_SIZE * cfg.TRAIN.ACCUM_STEPS * int(self.mesh.devices.size)
        self.run_tic = time.time()
        resilience.start_watchdog(cfg.FAULT.HANG_TIMEOUT_S)

    def epoch(self, loader, epoch: int) -> None:
        from distribuuuu_tpu import trainer

        self.state = trainer.train_epoch(
            loader, self.mesh, self.timed_step, self.state, epoch, self.dropout_key, True,
            start_epoch=0, run_tic=self.run_tic,
        )

    def skipped(self, epoch: int) -> int:
        from distribuuuu_tpu import resilience

        return int(resilience.RUN_STATS.skipped_steps.get(epoch, 0))

    def journal(self) -> list[dict]:
        with open(self.journal_path) as f:
            return [json.loads(line) for line in f if line.strip()]

    def end_run(self) -> None:
        from distribuuuu_tpu import obs, resilience

        resilience.stop_watchdog()
        obs.end_run(best_acc1=0.0, epochs=0, clean=True)

    def lower_step_text(self, batch_abs) -> str:
        """The compiled step's HLO text (a cache hit after the first run)."""
        import jax.numpy as jnp

        lowered = self.train_step.lower(self.state, batch_abs, jnp.float32(0.0), self.dropout_key)
        return lowered.compile().as_text()

    def free(self) -> None:
        self.state = self.params0 = self.stats0 = None
        self.train_step = self.timed_step = None


def first_steps(program: Program, pool) -> dict:
    """Drive the window's own object through its first three steps; return its readings."""
    import jax
    import jax.numpy as jnp

    opt, ref, hp = program.opt, program.ref, program.hp
    names = list(ref.shapes(program.settings))
    stat_names = list(ref.init_stats(program.settings))

    def leaf_norms(flat):
        return {k: jnp.linalg.norm(v.astype(jnp.float32).ravel()) for k, v in flat.items()}

    def param_norms(tree):  # in the reference's names, the packed leaves as their parts
        return leaf_norms(ref.compare_leaves(ref.from_program(tree, names)))

    first_grad = jax.jit(lambda o, p: param_norms(opt.first_gradient(o, p, hp)))
    delta_norms = jax.jit(lambda a, b: param_norms(jax.tree.map(lambda x, y: x - y, a, b)))
    stats_norms = jax.jit(lambda a, b: leaf_norms(
        ref.from_program(jax.tree.map(lambda x, y: x - y, a, b), stat_names)))

    program.epoch(traffic.PoolLoader(pool, steps=1, first=0), epoch=0)
    grad_norm = jax.device_get(first_grad(program.state.opt_state, program.params0))
    program.epoch(traffic.PoolLoader(pool, steps=FIRST_STEPS - 1, first=1), epoch=1)
    delta = jax.device_get(delta_norms(program.state.params, program.params0))
    stats_delta = (
        jax.device_get(stats_norms(program.state.batch_stats, program.stats0)) if stat_names else {}
    )
    windows = [r for r in program.journal() if r["kind"] == "window" and r["epoch"] in (0, 1)]
    losses = [r["loss"] for r in windows]
    if len(losses) != FIRST_STEPS or any(r["steps"] != 1 for r in windows):
        raise RuntimeError(f"expected {FIRST_STEPS} one-step windows in the journal, found {windows}")
    flat = lambda norms: {k: float(v) for k, v in norms.items()}
    return {
        "loss": [float("nan") if v is None else float(v) for v in losses],
        "grad_norm": flat(grad_norm),
        "delta_norm": flat(delta),
        "stats_delta_norm": flat(stats_delta),
        "skipped": program.skipped(0) + program.skipped(1),
    }


def reference_for(program: Program, pool, shards: int, precision: str = "f32", fault=None) -> dict:
    from benchmark.reference import schedule

    lrs = [schedule.lr_at_epoch(program.hp, e) for e in (0, 1, 1)]
    return compare.reference_readings(
        program.ref, program.opt, program.settings, program.weights_key, pool[:FIRST_STEPS], lrs,
        shards, precision=precision, fault=fault,
    )


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def window_pacing(asked_at: list, journal: list, epoch: int) -> dict:
    """Where a slow window lost its time, as far as the host can tell: the longest wait between
    two asks of the program's prefetch thread for a batch, each journal window's seconds per
    step, and the program's own wait counters over the window."""
    out = {"ask_gap_max_s": max((b - a for a, b in zip(asked_at, asked_at[1:])), default=0.0)}
    out["window_step_s"] = [r["step_time"] for r in journal
                            if r["kind"] == "window" and r["epoch"] == epoch and not r["warmup"]]
    for r in journal:
        if r["kind"] == "counters" and r.get("scope") == "epoch" and r.get("epoch") == epoch:
            out.update({k: v for k, v in r["waits"].items() if k in ("data_wait_s", "h2d_transfer_s")})
    return out


class _TraceWindow:
    """Wrap a few steady seconds of the window in ``jax.profiler.trace``, from a thread of its own."""

    def __init__(self, trace_dir: str, start_after: float, seconds: float):
        self.trace_dir, self.start_after, self.seconds = trace_dir, start_after, seconds
        self.error = None
        self._thread = threading.Thread(target=self._run, name="bench-trace", daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.start_after)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # level 2 traces every chunk of the host's layout transposes (340 000 events in
            # 2 s) and slows the feed until the device starves; level 1 keeps the spans
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(xplane.STEADY_SPAN):  # all the reduction reads
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:  # reported by join(); the run then fails loudly
            self.error = exc

    def join(self):
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the trace thread did not end")
        if self.error is not None:
            raise self.error


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, rehearse: bool = False,
             t_process_start: float | None = None, out_root: str | None = None,
             step_wrapper=None) -> dict:
    """One run of one cell. Returns the result line's object (metrics unprefixed)."""
    import jax

    global _T0
    t_process_start = _T0 = t_process_start if t_process_start is not None else time.time()
    cell, config = load_cell(name)
    settings = settings_for(cell, config, rehearse)
    out_root = out_root or os.path.join(files.ROOT, "benchmark_out")
    out_dir = os.path.join(out_root, name, f"seed{seed}_trace{int(trace)}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    devices = jax.devices()[: cell["chips"]]
    device = devices[0]
    phase("imports done, building the program", t_process_start)
    program = Program(cell, config, settings, seed, out_dir, step_wrapper=step_wrapper)
    phase("program built", t_process_start)
    if int(program.mesh.devices.size) != cell["chips"]:
        raise RuntimeError(f"the mesh holds {program.mesh.devices.size} devices, the cell asks for {cell['chips']}")
    pool = traffic.make_pool(config["input"], seed, cell["mix"]["pool_batches"], program.global_batch, settings)
    phase("pool made", t_process_start)
    got = first_steps(program, pool)
    phase("first three steps done", t_process_start)

    # -- the measured window ------------------------------------------------
    window_epoch = 2
    annotate = jax.profiler.TraceAnnotation if trace else None
    loader = traffic.PoolLoader(pool, seconds=seconds, first=FIRST_STEPS,
                                input_mode=cell["mix"]["input_mode"], annotate=annotate)
    tracer = None
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        tracer = _TraceWindow(trace_dir, TRACE_START_SHARE * seconds,
                              min(cell.get("trace_seconds", TRACE_SECONDS_DEFAULT), TRACE_SECONDS_SHARE * seconds))
    t_begin_wall = time.time()
    t_begin = loader.start()
    if tracer:
        tracer.start()
        with jax.profiler.TraceAnnotation("bench.train_epoch"):
            program.epoch(loader, window_epoch)
    else:
        program.epoch(loader, window_epoch)
    jax.block_until_ready(program.state)
    window_s = time.monotonic() - t_begin
    if tracer:
        tracer.join()
    attempted = loader.yielded
    failed = program.skipped(window_epoch)
    images = (attempted - failed) * program.global_batch
    program.end_run()
    journal = program.journal()
    memory_peak = memory_peak_bytes(devices)
    phase("window closed", t_process_start)

    result = {
        "attempted": attempted, "failed": failed,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": cell["chips"], "memory_peak_bytes": memory_peak},
        "end_to_end": {
            "img_per_s_per_chip": images / window_s / cell["chips"],
            "setup_s": t_begin_wall - t_process_start,
        },
        "window": {"seconds": window_s, "steps": attempted, "images": images,
                   "global_batch": program.global_batch, "epoch": window_epoch},
        "host": window_pacing(loader.asked_at, journal, window_epoch),
    }

    # -- the traced run's per-layer metrics -----------------------------------
    if trace:
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch_abs = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(
                program.mesh, P("data", *([None] * (v.ndim - 1)))))
            for k, v in pool[0].items()
        }
        hlo_text = program.lower_step_text(batch_abs)
        with open(os.path.join(out_dir, "step.hlo.txt"), "w") as f:
            f.write(hlo_text)
        reduced = xplane.load(xplane.find_xplane(trace_dir))
        if not reduced.devices:
            if not rehearse:
                raise RuntimeError("the trace holds no device plane with ops")
            reduced = None  # a CPU rehearsal has no device plane: readers find nothing
        layers = files.load_module("flops", cell["config"]).layers(settings)
        context = {
            "cell": cell, "settings": settings, "journal": journal, "window": result["window"],
            "trace": reduced, "classes": hlo.classify(hlo_text), "layers": layers,
            "kernels": roofline.kernel_costs(hlo.kernel_calls(hlo_text)),  # a kernel with no file fails the run
            "device": result["device"], "rehearse": rehearse, "chips": cell["chips"],
            "batch_per_chip": program.global_batch // cell["chips"], "roofline": roofline,
        }
        if not rehearse:
            from benchmark import peaks

            context["peaks"] = peaks.lookup(device.device_kind)
        per_layer = {}
        for module in files.layer_metric_modules():
            value = module.read(context)
            if value is not None:
                per_layer[module.NAME] = {"value": float(value), "unit": module.UNIT}
        result["per_layer"] = per_layer
        if reduced is not None:
            lo, hi = reduced.window()
            result["device"]["busy_s"] = reduced.busy_mean_s()
            result["device"]["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {"device_ops": reduced.top_ops(10), "idle_gaps": reduced.idle_gaps(10)}
            result["trace"] = reduced.whole
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correct: once the window has closed and the program's state is freed --
    program.free()
    phase("reference starts", t_process_start)
    t_reference = time.time()
    want = reference_for(program, pool, shards=cell["chips"])
    found = compare.gaps(got, want)
    limits = cell["rehearse"]["limits"] if rehearse else cell["limits"]
    correct, compared = compare.verdict(found["numbers"], limits)
    if got["skipped"]:  # a step the non-finite guard skipped is no step
        correct = False
    result["correct"] = bool(correct)
    result["compared"] = compared
    result["worst_leaves"] = found["leaves"]
    result["reference_s"] = time.time() - t_reference
    with open(os.path.join(out_dir, "first_steps.json"), "w") as f:
        json.dump({"program": got, "reference": want, "gaps": found}, f)
    return result
