"""The program's own phases (obs/trace.py): named scopes inside the jitted
train step, `phase` spans and counters in the loop and on the input thread,
and the journal's window spans that follow the same vocabulary.

All on the CPU mesh, tiny shapes. The compiled text is read with the
persistent compile cache off: jax strips debug info before hashing a program
for the cache, so a cached executable hands back the metadata of whichever
build wrote it (PERF.md section 7) — the very thing these tests read.
"""

from __future__ import annotations

import contextlib
import glob
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distribuuuu_tpu import obs, resilience, trainer
from distribuuuu_tpu.models.resnet import BasicBlock, ResNet
from distribuuuu_tpu.models.vit import ViT
from distribuuuu_tpu.obs import trace as obs_trace
from distribuuuu_tpu.obs.journal import read_journal, validate_journal
from distribuuuu_tpu.runtime import data_mesh

CLASSES, IM = 4, 16
DTPU_SCOPES = tuple(f"dtpu.{name}" for name in obs_trace.STEP_SCOPES)


def _model(arch: str):
    if arch == "resnet":
        return ResNet(block=BasicBlock, stage_sizes=(1, 1, 1, 1), num_classes=CLASSES,
                      dtype=jnp.float32)
    return ViT(patch=4, dim=32, depth=2, num_heads=4, mlp_dim=64, num_classes=CLASSES,
               dtype=jnp.float32)


def _host_batch(n: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "image": rng.standard_normal((n, IM, IM, 3)).astype(np.float32),
        "label": rng.integers(0, CLASSES, n).astype(np.int32),
        "weight": np.ones((n,), np.float32),
    }


def _device_batch(batch: dict, mesh) -> dict:
    img = NamedSharding(mesh, P("data", None, None, None))
    vec = NamedSharding(mesh, P("data"))
    return {k: jax.device_put(v, img if v.ndim == 4 else vec) for k, v in batch.items()}


#: a token family at toy sizes with no head or group axis of one (XLA's CPU simplifier drops the metadata of a
#: product it rewrites round such an axis; the compiles for a described v5e at the cells' sizes keep it), and the
#: pattern that holds each of its kinds of layer: (module, class, sizes, pattern)
TOKEN_FAMILIES = {
    "nemotron_h": ("nemotron_h", "NemotronH", dict(
        vocab=48, dim=32, layers_total=8, mamba_heads=4, mamba_head_dim=8, mamba_groups=2, ssm_state=16,
        conv_kernel=4, chunk=16, attn_heads=4, kv_heads=2, head_dim=8, experts=16, experts_held=4,
        expert_first=4, top_k=3, latent=16, expert_width=24, shared_width=40, routed_scale=5.0), "EM*"),
    "qwen3_next": ("qwen3_next", "Qwen3Next", dict(
        vocab=48, dim=32, linear_key_heads=2, linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
        conv_kernel=4, chunk=16, attn_heads=4, kv_heads=2, head_dim=16, rope_share=0.25, rope_theta=1e4,
        experts=16, experts_held=4, expert_first=4, top_k=3, expert_width=24, shared_width=24), "GA"),
    "deepseek_v3": ("deepseek_v3", "DeepseekV3", dict(
        vocab=48, dim=32, attn_heads=4, kv_latent=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, rope_theta=1e4,
        dense_width=40, experts=16, experts_held=4, expert_first=4, top_k=3, expert_width=24, shared_width=48,
        routed_scale=2.448), "DEE"),
}


def _build_lm(cfg, family: str, mesh):
    """(state, jitted train step, device batch) of a token family at toy sizes, with the layer checkpoint."""
    import importlib

    module, cls, sizes, pattern = TOKEN_FAMILIES[family]
    m = importlib.import_module(f"distribuuuu_tpu.models.{module}")
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK = "lm", "adafactor", 16
    model = getattr(m, cls)(m.Sizes(pattern=pattern, **sizes), dtype=jnp.float32, remat=True)
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, 0)
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2 * int(mesh.devices.size), 25), 0, sizes["vocab"])
    return state, step, {"tokens": jax.device_put(tokens, NamedSharding(mesh, P("data", None)))}


def _build(cfg, arch: str, mesh):
    """(state, jitted train step, device batch) of a tiny model as the trainer builds them."""
    if arch in TOKEN_FAMILIES:
        return _build_lm(cfg, arch, mesh)
    cfg.OPTIM.OPTIMIZER = "sgd" if arch == "resnet" else "lamb"
    model = _model(arch)
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, IM)
    step = trainer.make_train_step(model, tx, mesh, topk=2)
    batch = _device_batch(_host_batch(2 * int(mesh.devices.size)), mesh)
    return state, step, batch


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(step, state, batch) -> str:
    lowered = step.lower(state, batch, jnp.float32(0.1), jax.random.PRNGKey(1))
    return lowered.compile().as_text()


def _op_names(text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', text)


# -- (a) the scopes are in the executable's metadata --------------------------

@pytest.mark.parametrize("arch,block", [("resnet", "layer2_0/conv1"), ("vit", "block1/attn")])
def test_compiled_step_names_every_scope_and_both_passes(fresh_cfg, no_compile_cache, arch, block):
    state, step, batch = _build(fresh_cfg, arch, data_mesh(1))
    names = _op_names(_compiled_text(step, state, batch))
    for scope in DTPU_SCOPES:
        if scope == "dtpu.grad_sync":
            continue  # a mean over one device is no op: see the four-device test
        if scope[len("dtpu."):] in obs_trace.MODEL_SCOPES:
            continue  # a state-space or expert mixer's: tests/test_nemotron_h.py
        assert any(scope in n for n in names), f"no op under {scope}"
    # the loss outside the module reads as forward and backward of a named thing
    assert any("/jvp(dtpu.loss)/" in n for n in names)
    assert any("transpose(jvp(dtpu.loss))" in n for n in names)
    # flax's module paths, under both passes
    forward = [n for n in names if "/jvp(" in n and "transpose(" not in n and f"/{block}/" in n]
    backward = [n for n in names if "transpose(jvp(" in n and f"/{block}/" in n]
    assert forward and backward, (len(forward), len(backward))


@pytest.mark.parametrize("family", sorted(TOKEN_FAMILIES))
def test_every_product_of_a_token_step_lies_under_a_scope(fresh_cfg, no_compile_cache, family):
    """Every ``dot`` and ``convolution`` of every computation of the compiled step (loop bodies, the
    checkpoints' recomputation, the backward pass) names a ``dtpu.`` scope in its ``op_name``: all the step's
    matrix work is placed by a scope metric of the benchmark, and none is left to `step_unplaced_pct`."""
    state, step, batch = _build(fresh_cfg, family, data_mesh(1))
    text = _compiled_text(step, state, batch)
    products = [line for line in text.splitlines()
                if re.match(r"\s*(ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+(dot|convolution)\(", line)]
    assert len(products) > 20, len(products)
    for line in products:
        names = _op_names(line)
        assert names and any(part.startswith("dtpu.") for part in names[0].split("/")), line[:300]


# -- (b) on four devices every collective of the step has a name ---------------

def test_every_all_reduce_lies_under_a_scope_on_four_devices(fresh_cfg, no_compile_cache):
    state, step, batch = _build(fresh_cfg, "resnet", data_mesh(4))
    text = _compiled_text(step, state, batch)
    reduces = [line for line in text.splitlines() if re.search(r"\ball-reduce(-start)?\(", line)]
    assert reduces, "the four-device step holds no all-reduce"
    allowed = ("dtpu.grad_sync", "dtpu.metrics", "dtpu.guard")
    for line in reduces:
        names = _op_names(line)
        assert names and any(scope in names[0] for scope in allowed), line[:300]
    assert any("dtpu.grad_sync" in _op_names(line)[0] for line in reduces)


# -- (c) the two jitted steps are named on purpose ----------------------------

def test_train_step_module_is_jit_step_and_eval_step_is_not(fresh_cfg):
    mesh = data_mesh(1)
    state, step, batch = _build(fresh_cfg, "resnet", mesh)
    train_text = step.lower(state, batch, jnp.float32(0.1), jax.random.PRNGKey(1)).as_text()
    # the name the program owns: benchmark/xplane finds the step by the
    # ``jit_step`` prefix, and the name (not the scopes) keys the compile cache
    assert re.search(r"module @jit_step_scoped\b", train_text)
    eval_step = trainer.make_eval_step(_model("resnet"), mesh, topk=2)
    eval_text = eval_step.lower(state, batch, trainer.zero_metrics(2, mesh)).as_text()
    module = re.search(r"module @(\w+)", eval_text).group(1)
    assert module == "jit_eval_step" and not module.startswith("jit_step")


# -- (d) a scope is metadata only ---------------------------------------------

@pytest.mark.parametrize("arch", ["resnet", "vit", *sorted(TOKEN_FAMILIES)])
def test_scopes_change_no_bit_of_the_step(fresh_cfg, monkeypatch, arch):
    mesh = data_mesh(1)

    def three_steps():
        state, step, batch = _build(fresh_cfg, arch, mesh)
        metrics = []
        for i in range(3):
            state, m = step(state, batch, jnp.float32(0.1), jax.random.PRNGKey(i))
            metrics.append(m)
        return jax.device_get((state.params, state.batch_stats, metrics))

    scoped = three_steps()
    # every module that puts a scope holds its own name for `step_scope`: each is replaced
    for module in [m for m in list(sys.modules.values()) if getattr(m, "step_scope", None) is obs_trace.step_scope]:
        if module is not obs_trace:
            monkeypatch.setattr(module, "step_scope", lambda name: contextlib.nullcontext())
    plain = three_steps()
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(plain), strict=True):
        np.testing.assert_array_equal(a, b)


# -- (e)/(f) the loop's phases: spans under a trace, counters always -----------

class _Loader:
    """What `train_epoch` needs of a loader, over a list of host batches."""

    def __init__(self, batches):
        self.batches = batches

    def set_epoch(self, epoch, start_batch=0):
        pass

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        return iter(self.batches)


@pytest.fixture()
def run(fresh_cfg, tmp_path):
    """A journaled run around `train_epoch` calls on the tiny resnet, one device."""
    resilience.reset_run_stats()
    resilience.clear_preemption()
    fresh_cfg.OUT_DIR = str(tmp_path)
    fresh_cfg.TRAIN.PRINT_FREQ = 2
    fresh_cfg.TRAIN.BATCH_SIZE = 2
    fresh_cfg.TRAIN.TOPK = 2
    mesh = data_mesh(1)
    state, step, _ = _build(fresh_cfg, "resnet", mesh)
    held = [state]  # the step donates its state: each epoch hands the next one its own
    obs.start_run(str(tmp_path))

    def epoch(steps: int, number: int = 0):
        loader = _Loader([_host_batch(2, seed=i) for i in range(steps)])
        held[0] = trainer.train_epoch(
            loader, mesh, step, held[0], number, jax.random.PRNGKey(2), True
        )

    yield epoch
    obs.end_run()


def test_train_epoch_leaves_its_spans_in_a_profiler_trace(run, tmp_path):
    from jax.profiler import ProfileData

    run(1)  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        run(3, number=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        # a line is a thread; this Python does not hand a thread's name to the
        # OS, so both read "python" and the line's place tells them apart
        for thread, line in enumerate(plane.lines):
            for event in line.events:
                if event.name.startswith(obs_trace.SPAN_PREFIX):
                    spans.setdefault(event.name, []).append((thread, dict(event.stats)))
    dispatch = spans["dtpu.dispatch"]
    assert [s["step_num"] for _, s in dispatch] == [3, 4, 5]  # gstep = epoch 1 x 3 steps + it
    (loop_thread,) = {thread for thread, _ in dispatch}
    assert [(thread, s["gstep"]) for thread, s in spans["dtpu.throttle"]] == [
        (loop_thread, 3), (loop_thread, 4), (loop_thread, 5)]
    assert {thread for thread, _ in spans["dtpu.data_wait"]} == {loop_thread}
    assert {thread for thread, _ in spans["dtpu.fetch_wait"]} == {loop_thread}
    assert all("gstep" in s for _, s in spans["dtpu.fetch_wait"])
    (input_thread,) = {thread for thread, _ in spans["dtpu.h2d_transfer"]}
    assert input_thread != loop_thread  # the dtpu-h2d-prefetch thread
    assert len(spans["dtpu.h2d_transfer"]) == 3
    assert set(spans) == {"dtpu." + name for name in obs_trace.HOST_PHASES} - {"dtpu.checkpoint"}


def test_untraced_windows_split_their_wall_into_the_phases(run, tmp_path):
    run(5)
    obs.end_run()
    journal = obs.journal_path(str(tmp_path))
    assert validate_journal(journal) == []
    records = list(read_journal(journal))
    windows = [r for r in records if r["kind"] == "window"]
    assert len(windows) == 3  # steps 0, 2, 4 at PRINT_FREQ 2
    spans: dict[int, dict] = {}
    for r in records:
        if r["kind"] == "span":
            spans.setdefault(r["gstep"], {})[r["phase"]] = r["ms"]
    for w in windows:
        phases = spans[w["gstep"]]
        assert set(phases) == set(obs_trace.TRAIN_PHASES) - {"checkpoint"}
        for name in ("throttle", "dispatch", "fetch_wait"):
            assert w[f"{name}_s"] > 0, name
            assert phases[name] == pytest.approx(1000 * w[f"{name}_s"], abs=2e-3)
        wall_ms = 1000 * w["step_time"] * w["steps"]
        assert sum(phases.values()) == pytest.approx(wall_ms, abs=1.0)
    (counters,) = [r for r in records if r["kind"] == "counters" and r.get("scope") == "epoch"]
    assert {c for c in obs_trace.HOST_PHASES.values() if c} <= set(counters["waits"])
    for name in ("h2d_transfer_s", "data_wait_s", "throttle_s", "dispatch_s", "fetch_wait_s"):
        assert counters["waits"][name] > 0, name
    assert counters["waits"]["dispatch_s"] == pytest.approx(
        sum(w["dispatch_s"] for w in windows), abs=1e-4)


# -- (g) phase() with no run, and from two threads -----------------------------

def test_phase_works_outside_a_run():
    assert not obs.current().enabled
    with obs_trace.phase("fetch_wait", gstep=0) as p:
        pass
    assert p.seconds >= 0.0
    with pytest.raises(KeyError):
        obs_trace.phase("compute")
    with pytest.raises(ValueError):
        obs_trace.step_scope("compute")


def test_phase_counters_lose_no_update_between_threads(fresh_cfg, tmp_path):
    tel = obs.start_run(str(tmp_path))
    rounds, workers = 400, 8
    totals = [0.0] * workers

    def work(i: int):
        for n in range(rounds):
            with obs_trace.phase("h2d_transfer" if i % 2 else "data_wait") as p:
                pass
            totals[i] += p.seconds

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        waits = dict(tel._waits)
        obs.end_run()
    assert waits["h2d_transfer_s"] == pytest.approx(sum(totals[1::2]), rel=1e-9)
    assert waits["data_wait_s"] == pytest.approx(sum(totals[0::2]), rel=1e-9)
