"""The files of the configuration ``nemotron3_super``: the ``tokens`` input kind's pools, the work that
``flops/`` counts, the configuration's file against the catalog's published widths, the benchmark's copy
of the reference against ``tests/reference/nemotron_h.py``, the optimizer's reference against the
program's optimizer, and the six per-layer readers on a made trace. CPU; nothing here is a device number."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import files, harness, roofline, traffic, xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "nemotron3_super.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DIGESTS = {  # sha256 over the pool's leaves (name, dtype, shape, bytes), pool of 8 batches of 1 row
    7: "8f53c49111f8bd6c95a392be1a0f44d20e880db19939f1eca1bd64633d8ac89e",
    2**31 + 17: "588bdf790964f4404855e59c21c4b2ccab416698ec62359ad02be11e50f9b299",
}


def cell_settings(rehearse: bool = False):
    cell, config = harness.load_cell(CELL)
    return cell, config, harness.settings_for(cell, config, rehearse)


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_a_seeds_token_pool_is_these_bytes(seed):
    cell, config, settings = cell_settings()
    pool = traffic.make_pool(config["input"], seed, cell["mix"]["pool_batches"], 1, settings)
    h = hashlib.sha256()
    for batch in pool:
        assert set(batch) == {"tokens"} and batch["tokens"].dtype == np.int32
        assert batch["tokens"].shape == (1, settings["LM"]["SEQ_LEN"] + 1)  # rows leading, L + 1 ids a row
        assert 0 <= batch["tokens"].min() and batch["tokens"].max() < settings["LM"]["VOCAB"]
        h.update(f"tokens:{batch['tokens'].dtype.str}:{batch['tokens'].shape};".encode())
        h.update(batch["tokens"].tobytes())
    assert len(pool) == 8 and h.hexdigest() == DIGESTS[seed]


def test_token_rows_lead_and_differ():
    _, config, settings = cell_settings(rehearse=True)
    pool = traffic.make_pool(config["input"], 3, 2, 4, settings)
    assert all(batch["tokens"].shape[0] == 4 for batch in pool)
    rows = np.concatenate([batch["tokens"] for batch in pool])
    assert len({row.tobytes() for row in rows}) == len(rows)


def test_flops_file_counts_429m_macs_a_token_forward():
    """This issue's count (5 Mamba 71 M, attention 9.4 M at half of 8192 keys, 5 expert layers 282 M with
    the routed products at the expected 22 x 8/512 slots a token, head 67 M), to half a percent."""
    _, _, settings = cell_settings()
    layers = files.load_module("flops", "nemotron3_super").layers(settings)
    tokens = settings["LM"]["SEQ_LEN"]
    per_token = roofline.forward_macs_per_image(layers) / tokens
    assert per_token == pytest.approx(429e6, rel=5e-3)
    by = lambda *ends: sum(layer["macs"] for layer in layers if layer["name"].endswith(ends)) / tokens
    assert by(".in_proj", ".out_proj", ".scan") == pytest.approx(71e6, rel=0.02)
    assert by(".qkv", ".scores", ".values", ".o") == pytest.approx(9.4e6, rel=0.02)
    assert by(".router", ".down", ".up", ".shared1", ".shared2", ".routed1", ".routed2") == pytest.approx(282e6, rel=0.01)
    assert by("head") == pytest.approx(67.1e6, rel=0.01)
    assert roofline.train_flops_per_image(layers) == pytest.approx(21.1e12, rel=0.01)  # a row, a train step
    scans = [layer for layer in layers if layer["name"].endswith(".scan")]
    assert len(scans) == 5 and all(layer["w"] == 0 for layer in scans)


def test_configuration_file_holds_the_published_widths_and_names_every_cut():
    with open(os.path.join(BENCH, "configs", "nemotron3_super.json")) as f:
        ours = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "nemotron3_super")
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert ours["source"] == entry["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if ours.get(k, object()) != v}
        assert differing == set(entry["reduced"]) == set(ours["reduced"])
        assert ours["published"] == {k: row["config"][k] for k in ours["reduced"]}
    widths = {"hidden_size": 4096, "mamba_head_dim": 64, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
              "head_dim": 128, "moe_latent_size": 1024, "moe_intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376, "num_experts_per_tok": 22, "routed_scaling_factor": 5}
    assert {k: ours[k] for k in widths} == widths
    lm = ours["cfg"]["LM"]
    assert (lm["DIM"], lm["MAMBA_HEAD_DIM"], lm["SSM_STATE"], lm["CONV_KERNEL"], lm["CHUNK"], lm["HEAD_DIM"],
            lm["LATENT"], lm["EXPERT_WIDTH"], lm["SHARED_WIDTH"], lm["EXPERTS"], lm["TOP_K"], lm["ROUTED_SCALE"]) == (
        4096, 64, 128, 4, 128, 128, 1024, 2688, 5376, 512, 22, 5.0)
    assert "64 chips" in ours["deployment"] and "multi_token_prediction" in ours["left_out"]
    # the held counts divide the published ones as the deployment says
    assert lm["MAMBA_HEADS"] * 8 == 128 and lm["ATTN_HEADS"] * 8 == 32 and lm["EXPERTS_HELD"] * 64 == 512
    assert lm["VOCAB"] * 8 == 131072 and len(lm["PATTERN"]) == ours["num_hidden_layers"] == 11


def test_reference_shapes_count_701m_parameters():
    _, _, settings = cell_settings()
    shapes = files.load_module("reference", "nemotron3_super").shapes(settings)
    count = sum(int(np.prod(shape)) for shape in shapes.values())
    assert count == pytest.approx(701e6, rel=0.01)
    assert shapes["U0.w1"] == (5, 8, 1024, 2688) and shapes["L10.q"] == (4096, 512) and shapes["head"] == (4096, 16384)


def _plain_reference():
    spec = importlib.util.spec_from_file_location(
        "plain_nemotron_h", os.path.join(ROOT, "tests", "reference", "nemotron_h.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_benchmarks_copy_agrees_with_the_plain_reference_on_loss_and_gradients():
    """The copy scans the repeats of the pattern's unit and the experts held, takes a row at a time and
    rematerialises; the plain one loops over layers and experts. Same weights (the copy's leaves of the
    repeats sliced apart), same rows: the same loss and gradients to float32's order of sums."""
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "nemotron3_super")
    plain = _plain_reference()
    params = jax.jit(lambda k: ours.init(k, settings))(jax.random.key(11))
    stats = ours.init_stats(settings)
    sizes = dict(ours.sizes(settings), eps=settings["LM"]["NORM_EPS"])
    unit, repeats = ours.repeated_unit(sizes["pattern"])
    assert repeats > 1  # the rehearsal's pattern walks the scanned path

    def per_layer(tree):
        out = {}
        for name, value in tree.items():
            prefix, _, leaf = name.partition(".")
            if prefix.startswith("U"):
                for r in range(repeats):
                    out[f"L{r * unit + int(prefix[1:])}.{leaf}"] = value[r]
            else:
                out[name] = value
        return out

    tokens = traffic.make_pool("tokens", 5, 1, 2, settings)[0]["tokens"]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ours.loss_fn(p, stats, {"tokens": tokens}, "f32"), has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: plain.loss_fn(p, per_layer(stats), tokens, sizes)))(per_layer(params))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    got = per_layer(grads)
    assert set(got) == set(want)
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]) / jnp.maximum(jnp.linalg.norm(want[name]), 1e-30))
        assert gap <= 2e-4, (name, gap)


@pytest.mark.parametrize("fault", ["top21", "no_scale", "no_causal"])
def test_a_planted_fault_changes_the_references_loss(fault):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "nemotron3_super")
    params = jax.jit(lambda k: ours.init(k, settings))(jax.random.key(11))
    stats = ours.init_stats(settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    grads = lambda precision: jax.jit(jax.grad(lambda p: ours.loss_fn(p, stats, batch, precision)[0]))(params)
    sound, planted = grads("f32"), grads(fault)
    moved = max(float(jnp.linalg.norm(planted[k] - sound[k]) / jnp.maximum(jnp.linalg.norm(sound[k]), 1e-30))
                for k in sound)
    assert moved > 0.05, moved
    with pytest.raises(ValueError, match="precision"):
        ours.loss_fn(params, stats, batch, "int4")


def test_adafactor_reference_follows_the_programs_optimizer_and_reads_its_first_gradient(monkeypatch):
    from distribuuuu_tpu import config, optim

    opt = files.load_module("reference", "optim_adafactor")
    monkeypatch.setattr(optim, "FACTOR_MIN_DIM", 8)  # toy leaves are factored too
    monkeypatch.setattr(opt, "FACTOR_MIN_DIM", 8)
    config.reset_cfg()
    try:
        config.cfg.OPTIM.OPTIMIZER, config.cfg.OPTIM.WEIGHT_DECAY = "adafactor", 0.01
        hp = {"WEIGHT_DECAY": 0.01}
        tx = optim.construct_optimizer()
        key = jax.random.key(0)
        params = {"a": jax.random.normal(key, (16, 12)), "b": jax.random.normal(key, (4, 10, 24)),
                  "c": jax.random.normal(key, (7,)), "d": jax.random.normal(key, (4, 20))}
        program, state = dict(params), tx.init(params)
        plain, plain_state = dict(params), opt.init(params)
        for i in range(3):
            grads = {k: (i + 1.0) * jax.random.normal(jax.random.fold_in(key, i), v.shape) for k, v in params.items()}
            updates, state = tx.update(grads, state, program)
            program = optim.apply_updates_with_lr(program, updates, 0.05)
            if i == 0:
                first = opt.first_gradient(state, params, hp)
                for k in grads:
                    assert float(jnp.linalg.norm(first[k])) == pytest.approx(float(jnp.linalg.norm(grads[k])), rel=1e-5)
            plain, plain_state = opt.step(plain, plain_state, grads, 0.05, hp)
            for k in params:
                np.testing.assert_allclose(program[k], plain[k], rtol=2e-5, atol=1e-6, err_msg=f"{k} step {i}")
    finally:
        config.reset_cfg()


# -- the six readers on a made trace -----------------------------------------------------------------

MADE_STEP = """HloModule jit_step_training

%fused_scan (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  ROOT %mul.0 = f32[8]{0} multiply(%p.0, %p.0), metadata={op_name="jit(step_training)/U1/dtpu.ssm_scan/mul"}
}

%fused_sort (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p.1), metadata={op_name="jit(step_training)/transpose(jvp(U0))/dtpu.moe_route/sort"}
}

%fused_experts (p.2: f32[8,8]) -> f32[8,8] {
  %p.2 = f32[8,8]{1,0} parameter(0)
  %dot.2 = f32[8,8]{1,0} dot(%p.2, %p.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_training)/U0/dtpu.moe_experts/dot_general"}
  ROOT %add.2 = f32[8,8]{1,0} add(%dot.2, %p.2), metadata={op_name="jit(step_training)/dtpu.optimizer/add"}
}

%body (t: (f32[8], f32[8,8])) -> (f32[8], f32[8,8]) {
  %t = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  %g.0 = f32[8]{0} get-tuple-element(%t), index=0
  %g.1 = f32[8,8]{1,0} get-tuple-element(%t), index=1
  %fusion.1 = f32[8]{0} fusion(%g.0), kind=kLoop, calls=%fused_scan, metadata={op_name="jit(step_training)/U1/dtpu.ssm_scan/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_sort, metadata={op_name="jit(step_training)/transpose(jvp(U0))/dtpu.moe_route/sort"}
  %fusion.3 = f32[8,8]{1,0} fusion(%g.1), kind=kOutput, calls=%fused_experts, metadata={op_name="jit(step_training)/dtpu.optimizer/add"}
  ROOT %tuple.0 = (f32[8]{0}, f32[8,8]{1,0}) tuple(%fusion.2, %fusion.3)
}

%cond (t.1: (f32[8], f32[8,8])) -> pred[] {
  %t.1 = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  ROOT %true = pred[] constant(true)
}

ENTRY %main (a: f32[8], b: f32[8,8]) -> (f32[8], f32[8,8]) {
  %a = f32[8]{0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %start = (f32[8]{0}, f32[8,8]{1,0}) tuple(%a, %b)
  ROOT %while.1 = (f32[8]{0}, f32[8,8]{1,0}) while(%start), condition=%cond, body=%body, metadata={op_name="jit(step_training)/U0/dtpu.moe_experts/while"}
}
"""


@pytest.fixture()
def made_context(tmp_path):
    """Four steps of 100 us, each one loop that spans a scan op (10 us), a routing op (20 us) and an experts op
    (5 us); the journal's windows count 7040 slots a step: half of what the flops file expects of a row."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    _, _, settings = cell_settings()
    us = 1000
    ops, modules = [], []
    for i in range(4):
        t0 = i * 100 * us
        modules.append(("jit_step_training(1)", t0, t0 + 100 * us))
        ops += [("%while.1 = while(...)", t0, t0 + 90 * us), ("%fusion.1 = fusion(...)", t0 + 10 * us, t0 + 20 * us),
                ("%fusion.2 = fusion(...)", t0 + 30 * us, t0 + 50 * us), ("%fusion.3 = fusion(...)", t0 + 60 * us, t0 + 65 * us)]
    trace = xplane.Trace({"/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: modules}}, [])
    window = lambda slots, ratio: {"kind": "window", "epoch": 2, "moe_slots_here": slots, "moe_load_max_over_mean": ratio}
    journal = [{"kind": "run_start", "out_dir": str(tmp_path)}, window(7040.0, 1.5), window(7040.0, 2.5),
               {"kind": "window", "epoch": 1, "moe_slots_here": 1.0, "moe_load_max_over_mean": 9.0}]
    return {"trace": trace, "journal": journal, "window": {"epoch": 2}, "settings": settings, "chips": 1,
            "batch_per_chip": 1, "roofline": roofline, "layers": files.load_module("flops", "nemotron3_super").layers(settings),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_six_readers_on_a_made_trace(made_context):
    read = lambda name: files.load_module("layer_metrics", name).read(made_context)
    # the ops inside the loop are read by their own scopes; the loop that spans them is not counted, and the
    # experts op is placed by the dot it holds, not by the optimizer's add at its root
    assert read("ssm_scan_ms") == pytest.approx(0.010)
    assert read("moe_route_ms") == pytest.approx(0.020)
    assert read("moe_experts_ms") == pytest.approx(0.005)
    assert read("moe_load_max_over_mean") == 2.5  # the measured window's worst, not another epoch's
    layers, peaks = made_context["layers"], made_context["peaks"]
    scans = [layer for layer in layers if layer["name"].endswith(".scan")]
    least_scan = roofline.mxu_min_seconds_per_step(scans, 1, peaks)
    assert read("ssm_scan_roofline_pct") == pytest.approx(100 * least_scan * 1000 / 0.010)
    # half the expected slots: FLOPs and rows halve, the held weights do not
    routed = [dict(layer, **{k: layer[k] / 2 for k in ("macs", "in", "out", "internal")}) for layer in layers if "slots" in layer]
    assert sum(layer["slots"] for layer in layers if "slots" in layer) / 2 == pytest.approx(14080.0)
    least_experts = roofline.mxu_min_seconds_per_step(routed, 1, peaks)
    assert read("moe_experts_roofline_pct") == pytest.approx(100 * least_experts * 1000 / 0.005)


def test_the_readers_find_nothing_and_raise_nothing_where_the_program_has_no_such_scope(made_context, tmp_path):
    """The parent: no scope in its step, no counter in its journal, or a run with no device trace."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP.replace("dtpu.ssm_scan", "x").replace("dtpu.moe_", "y."))
    bare = dict(made_context, journal=[r for r in made_context["journal"] if r["kind"] == "run_start"])
    names = ("ssm_scan_ms", "ssm_scan_roofline_pct", "moe_route_ms", "moe_experts_ms", "moe_experts_roofline_pct",
             "moe_load_max_over_mean")
    from benchmark import model_scopes

    model_scopes.op_name_of.cache_clear()
    for name in names:
        assert files.load_module("layer_metrics", name).read(bare) is None, name
        assert files.load_module("layer_metrics", name).read(dict(bare, trace=None)) is None, name
    model_scopes.op_name_of.cache_clear()
