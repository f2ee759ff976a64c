"""The files of the configuration ``laguna_s21``: the work that ``flops/`` counts (the band of the window layers),
the configuration's file against the catalog's published widths, the benchmark's copy of the reference against
``tests/reference/laguna.py`` and against the program at a small size, its planted faults, the readers of the
cell's per-layer metrics on a made trace, and the cell's step compiled for a described v5e beside the harness's
copy of the weights. CPU; nothing here is a device number."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import files, harness, model_scopes, roofline, traffic, xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "laguna_s21.train"
FAULTS = ["no_window", "no_yarn", "no_gate", "no_scale"]


def cell_settings(rehearse: bool = False):
    cell, config = harness.load_cell(CELL)
    return cell, config, harness.settings_for(cell, config, rehearse)


def test_token_pool_is_one_row_over_the_held_slice():
    cell, config, settings = cell_settings()
    pool = traffic.make_pool(config["input"], 2**31 + 17, cell["mix"]["pool_batches"], settings["TRAIN"]["BATCH_SIZE"], settings)
    assert len(pool) == 8 and all(batch["tokens"].shape == (1, 8193) for batch in pool)
    assert 0 <= min(b["tokens"].min() for b in pool) and max(b["tokens"].max() for b in pool) < 12544


def test_flops_file_counts_the_band_of_the_window_layers():
    """Per token, forward: the attention mixers' projections (full layers q 18.87 M, k and v 6.29 M, the gate 0.15 M,
    o 18.87 M; window layers q and o 28.31 M each, the gate 0.22 M), the dense feed-forward 113.2 M, four expert
    blocks of 13.2 M (router, shared expert and its gate and the expected 10/32 of a held expert a token), the head
    38.5 M; beside them the full layers' cores over the causal half and the window layers' over the band,
    ``Σ_i min(i + 1, 512)`` key positions a head: 8 times under the causal half at 8192."""
    _, _, settings = cell_settings()
    layers = files.load_module("flops", "laguna_s21").layers(settings)
    tokens = settings["LM"]["SEQ_LEN"]
    by = lambda *ends: sum(layer["macs"] for layer in layers if layer["name"].endswith(ends)) / tokens
    assert by("L0.q", "L0.kv", "L0.gate", "L0.o") == 3072 * (2 * 6144 + 2048 + 48)
    assert by("L1.q", "L1.kv", "L1.gate", "L1.o") == 3072 * (2 * 9216 + 2048 + 72)
    assert by(".ff1", ".ff2") == 3 * 3072 * 12288
    assert by(".router", ".routed1", ".routed2", ".shared1", ".shared2", ".shared_gate") == pytest.approx(
        4 * 3072 * (256 + 3 * 1024 * 10 * 8 / 256 + 3 * 1024 + 1))
    assert by("head") == 3072 * 12544
    band = 512 * 513 // 2 + (tokens - 512) * 512
    assert files.load_module("flops", "laguna_s21").band_positions(tokens, 512) == band
    assert by(".swa_scores") == by(".swa_values") == 3 * 72 * 128 * band / tokens
    assert by("L0.scores") == by("L4.scores") == 48 * 128 * (tokens + 1) / 2
    causal = tokens * (tokens + 1) // 2
    assert causal / band == pytest.approx(8.26, abs=0.01)
    cores = [layer for layer in layers if layer["name"].endswith((".swa_scores", ".swa_values"))]
    assert len(cores) == 6 and all(layer["internal"] == 72 * band for layer in cores)
    assert not [layer for layer in layers if layer["name"].endswith((".kda", ".gdn", ".scan", ".mla_scores"))]
    routed = [layer for layer in layers if "slots" in layer]
    assert len(routed) == 8 and all(layer["slots"] == tokens * 10 * 8 / 256 for layer in routed)


def test_configuration_file_holds_the_published_widths_and_names_every_cut():
    with open(os.path.join(BENCH, "configs", "laguna_s21.json")) as f:
        ours = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "laguna_s21")
    cut = {"num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "num_experts",
           "vocab_size"}
    assert ours["source"] == entry["source"] and set(entry["reduced"]) == set(ours["reduced"]) == cut
    widths = {"hidden_size": 3072, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
              "intermediate_size": 12288, "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
              "num_experts_per_tok": 10, "moe_routed_scaling_factor": 2.5, "sliding_window": 512,
              "norm_topk_prob": True, "rms_norm_eps": 1e-06, "gating": "per-head", "tie_word_embeddings": False,
              "moe_router_logit_softcapping": 0, "moe_apply_router_weight_on_input": False}
    assert {k: ours[k] for k in widths} == widths
    full, sliding = ours["rope_parameters"]["full_attention"], ours["rope_parameters"]["sliding_attention"]
    lm = ours["cfg"]["LM"]
    assert (lm["DIM"], lm["FULL_HEADS"], lm["WINDOW_HEADS"], lm["KV_HEADS"], lm["HEAD_DIM"], lm["WINDOW"], lm["DENSE_WIDTH"],
            lm["EXPERT_WIDTH"], lm["SHARED_WIDTH"], lm["EXPERTS"], lm["TOP_K"], lm["ROUTED_SCALE"], lm["NORM_EPS"]) == (
        3072, 48, 72, 8, 128, 512, 12288, 1024, 1024, 256, 10, 2.5, 1e-6)
    assert (lm["ROPE_THETA"], lm["ROPE_SHARE"], lm["YARN_FACTOR"], lm["YARN_ORIGINAL_POSITIONS"], lm["YARN_BETA_FAST"],
            lm["YARN_BETA_SLOW"], lm["YARN_ATTENTION_FACTOR"]) == (
        full["rope_theta"], full["partial_rotary_factor"], full["factor"], full["original_max_position_embeddings"],
        full["beta_fast"], full["beta_slow"], full["attention_factor"])
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default" and sliding["partial_rotary_factor"] == 1
    assert lm["WINDOW_ROPE_THETA"] == sliding["rope_theta"]
    assert "32 chips" in ours["deployment"]
    published = ours["published"]
    assert (published["num_hidden_layers"], published["num_experts"], published["vocab_size"]) == (48, 256, 100352)
    # the held counts divide the published ones as the deployment says; the router keeps all its outputs
    assert lm["EXPERTS_HELD"] * 32 == 256 == ours["num_experts"] * 32 == lm["EXPERTS"] and lm["VOCAB"] * 8 == 100352
    # the held layers are the published first five, their kinds and heads as published
    kinds = {("full_attention", "dense"): "D", ("sliding_attention", "sparse"): "W", ("full_attention", "sparse"): "F"}
    pattern = "".join(kinds[pair] for pair in zip(ours["layer_types"], ours["mlp_layer_types"]))
    assert pattern == lm["PATTERN"] == "DWWWF" and len(pattern) == ours["num_hidden_layers"]
    assert ours["layer_types"] == published["layer_types"][:5] and ours["mlp_layer_types"] == published["mlp_layer_types"][:5]
    assert ours["num_attention_heads_per_layer"] == published["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    for key in ("qk_norm", "window", "gating", "shared_expert_gate", "rotary"):
        assert key in ours["assumed"], key
    import yaml

    with open(os.path.join(ROOT, "config", "laguna_s21.yaml")) as f:
        shipped = yaml.safe_load(f)
    assert shipped["LM"] == lm and shipped["TRAIN"]["BATCH_SIZE"] == ours["cfg"]["TRAIN"]["BATCH_SIZE"]
    assert {k: shipped["OPTIM"][k] for k in shipped["OPTIM"]} == {k: ours["cfg"]["OPTIM"][k] for k in shipped["OPTIM"]}


def test_reference_shapes_count_811m_parameters():
    _, _, settings = cell_settings()
    ours = files.load_module("reference", "laguna_s21")
    shapes = ours.shapes(settings)
    assert sum(int(np.prod(shape)) for shape in shapes.values()) == 811_030_784
    assert shapes["U0.q"] == (3, 3072, 9216) and shapes["L4.q"] == (3072, 6144) and shapes["head"] == (3072, 12544)
    count = lambda prefix: sum(int(np.prod(v)) for k, v in shapes.items() if k.startswith(prefix + "."))
    assert count("L0") == 157_440_256 and count("U0") == 3 * 148_866_304 and count("L4") == 129_918_208


def _load_plain():
    spec = importlib.util.spec_from_file_location("plain_laguna", os.path.join(ROOT, "tests", "reference", "laguna.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _made_weights(ours, settings, key=11):
    """The reference's weights with the norms' moved off 1, so that each counts."""
    params = jax.jit(lambda k: ours.init(k, settings))(jax.random.key(key))
    return {k: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in ours.NORMS else v
            for k, v in params.items()}


def _per_layer(ours, sizes, tree):
    first, unit, repeats = ours.repeated_unit(sizes["pattern"])
    out = {}
    for name, value in tree.items():
        prefix, _, leaf = name.partition(".")
        if prefix.startswith("U"):
            for r in range(repeats):
                out[f"L{first + r * unit + int(prefix[1:])}.{leaf}"] = value[r]
        else:
            out[name] = value
    return out


def test_benchmarks_copy_agrees_with_the_plain_reference_on_loss_and_gradients(monkeypatch):
    """The copy scans the repeats of the pattern's unit and the experts held, takes a row, a key head's group, a head
    and a block of attention's rows and a block of tokens at a time and rematerialises; the plain one loops over
    layers and experts, dense with a mask. Same weights, same rows: the same loss and gradients to float32's
    order of sums."""
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "laguna_s21")
    monkeypatch.setattr(ours, "ROWS", 16)    # 80 positions: five blocks of attention's rows
    monkeypatch.setattr(ours, "TOKENS", 16)  # five blocks of the loss and of the feed-forwards
    plain = _load_plain()
    params = _made_weights(ours, settings)
    sizes = dict(ours.sizes(settings), eps=settings["LM"]["NORM_EPS"])
    assert ours.repeated_unit(sizes["pattern"]) == (1, 1, 3)
    tokens = traffic.make_pool("tokens", 5, 1, 2, settings)[0]["tokens"]
    (loss, _), grads = jax.jit(jax.value_and_grad(lambda p: ours.loss_fn(p, {}, {"tokens": tokens}, "f32"), has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: plain.loss_fn(p, {}, tokens, sizes)))(_per_layer(ours, sizes, params))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    got = _per_layer(ours, sizes, grads)
    assert set(got) == set(want)
    floor = 1e-2 * float(np.median([float(jnp.linalg.norm(v)) for v in want.values()]))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]) / jnp.maximum(jnp.linalg.norm(want[name]), floor))
        assert gap <= 2e-4, (name, gap)


def test_the_program_agrees_with_the_benchmarks_copy_at_a_small_size(fresh_cfg_lm):
    """The program built as the harness builds it (float32 here) on the copy's own weights: the same loss and
    gradients, the copy's leaves in the program's names."""
    from distribuuuu_tpu import trainer

    cell, config, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "laguna_s21")
    harness.merge_into_cfg(dict(settings, MODEL=dict(settings["MODEL"], DTYPE="float32")), "/tmp/unused", 0)
    model = trainer._build_cfg_model()
    params = _made_weights(ours, settings)
    program_params, _ = ours.to_program(params, {})
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: trainer._forward_loss_lm(model, p, {}, batch)[0]))(program_params)
    (want_loss, _), want = jax.jit(jax.value_and_grad(lambda p: ours.loss_fn(p, {}, batch, "f32"), has_aux=True))(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    want = ours.to_program(want, {})[0]
    floor = 1e-2 * float(np.median([float(jnp.linalg.norm(v)) for v in want.values()]))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]) / jnp.maximum(jnp.linalg.norm(want[name]), floor))
        assert gap <= 2e-4, (name, gap)


@pytest.fixture()
def fresh_cfg_lm():
    from distribuuuu_tpu.config import reset_cfg

    reset_cfg()
    yield
    reset_cfg()


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_changes_the_references_gradient(fault):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "laguna_s21")
    assert set(ours.FAULTS) == set(FAULTS)
    params = _made_weights(ours, settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    grads = lambda precision: jax.jit(jax.grad(lambda p: ours.loss_fn(p, {}, batch, precision)[0]))(params)
    sound, planted = grads("f32"), grads(fault)
    moved = max(float(jnp.linalg.norm(planted[k] - sound[k]) / jnp.maximum(jnp.linalg.norm(sound[k]), 1e-30))
                for k in sound)
    assert moved > 0.05, moved
    with pytest.raises(ValueError, match="precision"):
        ours.loss_fn(params, {}, batch, "int4")


# -- the cell's readers on a made trace --------------------------------------------------------------------

MADE_STEP = """HloModule jit_step_scoped

%fused_band (p.0: f32[8,8]) -> f32[8,8] {
  %p.0 = f32[8,8]{1,0} parameter(0)
  ROOT %dot.0 = f32[8,8]{1,0} dot(%p.0, %p.0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_scoped)/U0/dtpu.causal_attn/dtpu.window_attn/dot_general"}
}

%fused_causal (p.1: f32[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,8]{1,0} dot(%p.1, %p.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_scoped)/L0/dtpu.causal_attn/dot_general"}
}

%body (t: (f32[8], f32[8,8])) -> (f32[8], f32[8,8]) {
  %t = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  %g.0 = f32[8]{0} get-tuple-element(%t), index=0
  %g.1 = f32[8,8]{1,0} get-tuple-element(%t), index=1
  %fusion.1 = f32[8,8]{1,0} fusion(%g.1), kind=kOutput, calls=%fused_band, metadata={op_name="jit(step_scoped)/transpose(jvp(U0))/dtpu.causal_attn/dtpu.window_attn/dot_general"}
  ROOT %tuple.0 = (f32[8]{0}, f32[8,8]{1,0}) tuple(%g.0, %fusion.1)
}

%cond (t.1: (f32[8], f32[8,8])) -> pred[] {
  %t.1 = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  ROOT %true = pred[] constant(true)
}

ENTRY %main (a: f32[8], b: f32[8,8]) -> (f32[8], f32[8,8]) {
  %a = f32[8]{0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %fusion.0 = f32[8,8]{1,0} fusion(%b), kind=kOutput, calls=%fused_causal, metadata={op_name="jit(step_scoped)/L0/dtpu.causal_attn/dot_general"}
  %start = (f32[8]{0}, f32[8,8]{1,0}) tuple(%a, %fusion.0)
  ROOT %while.1 = (f32[8]{0}, f32[8,8]{1,0}) while(%start), condition=%cond, body=%body, metadata={op_name="jit(step_scoped)/U0/while"}
}
"""


@pytest.fixture()
def made_context(tmp_path):
    """Four steps of 100 us: the full layer's core on its own (5 us), then one loop that spans a window core's op
    (10 us)."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    _, _, settings = cell_settings()
    us = 1000
    ops, modules = [], []
    for i in range(4):
        t0 = i * 100 * us
        modules.append(("jit_step_scoped(1)", t0, t0 + 100 * us))
        ops += [("%fusion.0 = fusion(...)", t0, t0 + 5 * us), ("%while.1 = while(...)", t0 + 5 * us, t0 + 95 * us),
                ("%fusion.1 = fusion(...)", t0 + 10 * us, t0 + 20 * us)]
    trace = xplane.Trace({"/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: modules}}, [])
    journal = [{"kind": "run_start", "out_dir": str(tmp_path)}]
    model_scopes.op_name_of.cache_clear()
    yield {"trace": trace, "journal": journal, "window": {"epoch": 2}, "settings": settings, "chips": 1,
           "batch_per_chip": 1, "roofline": roofline, "layers": files.load_module("flops", "laguna_s21").layers(settings),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    model_scopes.op_name_of.cache_clear()


def test_the_cells_readers_on_a_made_trace(made_context):
    read = lambda name: files.load_module("layer_metrics", name).read(made_context)
    assert read("window_attn_ms") == pytest.approx(0.010)  # the window core's op inside the loop; not the loop
    assert read("causal_attn_ms") == pytest.approx(0.015)  # the window's scope is inside the causal one's
    for another_familys in ("kda_scan_ms", "gdn_scan_ms", "latent_attn_ms", "latent_attn_roofline_pct"):
        assert read(another_familys) is None
    cores = [layer for layer in made_context["layers"] if layer["name"].endswith((".swa_scores", ".swa_values"))]
    least = roofline.mxu_min_seconds_per_step(cores, 1, made_context["peaks"])
    assert read("window_attn_roofline_pct") == pytest.approx(100 * least * 1000 / 0.010)
    # three layers' cores, three passes: their FLOPs bound them (72 heads, 128 wide, the band's positions)
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert least == pytest.approx(3 * 3 * 2 * 2 * 72 * 128 * band / 197e12)


def test_the_new_readers_find_nothing_and_raise_nothing_where_the_program_has_no_such_scope(made_context, tmp_path):
    """The parent: no such scope in its step, or a run with no device trace; and a configuration with no window."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP.replace("/dtpu.window_attn", ""))
    model_scopes.op_name_of.cache_clear()
    for name in ("window_attn_ms", "window_attn_roofline_pct"):
        assert files.load_module("layer_metrics", name).read(made_context) is None, name
        assert files.load_module("layer_metrics", name).read(dict(made_context, trace=None)) is None, name
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    model_scopes.op_name_of.cache_clear()
    other = files.load_module("flops", "qwen3_next").layers(harness.settings_for(*harness.load_cell("qwen3_next.train"), False))
    assert files.load_module("layer_metrics", "window_attn_roofline_pct").read(dict(made_context, layers=other)) is None


# -- the cell's step for a described v5e -------------------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the chip from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_laguna_s21_step_compiles_for_v5e_and_fits_beside_the_benchmarks_copy(topo, fresh_cfg_lm):
    """The cell's own step (config/laguna_s21.yaml: one row of 8192 tokens, 811.0 M parameters, Adafactor, the layer
    checkpoint) compiled for the described chip: the compiler accepts it, and by its own count the step's peak
    leaves room on the chip's 15.75 GiB for the benchmark's second copy of the weights; the window layers' cores are
    the window pair and the full layers' the causal pair, each call of five operands at the most."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distribuuuu_tpu import optim, trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    jax.config.update("jax_enable_compilation_cache", False)
    cfg.merge_from_file(os.path.join(ROOT, "config", "laguna_s21.yaml"))
    interpret = set_pallas_interpret(False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        model = trainer._build_cfg_model()
        tx = optim.construct_optimizer()

        def init(key):
            variables = model.init(key, model.dummy_input(0), train=False)
            return trainer.TrainState(params=variables["params"], batch_stats={}, opt_state=tx.init(variables["params"]))

        replicated = NamedSharding(mesh, P())
        struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
        state = jax.tree.map(struct, jax.eval_shape(init, jax.random.key(0)))
        batch = {"tokens": jax.ShapeDtypeStruct((cfg.TRAIN.BATCH_SIZE, cfg.LM.SEQ_LEN + 1), jnp.int32,
                                                sharding=NamedSharding(mesh, P("data", None)))}
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        compiled = step.lower(state, batch, struct(jnp.float32(0.0)),
                              struct(jax.eval_shape(lambda: jax.random.key(1)))).compile()
    finally:
        set_pallas_interpret(interpret)
    memory = compiled.memory_analysis()
    weights = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params))
    assert weights == 4 * 811_030_784
    assert memory.peak_memory_in_bytes + weights <= 15.75 * 2**30, memory.peak_memory_in_bytes / 2**30
    text = compiled.as_text()
    assert set(re.findall(r"/(dtpu_\w+)/pallas_call", text)) == {
        "dtpu_window512_attn_fwd", "dtpu_window512_attn_bwd", "dtpu_causal_attn_fwd", "dtpu_causal_attn_bwd",
        "dtpu_moe_gmm", "dtpu_moe_tgmm"}
    for scope in ("dtpu.window_attn", "dtpu.causal_attn", "dtpu.mixer_proj", "dtpu.dense_ffn", "dtpu.moe_route",
                  "dtpu.moe_experts", "dtpu.lm_head", "dtpu.optimizer", "dtpu.loss"):
        assert scope in text, scope
    # the window cores stand under the scanned unit alone, and inside the causal scope
    assert set(re.findall(r"/(L\d+|U\d+)/dtpu\.causal_attn/dtpu\.window_attn/", text)) == {"U0"}
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert not any("/*index=" in re.search(r"custom-call\(([^)]*)\)", line).group(1) for line in calls)
