"""The files of the configuration ``kanana2_30b``: the work that ``flops/`` counts, the configuration's
file against the catalog's published widths, the benchmark's copy of the reference against
``tests/reference/deepseek_v3.py``, its planted faults, and the readers of the cell's per-layer metrics on
a made trace. CPU; nothing here is a device number."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import files, harness, model_scopes, roofline, traffic, xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "kanana2_30b.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAULTS = ["top5", "no_scale", "no_renorm", "no_shared", "no_rope", "scale_128", "no_latent_norm", "no_causal"]


def cell_settings(rehearse: bool = False):
    cell, config = harness.load_cell(CELL)
    return cell, config, harness.settings_for(cell, config, rehearse)


def test_token_pool_is_two_rows_over_the_held_slice():
    cell, config, settings = cell_settings()
    pool = traffic.make_pool(config["input"], 2**31 + 17, cell["mix"]["pool_batches"], settings["TRAIN"]["BATCH_SIZE"], settings)
    assert len(pool) == 8 and all(batch["tokens"].shape == (2, 8193) for batch in pool)
    assert 0 <= min(b["tokens"].min() for b in pool) and max(b["tokens"].max() for b in pool) < 16032


def test_flops_file_counts_255m_dense_macs_a_token_forward():
    """The issue's count: five mixers' projections of 26.35 M, the dense feed-forward 37.75 M, four expert
    blocks of 13.24 M (router, shared experts and the expected 0.75 held experts a token), the head 32.8 M;
    beside them the core's two products over the causal half, 192 and 128 wide a head and key position."""
    _, _, settings = cell_settings()
    layers = files.load_module("flops", "kanana2_30b").layers(settings)
    tokens = settings["LM"]["SEQ_LEN"]
    by = lambda *ends: sum(layer["macs"] for layer in layers if layer["name"].endswith(ends)) / tokens
    assert by(".q", ".kv_a", ".kv_b", ".o") == pytest.approx(5 * 26.35e6, rel=1e-3)
    assert by(".ff1", ".ff2") == pytest.approx(37.75e6, rel=1e-3)
    assert by(".router", ".routed1", ".routed2", ".shared1", ".shared2") == pytest.approx(4 * 13.24e6, rel=1e-3)
    assert by("head") == pytest.approx(32.8e6, rel=2e-3)
    dense = by(".q", ".kv_a", ".kv_b", ".o", ".ff1", ".ff2", ".router", ".routed1", ".routed2", ".shared1", ".shared2", "head")
    assert dense == pytest.approx(255e6, rel=5e-3)
    assert by(".mla_scores") == pytest.approx(5 * 32 * 192 * (tokens + 1) / 2) and by(".mla_values") == pytest.approx(5 * 32 * 128 * (tokens + 1) / 2)
    cores = [layer for layer in layers if layer["name"].endswith((".mla_scores", ".mla_values"))]
    assert len(cores) == 10 and all(layer["w"] == 0 and layer["internal"] == 32 * tokens * (tokens + 1) // 2 for layer in cores)
    # the core of one step, three passes, two rows: the issue's 20.6 TF
    assert 2 * roofline.train_flops_per_image(cores) == pytest.approx(20.6e12, rel=5e-3)
    assert not [layer for layer in layers if layer["name"].endswith((".scan", ".gdn", ".scores", ".values"))]  # other families' readers find none
    routed = [layer for layer in layers if "slots" in layer]
    assert len(routed) == 8 and all(layer["slots"] == tokens * 6 * 16 / 128 for layer in routed)
    assert roofline.forward_macs_per_image(layers) / tokens == pytest.approx(dense + by(".mla_scores", ".mla_values"))


def test_configuration_file_holds_the_published_widths_and_names_every_cut():
    with open(os.path.join(BENCH, "configs", "kanana2_30b.json")) as f:
        ours = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "kanana2_30b")
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert ours["source"] == entry["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if ours.get(k, object()) != v}
        assert differing == set(entry["reduced"]) == set(ours["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
        assert ours["published"] == {k: row["config"][k] for k in ours["reduced"]}
    widths = {"hidden_size": 2048, "num_attention_heads": 32, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
              "kv_lora_rank": 512, "q_lora_rank": None, "intermediate_size": 6144, "moe_intermediate_size": 768,
              "n_shared_experts": 2, "num_experts_per_tok": 6, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
              "first_k_dense_replace": 1, "rope_theta": 1000000, "rms_norm_eps": 1e-06, "n_group": 1, "topk_group": 1}
    assert {k: ours[k] for k in widths} == widths
    lm = ours["cfg"]["LM"]
    assert (lm["DIM"], lm["ATTN_HEADS"], lm["QK_NOPE_DIM"], lm["QK_ROPE_DIM"], lm["V_HEAD_DIM"], lm["KV_LATENT"], lm["DENSE_WIDTH"],
            lm["EXPERT_WIDTH"], lm["SHARED_WIDTH"], lm["EXPERTS"], lm["TOP_K"], lm["ROUTED_SCALE"], lm["ROPE_THETA"], lm["NORM_EPS"]) == (
        2048, 32, 128, 64, 128, 512, 6144, 768, 2 * 768, 128, 6, 2.448, 1e6, 1e-6)
    assert "8 chips" in ours["deployment"] and ours["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128, "vocab_size": 128256}
    # the held counts divide the published ones as the deployment says; the router keeps all its outputs
    assert lm["EXPERTS_HELD"] * 8 == 128 == ours["n_routed_experts"] * 8 == lm["EXPERTS"] and lm["VOCAB"] * 8 == 128256
    assert len(lm["PATTERN"]) == ours["num_hidden_layers"] == 5 and lm["PATTERN"] == "D" * ours["first_k_dense_replace"] + "EEEE"
    # the program's own file says the same
    import yaml

    with open(os.path.join(ROOT, "config", "kanana2_30b.yaml")) as f:
        shipped = yaml.safe_load(f)
    assert shipped["LM"] == lm and shipped["TRAIN"]["BATCH_SIZE"] == ours["cfg"]["TRAIN"]["BATCH_SIZE"]
    assert {k: shipped["OPTIM"][k] for k in shipped["OPTIM"]} == {k: ours["cfg"]["OPTIM"][k] for k in shipped["OPTIM"]}


def test_reference_shapes_count_576m_parameters():
    _, _, settings = cell_settings()
    ours = files.load_module("reference", "kanana2_30b")
    shapes = ours.shapes(settings)
    assert sum(int(np.prod(shape)) for shape in shapes.values()) == 575_955_456
    assert shapes["U0.w1"] == (4, 16, 2048, 1536) and shapes["L0.ff1"] == (2048, 12288) and shapes["head"] == (2048, 16032)
    count = lambda prefix, leaves: sum(int(np.prod(shapes[f"{prefix}.{leaf}"])) for leaf in leaves)
    assert count("L0", ("q", "kv_a", "kv_norm", "kv_b", "o")) == 26_345_984
    assert count("L0", ("ff1", "ff2")) == 37_748_736
    assert count("U0", ("router", "w1", "w2", "shared1", "shared2")) == 4 * 85_196_800
    stats = jax.eval_shape(lambda: ours.init_stats(settings))
    assert {k: v.shape for k, v in stats.items()} == {"U0.b_corr": (4, 128)}  # the router keeps its 128 outputs


def _plain_reference():
    spec = importlib.util.spec_from_file_location(
        "plain_deepseek_v3", os.path.join(ROOT, "tests", "reference", "deepseek_v3.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _made_weights(ours, settings, key=11):
    """The reference's weights with the norms' moved off 1, so that each counts, and buffers off 0."""
    params = jax.jit(lambda k: ours.init(k, settings))(jax.random.key(key))
    params = {k: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in ours.NORMS else v
              for k, v in params.items()}
    stats = {k: 0.05 * jax.random.normal(jax.random.key(13), v.shape) for k, v in ours.init_stats(settings).items()}
    return params, stats


def test_benchmarks_copy_agrees_with_the_plain_reference_on_loss_and_gradients(monkeypatch):
    """The copy scans the repeats of the pattern's unit (after the leading dense layer) and the experts held,
    takes a row, a head and a block of attention's rows at a time and rematerialises; the plain one loops over
    layers and experts, dense with a mask. Same weights (the copy's leaves of the repeats sliced apart), same
    rows: the same loss and gradients to float32's order of sums."""
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "kanana2_30b")
    monkeypatch.setattr(ours, "ROWS", 16)  # 48 positions: three blocks of attention's rows
    plain = _plain_reference()
    params, stats = _made_weights(ours, settings)
    sizes = dict(ours.sizes(settings), eps=settings["LM"]["NORM_EPS"])
    first, unit, repeats = ours.repeated_unit(sizes["pattern"])
    assert (first, unit, repeats) == (1, 1, 4)  # the rehearsal's pattern walks the leading layer and the scanned path

    def per_layer(tree):
        out = {}
        for name, value in tree.items():
            prefix, _, leaf = name.partition(".")
            if prefix.startswith("U"):
                for r in range(repeats):
                    out[f"L{first + r * unit + int(prefix[1:])}.{leaf}"] = value[r]
            else:
                out[name] = value
        return out

    tokens = traffic.make_pool("tokens", 5, 1, 2, settings)[0]["tokens"]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ours.loss_fn(p, stats, {"tokens": tokens}, "f32"), has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: plain.loss_fn(p, per_layer(stats), tokens, sizes)))(per_layer(params))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    got = per_layer(grads)
    assert set(got) == set(want)
    floor = 1e-2 * float(np.median([float(jnp.linalg.norm(v)) for v in want.values()]))
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]) / jnp.maximum(jnp.linalg.norm(want[name]), floor))
        assert gap <= 2e-4, (name, gap)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_changes_the_references_gradient(fault):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "kanana2_30b")
    assert set(ours.FAULTS) == set(FAULTS)
    params, stats = _made_weights(ours, settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    grads = lambda precision: jax.jit(jax.grad(lambda p: ours.loss_fn(p, stats, batch, precision)[0]))(params)
    sound, planted = grads("f32"), grads(fault)
    moved = max(float(jnp.linalg.norm(planted[k] - sound[k]) / jnp.maximum(jnp.linalg.norm(sound[k]), 1e-30))
                for k in sound)
    assert moved > 0.05, moved
    with pytest.raises(ValueError, match="precision"):
        ours.loss_fn(params, stats, batch, "int4")


@pytest.mark.parametrize("control", ["bf16", "fp8"])
def test_a_control_rounds_the_references_products(control):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "kanana2_30b")
    params, stats = _made_weights(ours, settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    loss = lambda precision: float(jax.jit(lambda p: ours.loss_fn(p, stats, batch, precision)[0])(params))
    gap = abs(loss(control) - loss("f32")) / loss("f32")
    assert 0 < gap < (1e-3 if control == "bf16" else 5e-2)


# -- the cell's readers on a made trace --------------------------------------------------------------------

MADE_STEP = """HloModule jit_step_training

%fused_core (p.0: f32[8,8]) -> f32[8,8] {
  %p.0 = f32[8,8]{1,0} parameter(0)
  ROOT %dot.0 = f32[8,8]{1,0} dot(%p.0, %p.0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_training)/U0/dtpu.latent_attn/checkpoint/dot_general"}
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
  %fusion.2 = f32[8]{0} fusion(%g.0), kind=kLoop, calls=%fused_sort, metadata={op_name="jit(step_training)/transpose(jvp(U0))/dtpu.moe_route/sort"}
  %fusion.1 = f32[8,8]{1,0} fusion(%g.1), kind=kOutput, calls=%fused_core, metadata={op_name="jit(step_training)/U0/dtpu.latent_attn/checkpoint/dot_general"}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.1), kind=kOutput, calls=%fused_experts, metadata={op_name="jit(step_training)/dtpu.optimizer/add"}
  ROOT %tuple.0 = (f32[8]{0}, f32[8,8]{1,0}) tuple(%fusion.2, %fusion.3)
}

%cond (t.1: (f32[8], f32[8,8])) -> pred[] {
  %t.1 = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  ROOT %true = pred[] constant(true)
}

ENTRY %main (a: f32[8], b: f32[8,8]) -> (f32[8], f32[8,8]) {
  %a = f32[8]{0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %fusion.0 = f32[8,8]{1,0} fusion(%b), kind=kOutput, calls=%fused_core, metadata={op_name="jit(step_training)/L0/dtpu.latent_attn/checkpoint/dot_general"}
  %start = (f32[8]{0}, f32[8,8]{1,0}) tuple(%a, %fusion.0)
  ROOT %while.1 = (f32[8]{0}, f32[8,8]{1,0}) while(%start), condition=%cond, body=%body, metadata={op_name="jit(step_training)/U0/while"}
}
"""


@pytest.fixture()
def made_context(tmp_path):
    """Four steps of 100 us: the dense layer's core on its own (5 us), then one loop that spans a core op (10 us),
    a routing op (20 us) and an experts op (5 us); the journal's windows count 24576 slots a step: half of what the
    flops file expects of two rows."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    _, _, settings = cell_settings()
    us = 1000
    ops, modules = [], []
    for i in range(4):
        t0 = i * 100 * us
        modules.append(("jit_step_training(1)", t0, t0 + 100 * us))
        ops += [("%fusion.0 = fusion(...)", t0, t0 + 5 * us), ("%while.1 = while(...)", t0 + 5 * us, t0 + 95 * us),
                ("%fusion.1 = fusion(...)", t0 + 10 * us, t0 + 20 * us), ("%fusion.2 = fusion(...)", t0 + 30 * us, t0 + 50 * us),
                ("%fusion.3 = fusion(...)", t0 + 60 * us, t0 + 65 * us)]
    trace = xplane.Trace({"/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: modules}}, [])
    window = lambda slots, ratio: {"kind": "window", "epoch": 2, "moe_slots_here": slots, "moe_load_max_over_mean": ratio}
    journal = [{"kind": "run_start", "out_dir": str(tmp_path)}, window(24576.0, 1.2), window(24576.0, 1.4)]
    model_scopes.op_name_of.cache_clear()
    yield {"trace": trace, "journal": journal, "window": {"epoch": 2}, "settings": settings, "chips": 1,
           "batch_per_chip": 2, "roofline": roofline, "layers": files.load_module("flops", "kanana2_30b").layers(settings),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    model_scopes.op_name_of.cache_clear()


def test_the_cells_readers_on_a_made_trace(made_context):
    read = lambda name: files.load_module("layer_metrics", name).read(made_context)
    assert read("latent_attn_ms") == pytest.approx(0.015)  # the leading layer's op and the one inside the loop; not the loop
    assert read("moe_route_ms") == pytest.approx(0.020)
    assert read("moe_experts_ms") == pytest.approx(0.005)
    assert read("moe_load_max_over_mean") == 1.4
    for another_familys in ("ssm_scan_ms", "ssm_scan_roofline_pct", "gdn_scan_ms", "gdn_scan_roofline_pct"):
        assert read(another_familys) is None
    layers, peaks = made_context["layers"], made_context["peaks"]
    cores = [layer for layer in layers if layer["name"].endswith((".mla_scores", ".mla_values"))]
    least = roofline.mxu_min_seconds_per_step(cores, 2, peaks)
    assert read("latent_attn_roofline_pct") == pytest.approx(100 * least * 1000 / 0.015)
    # the core's own work: FLOPs bound it (320 a head and key position over the causal half), three passes, five layers, two rows
    assert least == pytest.approx(3 * 5 * 2 * 2 * 32 * 320 * (8192 * 8193 // 2) / 197e12)
    # half the expected slots: FLOPs and rows halve, the held weights do not
    routed = [dict(layer, **{k: layer[k] / 2 for k in ("macs", "in", "out", "internal")}) for layer in layers if "slots" in layer]
    assert read("moe_experts_roofline_pct") == pytest.approx(100 * roofline.mxu_min_seconds_per_step(routed, 2, peaks) * 1000 / 0.005)


def test_the_new_readers_find_nothing_and_raise_nothing_where_the_program_has_no_such_scope(made_context, tmp_path):
    """The parent: no such scope in its step, or a run with no device trace; and a configuration with no such work."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP.replace("dtpu.latent_attn", "x"))
    model_scopes.op_name_of.cache_clear()
    for name in ("latent_attn_ms", "latent_attn_roofline_pct"):
        assert files.load_module("layer_metrics", name).read(made_context) is None, name
        assert files.load_module("layer_metrics", name).read(dict(made_context, trace=None)) is None, name
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    model_scopes.op_name_of.cache_clear()
    other = files.load_module("flops", "qwen3_next").layers(harness.settings_for(*harness.load_cell("qwen3_next.train"), False))
    assert files.load_module("layer_metrics", "latent_attn_roofline_pct").read(dict(made_context, layers=other)) is None
