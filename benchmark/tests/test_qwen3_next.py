"""The files of the configuration ``qwen3_next``: the work that ``flops/`` counts, the configuration's
file against the catalog's published widths, the benchmark's copy of the reference against
``tests/reference/qwen3_next.py``, its planted faults, and the readers of the cell's per-layer metrics on
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
CELL = "qwen3_next.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cell_settings(rehearse: bool = False):
    cell, config = harness.load_cell(CELL)
    return cell, config, harness.settings_for(cell, config, rehearse)


def test_token_pool_is_two_rows_over_the_held_slice():
    cell, config, settings = cell_settings()
    pool = traffic.make_pool(config["input"], 2**31 + 17, cell["mix"]["pool_batches"], settings["TRAIN"]["BATCH_SIZE"], settings)
    assert len(pool) == 8 and all(batch["tokens"].shape == (2, 8193) for batch in pool)
    assert 0 <= min(b["tokens"].min() for b in pool) and max(b["tokens"].max() for b in pool) < 18992


def test_flops_file_counts_192m_dense_macs_a_token_forward():
    """The issue's count: the mixers' products 3 x 33.7 M + 27.3 M, four expert blocks of 6.2 M (router, shared
    expert and the expected 0.625 held experts a token), the head 38.9 M; beside them attention's two products
    over the causal half and the recurrence's own 3 K V a token and value head."""
    _, _, settings = cell_settings()
    layers = files.load_module("flops", "qwen3_next").layers(settings)
    tokens = settings["LM"]["SEQ_LEN"]
    by = lambda *ends: sum(layer["macs"] for layer in layers if layer["name"].endswith(ends)) / tokens
    assert by(".in_qkvz", ".in_ba", ".out") == pytest.approx(3 * 33.68e6, rel=2e-3)
    assert by(".q", ".kv", ".o") == pytest.approx(27.26e6, rel=2e-3)
    assert by(".router", ".routed1", ".routed2", ".shared1", ".shared2", ".shared_gate") == pytest.approx(4 * 6.16e6, rel=5e-3)
    assert by("head") == pytest.approx(38.9e6, rel=2e-3)
    dense = by(".in_qkvz", ".in_ba", ".out", ".q", ".kv", ".o", ".router", ".routed1", ".routed2", ".shared1",
               ".shared2", ".shared_gate", "head")
    assert dense == pytest.approx(192e6, rel=5e-3)
    assert by(".scores", ".values") == pytest.approx(2 * 16 * 256 * (tokens + 1) / 2)
    rules = [layer for layer in layers if layer["name"].endswith(".gdn")]
    assert len(rules) == 3 and all(layer["w"] == 0 and layer["macs"] == tokens * 32 * 3 * 128 * 128 for layer in rules)
    assert not [layer for layer in layers if layer["name"].endswith(".scan")]  # the state-space scan's reader finds none
    routed = [layer for layer in layers if "slots" in layer]
    assert len(routed) == 8 and all(layer["slots"] == tokens * 10 * 32 / 512 for layer in routed)
    assert roofline.forward_macs_per_image(layers) / tokens == pytest.approx(dense + by(".scores", ".values", ".gdn"))


def test_configuration_file_holds_the_published_widths_and_names_every_cut():
    with open(os.path.join(BENCH, "configs", "qwen3_next.json")) as f:
        ours = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "qwen3_next")
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert ours["source"] == entry["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if ours.get(k, object()) != v}
        assert differing == set(entry["reduced"]) == set(ours["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
        assert ours["published"] == {k: row["config"][k] for k in ours["reduced"]}
    widths = {"hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
              "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_key_head_dim": 128,
              "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10, "partial_rotary_factor": 0.25,
              "rope_theta": 10000000, "rms_norm_eps": 1e-06, "full_attention_interval": 4}
    assert {k: ours[k] for k in widths} == widths
    lm = ours["cfg"]["LM"]
    assert (lm["DIM"], lm["ATTN_HEADS"], lm["KV_HEADS"], lm["HEAD_DIM"], lm["LINEAR_KEY_HEADS"], lm["LINEAR_VALUE_HEADS"],
            lm["LINEAR_KEY_DIM"], lm["LINEAR_VALUE_DIM"], lm["CONV_KERNEL"], lm["EXPERT_WIDTH"], lm["SHARED_WIDTH"],
            lm["EXPERTS"], lm["TOP_K"], lm["ROPE_SHARE"], lm["ROPE_THETA"], lm["NORM_EPS"]) == (
        2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 512, 512, 10, 0.25, 1e7, 1e-6)
    assert "16 chips" in ours["deployment"] and ours["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # the held counts divide the published ones as the deployment says
    assert lm["EXPERTS_HELD"] * 16 == 512 == ours["num_experts"] * 16 and lm["VOCAB"] * 8 == 151936
    assert len(lm["PATTERN"]) == ours["num_hidden_layers"] == ours["full_attention_interval"] == 4
    # the program's own file says the same
    import yaml

    with open(os.path.join(ROOT, "config", "qwen3_next.yaml")) as f:
        shipped = yaml.safe_load(f)
    assert shipped["LM"] == lm and shipped["TRAIN"]["BATCH_SIZE"] == ours["cfg"]["TRAIN"]["BATCH_SIZE"]
    assert {k: shipped["OPTIM"][k] for k in shipped["OPTIM"]} == {k: ours["cfg"]["OPTIM"][k] for k in shipped["OPTIM"]}


def test_reference_shapes_count_626m_parameters():
    _, _, settings = cell_settings()
    shapes = files.load_module("reference", "qwen3_next").shapes(settings)
    assert sum(int(np.prod(shape)) for shape in shapes.values()) == 625_667_136
    assert shapes["U0.w1"] == (3, 32, 2048, 1024) and shapes["L3.q"] == (2048, 8192) and shapes["head"] == (2048, 18992)
    mixer = lambda prefix, leaves: sum(int(np.prod(shapes[f"{prefix}.{leaf}"])) for leaf in leaves)
    assert mixer("U0", ("in_qkvz", "in_ba", "conv_w", "a_log", "dt_bias", "gnorm", "out")) == 3 * 33_718_464
    assert mixer("L3", ("q", "k", "v", "o", "q_norm", "k_norm")) == 27_263_488
    assert mixer("L3", ("router", "w1", "w2", "shared1", "shared2", "shared_gate")) == 104_859_648


def _plain_reference():
    spec = importlib.util.spec_from_file_location(
        "plain_qwen3_next", os.path.join(ROOT, "tests", "reference", "qwen3_next.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _made_weights(ours, settings, key=11):
    """The reference's weights with the leaves that start at 0 or 1 moved off them, so that each counts."""
    params = jax.jit(lambda k: ours.init(k, settings))(jax.random.key(key))
    flat = ours.ZERO_CENTRED + ("gnorm", "dt_bias")
    return {k: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in flat else v
            for k, v in params.items()}


def test_benchmarks_copy_agrees_with_the_plain_reference_on_loss_and_gradients(monkeypatch):
    """The copy scans the repeats of the pattern's unit and the experts held, takes a row, a stretch of the
    recurrence and a block of attention's rows at a time and rematerialises; the plain one loops over layers
    and experts. Same weights (the copy's leaves of the repeats sliced apart), same rows: the same loss and
    gradients to float32's order of sums."""
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "qwen3_next")
    monkeypatch.setattr(ours, "STRETCH", 16)  # 48 positions: three stretches
    monkeypatch.setattr(ours, "ROWS", 16)     # ... and three blocks of attention's rows
    plain = _plain_reference()
    params = _made_weights(ours, settings)
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
        lambda p: ours.loss_fn(p, {}, {"tokens": tokens}, "f32"), has_aux=True))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: plain.loss_fn(p, {}, tokens, sizes)))(per_layer(params))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    got = per_layer(grads)
    assert set(got) == set(want)
    floor = 1e-2 * float(np.median([float(jnp.linalg.norm(v)) for v in want.values()]))  # the decays' leaves are tiny
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name]) / jnp.maximum(jnp.linalg.norm(want[name]), floor))
        assert gap <= 2e-4, (name, gap)


@pytest.mark.parametrize("fault", ["top9", "no_renorm", "no_decay", "no_beta", "no_carry", "no_rope", "no_gate", "no_causal"])
def test_a_planted_fault_changes_the_references_gradient(fault, monkeypatch):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "qwen3_next")
    assert set(ours.FAULTS) == {"top9", "no_renorm", "no_decay", "no_beta", "no_carry", "no_rope", "no_gate", "no_causal"}
    monkeypatch.setattr(ours, "STRETCH", 16)
    params = _made_weights(ours, settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    grads = lambda precision: jax.jit(jax.grad(lambda p: ours.loss_fn(p, {}, batch, precision)[0]))(params)
    sound, planted = grads("f32"), grads(fault)
    moved = max(float(jnp.linalg.norm(planted[k] - sound[k]) / jnp.maximum(jnp.linalg.norm(sound[k]), 1e-30))
                for k in sound)
    assert moved > 0.05, moved
    with pytest.raises(ValueError, match="precision"):
        ours.loss_fn(params, {}, batch, "int4")


@pytest.mark.parametrize("control", ["bf16", "fp8"])
def test_a_control_rounds_the_references_products(control):
    _, _, settings = cell_settings(rehearse=True)
    ours = files.load_module("reference", "qwen3_next")
    params = _made_weights(ours, settings)
    batch = traffic.make_pool("tokens", 5, 1, 2, settings)[0]
    loss = lambda precision: float(jax.jit(lambda p: ours.loss_fn(p, {}, batch, precision)[0])(params))
    gap = abs(loss(control) - loss("f32")) / loss("f32")
    assert 0 < gap < (1e-3 if control == "bf16" else 5e-2)


# -- the cell's readers on a made trace --------------------------------------------------------------------

MADE_STEP = """HloModule jit_step_training

%fused_rule (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  ROOT %mul.0 = f32[8]{0} multiply(%p.0, %p.0), metadata={op_name="jit(step_training)/U0/dtpu.gdn_scan/mul"}
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
  %fusion.1 = f32[8]{0} fusion(%g.0), kind=kLoop, calls=%fused_rule, metadata={op_name="jit(step_training)/U0/dtpu.gdn_scan/mul"}
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
  ROOT %while.1 = (f32[8]{0}, f32[8,8]{1,0}) while(%start), condition=%cond, body=%body, metadata={op_name="jit(step_training)/U0/dtpu.gdn_scan/while"}
}
"""


@pytest.fixture()
def made_context(tmp_path):
    """Four steps of 100 us, each one loop that spans a delta-rule op (10 us), a routing op (20 us) and an experts
    op (5 us); the journal's windows count 20480 slots a step: half of what the flops file expects of two rows."""
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
    journal = [{"kind": "run_start", "out_dir": str(tmp_path)}, window(20480.0, 1.2), window(20480.0, 1.4)]
    model_scopes.op_name_of.cache_clear()
    yield {"trace": trace, "journal": journal, "window": {"epoch": 2}, "settings": settings, "chips": 1,
           "batch_per_chip": 2, "roofline": roofline, "layers": files.load_module("flops", "qwen3_next").layers(settings),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    model_scopes.op_name_of.cache_clear()


def test_the_cells_readers_on_a_made_trace(made_context):
    read = lambda name: files.load_module("layer_metrics", name).read(made_context)
    assert read("gdn_scan_ms") == pytest.approx(0.010)  # the op inside the loop; the loop that spans it is not counted
    assert read("moe_route_ms") == pytest.approx(0.020)
    assert read("moe_experts_ms") == pytest.approx(0.005)
    assert read("moe_load_max_over_mean") == 1.4
    assert read("ssm_scan_ms") is None and read("ssm_scan_roofline_pct") is None  # another family's
    layers, peaks = made_context["layers"], made_context["peaks"]
    rules = [layer for layer in layers if layer["name"].endswith(".gdn")]
    least = roofline.mxu_min_seconds_per_step(rules, 2, peaks)
    assert read("gdn_scan_roofline_pct") == pytest.approx(100 * least * 1000 / 0.010)
    # the recurrence's own work: bytes bound it (q, k, v in and o out once a pass), three passes, three layers, two rows
    assert least == pytest.approx(3 * 3 * 2 * 2 * 8192 * (2 * 2048 + 4096 + 64 + 4096) / 819e9)
    # half the expected slots: FLOPs and rows halve, the held weights do not
    routed = [dict(layer, **{k: layer[k] / 2 for k in ("macs", "in", "out", "internal")}) for layer in layers if "slots" in layer]
    assert read("moe_experts_roofline_pct") == pytest.approx(100 * roofline.mxu_min_seconds_per_step(routed, 2, peaks) * 1000 / 0.005)


def test_the_new_readers_find_nothing_and_raise_nothing_where_the_program_has_no_such_scope(made_context, tmp_path):
    """The parent: no such scope in its step, or a run with no device trace; and a configuration with no such work."""
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP.replace("dtpu.gdn_scan", "x"))
    model_scopes.op_name_of.cache_clear()
    for name in ("gdn_scan_ms", "gdn_scan_roofline_pct"):
        assert files.load_module("layer_metrics", name).read(made_context) is None, name
        assert files.load_module("layer_metrics", name).read(dict(made_context, trace=None)) is None, name
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    model_scopes.op_name_of.cache_clear()
    other = files.load_module("flops", "nemotron3_super").layers(harness.settings_for(*harness.load_cell("nemotron3_super.train"), False))
    assert files.load_module("layer_metrics", "gdn_scan_roofline_pct").read(dict(made_context, layers=other)) is None
