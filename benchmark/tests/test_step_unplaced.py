"""The readers of the token step's dense and causal scopes, and of what no scope places, on a made trace."""

import pytest

from benchmark import files, model_scopes, xplane

J = "jit(step_scoped)"
MADE_STEP = f"""HloModule jit_step_scoped

%fused_proj (p.0: f32[8,8]) -> f32[8,8] {{
  %p.0 = f32[8,8]{{1,0}} parameter(0)
  %dot.0 = f32[8,8]{{1,0}} dot(%p.0, %p.0), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{J}/jvp(M)/U0/dtpu.mixer_proj/dot_general"}}
  ROOT %mul.0 = f32[8,8]{{1,0}} multiply(%dot.0, %p.0), metadata={{op_name="{J}/jvp(M)/U0/mul"}}
}}

%fused_norm (p.1: f32[8]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0)
  ROOT %rsqrt.1 = f32[8]{{0}} rsqrt(%p.1), metadata={{op_name="{J}/jvp(M)/U0/rsqrt"}}
}}

%fused_core (p.2: f32[8,8]) -> f32[8,8] {{
  %p.2 = f32[8,8]{{1,0}} parameter(0)
  ROOT %dot.2 = f32[8,8]{{1,0}} dot(%p.2, %p.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{J}/jvp(M)/U0/dtpu.latent_attn/dtpu.causal_attn/dot_general"}}
}}

%fused_loss (p.3: f32[8]) -> f32[8] {{
  %p.3 = f32[8]{{0}} parameter(0)
  ROOT %exp.3 = f32[8]{{0}} exponential(%p.3), metadata={{op_name="{J}/jvp(dtpu.loss)/while/body/exp"}}
}}

%fused_head (p.4: f32[8,8]) -> f32[8,8] {{
  %p.4 = f32[8,8]{{1,0}} parameter(0)
  ROOT %dot.4 = f32[8,8]{{1,0}} dot(%p.4, %p.4), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{J}/transpose(jvp(dtpu.loss))/while/body/M.head_logits/dtpu.lm_head/dot_general"}}
}}

%fused_update (p.5: f32[8]) -> f32[8] {{
  %p.5 = f32[8]{{0}} parameter(0)
  ROOT %add.5 = f32[8]{{0}} add(%p.5, %p.5), metadata={{op_name="{J}/dtpu.optimizer/add"}}
}}

%body (t: (f32[8], f32[8,8])) -> (f32[8], f32[8,8]) {{
  %t = (f32[8]{{0}}, f32[8,8]{{1,0}}) parameter(0)
  %g.0 = f32[8]{{0}} get-tuple-element(%t), index=0
  %g.1 = f32[8,8]{{1,0}} get-tuple-element(%t), index=1
  %fusion.1 = f32[8,8]{{1,0}} fusion(%g.1), kind=kOutput, calls=%fused_proj, metadata={{op_name="{J}/jvp(M)/U0/mul"}}
  %fusion.2 = f32[8]{{0}} fusion(%g.0), kind=kLoop, calls=%fused_norm, metadata={{op_name="{J}/jvp(M)/U0/rsqrt"}}
  %copy.3 = f32[8,8]{{1,0}} copy(%fusion.1)
  %fusion.6 = f32[8,8]{{1,0}} fusion(%copy.3), kind=kOutput, calls=%fused_core, metadata={{op_name="{J}/jvp(M)/U0/dtpu.latent_attn/dtpu.causal_attn/dot_general"}}
  ROOT %tuple.0 = (f32[8]{{0}}, f32[8,8]{{1,0}}) tuple(%fusion.2, %fusion.6)
}}

%cond (t.1: (f32[8], f32[8,8])) -> pred[] {{
  %t.1 = (f32[8]{{0}}, f32[8,8]{{1,0}}) parameter(0)
  ROOT %true = pred[] constant(true)
}}

ENTRY %main (a: f32[8], b: f32[8,8]) -> (f32[8], f32[8,8]) {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %fusion.5 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_update, metadata={{op_name="{J}/dtpu.optimizer/add"}}
  %start = (f32[8]{{0}}, f32[8,8]{{1,0}}) tuple(%fusion.5, %b)
  %while.1 = (f32[8]{{0}}, f32[8,8]{{1,0}}) while(%start), condition=%cond, body=%body, metadata={{op_name="{J}/U0/while"}}
  %g.2 = f32[8,8]{{1,0}} get-tuple-element(%while.1), index=1
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_loss, metadata={{op_name="{J}/jvp(dtpu.loss)/while/body/exp"}}
  ROOT %fusion.7 = f32[8,8]{{1,0}} fusion(%g.2), kind=kOutput, calls=%fused_head, metadata={{op_name="{J}/transpose(jvp(dtpu.loss))/while/body/M.head_logits/dtpu.lm_head/dot_general"}}
}}
"""
US = 1000
#: one step of 100 us: (op, start, end) in us. The loop spans 20 to 60 and runs the projection, the norm, a copy
#: XLA made with no metadata and the causal core inside it; an op the step's text does not hold runs at 80
STEP = [("%fusion.5 = fusion(...)", 0, 10), ("%while.1 = while(...)", 20, 60), ("%fusion.1 = fusion(...)", 22, 32),
        ("%fusion.2 = fusion(...)", 35, 40), ("%copy.3 = copy(...)", 45, 50), ("%fusion.6 = fusion(...)", 50, 58),
        ("%fusion.4 = fusion(...)", 70, 75), ("%fusion.7 = fusion(...)", 75, 79), ("%all-reduce-done.7 = ...", 80, 83)]
# the step's busy time: 10 (update) + 40 (the loop's span) + 5 (loss) + 4 (head) + 3 (absent op) = 62 us; no scope
# places the norm, the copy, the loss's own op (its scope stands inside ``jvp(...)``) or the absent op: 18 us
WHOLE_US, UNPLACED_US = 62, 18


@pytest.fixture()
def made_context(tmp_path):
    (tmp_path / "step.hlo.txt").write_text(MADE_STEP)
    ops, modules = [], []
    for i in range(4):
        t0 = i * 100 * US
        modules.append(("jit_step_scoped(1)", t0, t0 + 100 * US))
        ops += [(name, t0 + start * US, t0 + end * US) for name, start, end in STEP]
    trace = xplane.Trace({"/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: modules}}, [])
    model_scopes.op_name_of.cache_clear()
    yield {"trace": trace, "journal": [{"kind": "run_start", "out_dir": str(tmp_path)}], "window": {"epoch": 2}}
    model_scopes.op_name_of.cache_clear()


def read(name: str, ctx):
    return files.load_module("layer_metrics", name).read(ctx)


def test_the_step_splits_into_the_scopes_and_what_no_scope_places(made_context):
    assert made_context["trace"].step_device_ms() == pytest.approx(WHOLE_US / 1000)
    assert read("mixer_proj_ms", made_context) == pytest.approx(0.010)  # the fusion by the product it holds
    assert read("causal_attn_ms", made_context) == pytest.approx(0.008)
    assert read("latent_attn_ms", made_context) == pytest.approx(0.008)  # the core nests inside latent attention's
    assert read("lm_head_ms", made_context) == pytest.approx(0.004)
    assert read("dense_ffn_ms", made_context) is None  # no such scope in this step
    assert read("step_unplaced_pct", made_context) == pytest.approx(100 * UNPLACED_US / WHOLE_US)


def test_the_scopes_and_the_remainder_cover_the_step_but_the_loops_own_time(made_context):
    """What the scope metrics read, the update and the loss's own op, and the remainder add up to the step less
    the time the loop's span holds between the ops it runs (20 us here, none on the chip's long bodies)."""
    placed_us = 1000 * sum(read(name, made_context) for name in ("mixer_proj_ms", "causal_attn_ms", "lm_head_ms"))
    update_us = 10  # `step_optimizer_ms` reads it from the entry computation
    unplaced_us = WHOLE_US * read("step_unplaced_pct", made_context) / 100
    loop_gaps_us = (60 - 20) - (10 + 5 + 5 + 8)
    assert placed_us + update_us + unplaced_us + loop_gaps_us == pytest.approx(WHOLE_US)


@pytest.mark.parametrize("name", ["causal_attn_ms", "mixer_proj_ms", "dense_ffn_ms", "lm_head_ms", "step_unplaced_pct"])
def test_the_new_readers_find_nothing_and_raise_nothing_without_a_trace_or_a_step(made_context, tmp_path, name):
    assert read(name, dict(made_context, trace=None)) is None
    assert read(name, dict(made_context, journal=[])) is None


@pytest.mark.parametrize("name", ["causal_attn_ms", "mixer_proj_ms", "dense_ffn_ms", "lm_head_ms"])
def test_a_scope_reader_finds_nothing_in_the_parents_step(made_context, tmp_path, name):
    """The parent's step holds none of the four scopes: each reader returns nothing, and the remainder grows."""
    parent = MADE_STEP
    for scope in ("dtpu.causal_attn/", "dtpu.mixer_proj/", "dtpu.lm_head/"):
        parent = parent.replace(scope, "")
    (tmp_path / "step.hlo.txt").write_text(parent)
    model_scopes.op_name_of.cache_clear()
    assert read(name, made_context) is None
    assert read("step_unplaced_pct", made_context) == pytest.approx(100 * (UNPLACED_US + 10 + 4) / WHOLE_US)
