"""Where a kernel route is chosen, and the one peak the program holds.

A route is chosen in the module that owns the kernel, from an explicit
argument or from what the code can observe: there is no registry, no
environment variable and no precedence chain.

- `switch_epilogue`: the whole truth table of the rule that remains (an
  explicit argument, else the run's ``MODEL.FUSED_EPILOGUE``);
- the retired ``DTPU_FUSED_*`` variables reach no trace;
- the retired names are in no source file or document;
- `obs/flops.peak_flops_per_device` is the published table, nothing else.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distribuuuu_tpu.obs import flops as obs_flops
from distribuuuu_tpu.ops.epilogue import (
    get_fused_epilogue_default,
    set_fused_epilogue_default,
    switch_epilogue,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("run_default", [False, True])
@pytest.mark.parametrize("fused", [None, True, False])
def test_switch_epilogue_rule(fused, run_default):
    prev = get_fused_epilogue_default()
    set_fused_epilogue_default(run_default)
    try:
        assert switch_epilogue(fused) is (run_default if fused is None else fused)
    finally:
        set_fused_epilogue_default(prev)


def _resnet_block_jaxpr():
    from distribuuuu_tpu.models.resnet import BasicBlock

    block = BasicBlock(planes=8, dtype=jnp.float32)
    x = jnp.zeros((1, 4, 4, 8), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x, train=False)
    return jax.make_jaxpr(lambda v, x_: block.apply(v, x_, train=False))(variables, x)


def _mhsa_jaxpr():
    from distribuuuu_tpu.models.botnet import MHSA

    mhsa = MHSA(fmap_size=(4, 4), heads=2, dim_qk=8, dim_v=8, dtype=jnp.float32)
    x = jnp.zeros((1, 4, 4, 16), jnp.float32)
    variables = mhsa.init(jax.random.PRNGKey(0), x)
    return jax.make_jaxpr(mhsa.apply)(variables, x)


def _switch_moe_jaxpr():
    from distribuuuu_tpu.parallel import switch_moe
    from distribuuuu_tpu.runtime import create_mesh

    e, d = 8, 8
    mesh = create_mesh({"expert": e})

    def body(gate, w, x_local):
        out, _ = switch_moe(
            x_local[0], gate, w[0], lambda p, t: t @ p, capacity=2, axis_name="expert"
        )
        return out[None]

    routed = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("expert"), P("expert")), out_specs=P("expert"),
        check_vma=False,
    )
    return jax.make_jaxpr(routed)(
        jnp.ones((d, e), jnp.float32), jnp.ones((e, d, d), jnp.float32),
        jnp.ones((e, 2, d), jnp.float32),
    )


@pytest.mark.parametrize(
    "var, trace",
    [
        pytest.param("DTPU_FUSED_EPILOGUE", _resnet_block_jaxpr, id="DTPU_FUSED_EPILOGUE"),
        pytest.param("DTPU_FUSED_ATTN", _mhsa_jaxpr, id="DTPU_FUSED_ATTN"),
        pytest.param("DTPU_FUSED_MOE", _switch_moe_jaxpr, id="DTPU_FUSED_MOE"),
    ],
)
def test_retired_env_vars_do_not_route(var, trace, monkeypatch):
    monkeypatch.delenv(var, raising=False)
    want = str(trace())
    assert "pallas_call" not in want
    monkeypatch.setenv(var, "1")
    assert str(trace()) == want


_SEARCHED = (
    "distribuuuu_tpu", "scripts", "config", "docs", "tutorial", ".github",
    "README.md", "chip_smoke.py", "train_net.py", "test_net.py",
)


@functools.lru_cache(maxsize=None)
def _searched_texts() -> dict:
    paths = []
    for entry in _SEARCHED:
        top = os.path.join(REPO, entry)
        if os.path.isfile(top):
            paths.append(top)
        for root, dirs, files in os.walk(top):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            paths += [os.path.join(root, f) for f in files if not f.endswith(".pyc")]
    texts = {}
    for path in paths:
        with open(path, encoding="utf-8", errors="ignore") as f:
            texts[os.path.relpath(path, REPO)] = f.read()
    return texts


@pytest.mark.parametrize(
    "name",
    [
        "DTPU_FUSED_EPILOGUE", "DTPU_FUSED_ATTN", "DTPU_FUSED_MOE", "DTPU_PERFDB",
        "DTPU_BENCH_", "FUSED_MOE", "OBS.PERFDB", "perfdb",
    ],
)
def test_retired_names_are_gone(name):
    texts = _searched_texts()
    assert len(texts) > 100  # the walk found the sources and the documents
    assert [path for path, text in texts.items() if name in text] == []


# Peak dense bf16 TFLOP/s per JAX device as Google Cloud's TPU system
# specifications publish them (a device is a core on v2/v3, a chip from v4 on).
_PUBLISHED_TFLOPS = {
    "tpu v2": 22.5,
    "tpu v3": 61.5,
    "tpu v4": 275.0,
    "tpu v5 lite": 197.0,
    "tpu v5e": 197.0,
    "tpu v5": 459.0,
    "tpu v5p": 459.0,
    "tpu v6 lite": 918.0,
    "tpu v6e": 918.0,
}


@pytest.mark.parametrize("kind", sorted(_PUBLISHED_TFLOPS))
def test_peak_flops_static_table(kind):
    class _Dev:
        device_kind = kind.replace("tpu", "TPU")  # as the runtime spells it

    assert set(_PUBLISHED_TFLOPS) == set(obs_flops._PEAK_BF16_TFLOPS)
    assert obs_flops.peak_flops_per_device(_Dev()) == pytest.approx(_PUBLISHED_TFLOPS[kind] * 1e12)
