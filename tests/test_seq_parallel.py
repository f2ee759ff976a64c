"""seq mesh axis: sequence-parallel training (ISSUE 15).

Four tiers (docs/PARALLELISM.md "The seq axis"):

- **Partition-rule / mesh units**: `parallel.seq.token_spec` shards the
  token dimension (the SNIPPETS [3] ``"seq"`` TODO answered),
  `data_mesh(..., seq=N)` appends the trailing seq axis, `local_tokens`
  slices evenly or refuses loudly, and the loader topology counts only
  batch-bearing devices.
- **Module oracle**: the sequence-parallel ViT classifier (gap pooling +
  the bias-1/P partial-logits head) matches the dense model's logits AND
  gradients — including the `psum_partial` transpose (a plain psum here
  scales every grad by the axis size; regression-pinned).
- **Trainer oracle**: 24 steps of the MAE config at data2×seq2 (ring; one
  epoch of Ulysses) replay the seq=1 reference's loss stream and final
  params allclose — same data topology, so the per-shard mask RNG streams
  agree. The journaled ``activation_bytes`` census shows the measured
  1/seq; steady-state steps compile exactly zero new programs.
- **Elastic round-trip** (slow tier + the CI seq-smoke job, like the fsdp
  composition run): a run preempted at seq=2 resumes at seq=1 and seq=2
  through the existing target-sharding restore (state is seq-replicated,
  so PR 4's machinery makes this free — proven, not assumed).
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distribuuuu_tpu import checkpoint as ckpt
from distribuuuu_tpu import config, obs, resilience, trainer
from distribuuuu_tpu.models import list_models, register_model
from distribuuuu_tpu.models.mae import MAEViT, patchify
from distribuuuu_tpu.models.vit import ViT
from distribuuuu_tpu.parallel import seq as seqpar
from distribuuuu_tpu.runtime import create_mesh
from distribuuuu_tpu.runtime.mesh import data_mesh

if "mae_tiny" not in list_models():
    # the shipped MAEViT class at test size — the trainer path under test is
    # exactly what config/mae_vit_b16.yaml ships, minus the parameter count
    @register_model("mae_tiny")
    def mae_tiny(num_classes=0, dtype=jnp.float32, bn_axis_name=None, remat=False,
                 seq_axis=None, seq_impl="ring", decoder_dim=16):
        return MAEViT(
            patch=4, dim=16, depth=2, num_heads=2, mlp_dim=32,
            decoder_dim=decoder_dim, dtype=jnp.float32, remat=remat,
            seq_axis=seq_axis, seq_impl=seq_impl,
        )


_GLOBAL_BATCH = 8  # held fixed across topologies: same sample stream
_EPOCH_SAMPLES = 64  # -> 8 optimizer steps/epoch at every topology


def _seq_cfg(c, out_dir, data: int, seq_n: int, impl: str = "ring",
             max_epoch: int = 3):
    c.MODEL.ARCH = "mae_tiny"
    c.MODEL.DTYPE = "float32"
    c.MODEL.DUMMY_INPUT = True
    c.MODEL.SEQ_ATTN = impl if seq_n > 1 else "none"
    c.MODEL.MAE_DECODER_DIM = 16
    c.TRAIN.TASK = "mae"
    c.MESH.DATA = data
    c.MESH.SEQ = seq_n
    # global batch is carried by the data axis only — seq devices cooperate
    c.TRAIN.BATCH_SIZE = _GLOBAL_BATCH // data
    c.TRAIN.IM_SIZE = 16  # 4x4 patches -> L=16 tokens
    c.TEST.IM_SIZE = 16
    c.TEST.CROP_SIZE = 16
    c.TEST.BATCH_SIZE = _GLOBAL_BATCH // data
    c.TRAIN.DUMMY_EPOCH_SAMPLES = _EPOCH_SAMPLES
    c.TRAIN.PRINT_FREQ = 1
    c.OPTIM.MAX_EPOCH = max_epoch
    c.OPTIM.WARMUP_EPOCHS = 0
    c.OPTIM.BASE_LR = 0.01
    c.RNG_SEED = 7
    c.FAULT.HANDLE_SIGNALS = False
    c.OUT_DIR = str(out_dir)
    return c


def _param_leaves(state):
    return [np.array(x) for x in jax.tree.leaves(jax.device_get(state.params))]


def _window_losses(out_dir) -> dict[int, float]:
    losses: dict[int, float] = {}
    for rec in obs.read_journal(os.path.join(str(out_dir), "telemetry.jsonl")):
        if rec.get("kind") == "window" and rec.get("loss") is not None:
            assert rec["gstep"] not in losses
            losses[rec["gstep"]] = rec["loss"]
    return losses


def _activation_record(out_dir) -> dict:
    recs = [
        r
        for r in obs.read_journal(os.path.join(str(out_dir), "telemetry.jsonl"))
        if r.get("kind") == "activation_bytes"
    ]
    assert recs, "no activation_bytes record journaled"
    return recs[-1]


@pytest.fixture(autouse=True)
def _reset_resilience():
    resilience.reset_run_stats()
    resilience.clear_preemption()
    yield
    resilience.clear_preemption()
    resilience.uninstall_preemption_handler()


# ---------------------------------------------------------------------------
# Partition-rule / mesh units
# ---------------------------------------------------------------------------

def test_token_spec_rules():
    # the [B, L, D] token stream under data×fsdp×seq
    assert seqpar.token_spec(3, batch_axes=("data", "fsdp")) == P(
        ("data", "fsdp"), "seq", None
    )
    # [B, H, L, D] attention heads: token dim 2
    assert seqpar.token_spec(4, token_dim=2) == P(None, None, "seq", None)
    assert seqpar.token_spec(2) == P(None, "seq")
    with pytest.raises(ValueError, match="out of range"):
        seqpar.token_spec(2, token_dim=2)
    with pytest.raises(ValueError, match="batch axes"):
        seqpar.token_spec(2, token_dim=0, batch_axes="data")


def test_data_mesh_seq_axis():
    mesh = data_mesh(2, 1, 2)
    assert mesh.axis_names == ("data", "seq")
    assert dict(mesh.shape) == {"data": 2, "seq": 2}
    assert seqpar.seq_size(mesh) == 2
    assert seqpar.batch_device_count(mesh) == 2
    mesh3 = data_mesh(2, 2, 2)
    assert mesh3.axis_names == ("data", "fsdp", "seq")
    assert dict(mesh3.shape) == {"data": 2, "fsdp": 2, "seq": 2}
    assert seqpar.batch_device_count(mesh3) == 4
    # seq-less meshes are untouched (bit-for-bit the original contract)
    assert data_mesh(2).axis_names == ("data",)
    assert seqpar.seq_size(data_mesh(2)) == 1
    with pytest.raises(ValueError, match="wildcard"):
        data_mesh(2, 1, -1)


def test_loader_topology_counts_batch_devices_only():
    from distribuuuu_tpu.data.loader import _topology

    _, _, local, global_ = _topology(data_mesh(2, 1, 2))
    assert (local, global_) == (2, 2)
    _, _, local, global_ = _topology(data_mesh(4))
    assert (local, global_) == (4, 4)


def test_local_tokens_slices_and_refuses_indivisible():
    mesh = create_mesh({"seq": 4}, devices=jax.devices()[:4])
    x = jnp.arange(16.0).reshape(1, 16, 1)

    def f(t):
        return seqpar.local_tokens(t)

    out = jax.shard_map(
        f, mesh=mesh, in_specs=(P(),), out_specs=P(None, "seq", None),
        check_vma=False,
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    bad = jnp.zeros((1, 15, 1))
    with pytest.raises(ValueError, match="not divisible"):
        jax.shard_map(
            f, mesh=mesh, in_specs=(P(),), out_specs=P(None, "seq", None),
            check_vma=False,
        )(bad)


def test_seq_attention_dispatch_validates_impl():
    with pytest.raises(ValueError, match="ring.*ulysses"):
        jax.shard_map(
            lambda q: seqpar.seq_attention(q, q, q, impl="dense"),
            mesh=create_mesh({"seq": 2}, devices=jax.devices()[:2]),
            in_specs=(P(None, None, "seq", None),),
            out_specs=P(None, None, "seq", None),
            check_vma=False,
        )(jnp.zeros((1, 2, 4, 4)))


# ---------------------------------------------------------------------------
# Module oracle: seq ViT classifier == dense (fwd + grads)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl,p", [("ring", 4), ("ulysses", 2)])
def test_vit_classifier_seq_matches_dense(impl, p):
    """Logits AND psum'd grads of the sequence-parallel classifier equal the
    dense model's — the bias-1/P head plus psum_partial make every member
    grad an exact partial (a plain lax.psum in either place scales grads by
    the axis size; that regression is pinned below)."""
    B, IM = 2, 16
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((B, IM, IM, 3)), jnp.float32)
    labels = jnp.asarray([1, 3])
    kw = dict(patch=4, dim=16, depth=2, num_heads=2, mlp_dim=32, num_classes=5,
              pool="gap", dtype=jnp.float32)
    dense = ViT(**kw)
    params = dense.init(jax.random.PRNGKey(0), x, train=False)["params"]
    # head kernel is zeros-init; perturb so head grads are non-trivial
    prng = np.random.default_rng(2)
    params = jax.tree.map(
        lambda a: a + 0.01 * prng.standard_normal(a.shape).astype(a.dtype), params
    )

    def ce(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(B), labels])

    seqm = ViT(**kw, seq_axis="seq", seq_impl=impl)
    mesh = create_mesh({"seq": p}, devices=jax.devices()[:p])

    def member(prms):
        logits = seqm.apply({"params": prms}, x, train=False)
        g = jax.grad(lambda q: ce(seqm.apply({"params": q}, x, train=False)))(prms)
        return logits, jax.lax.psum(g, "seq")

    logits, g_seq = jax.shard_map(
        member, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()), check_vma=False
    )(params)
    np.testing.assert_allclose(
        np.array(logits), np.array(dense.apply({"params": params}, x, train=False)),
        rtol=1e-5, atol=1e-5,
    )
    g_dense = jax.grad(lambda q: ce(dense.apply({"params": q}, x, train=False)))(params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_dense), jax.tree.leaves(g_seq)
    ):
        np.testing.assert_allclose(
            np.array(a), np.array(b), rtol=2e-4, atol=1e-6,
            err_msg=f"{impl} {jax.tree_util.keystr(path)}",
        )


def test_psum_partial_identity_transpose():
    """grad through psum_partial is 1 per member; through plain psum it is
    the axis size (the unchecked-mode transpose double count the seq loss
    reductions exist to avoid — this is the regression pin)."""
    mesh = create_mesh({"seq": 4}, devices=jax.devices()[:4])

    def g_of(reduction):
        def member(x):
            return jax.grad(lambda t: reduction(t * t))(x)

        return jax.shard_map(
            member, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
        )(jnp.float32(3.0))

    assert float(g_of(lambda s: seqpar.psum_partial(s, "seq"))) == 6.0
    assert float(g_of(lambda s: jax.lax.psum(s, "seq"))) == 24.0  # 4x: why not psum


def test_vit_seq_requires_gap_pool():
    m = ViT(patch=4, dim=16, depth=1, num_heads=2, mlp_dim=32, num_classes=4,
            pool="token", dtype=jnp.float32, seq_axis="seq")
    with pytest.raises(ValueError, match="gap"):
        m.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)), train=False)


def test_mae_masking_and_patchify_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 8, 3)), jnp.float32)
    t = patchify(x, 4)
    assert t.shape == (2, 4, 48)
    # token order matches the patch conv's row-major grid
    np.testing.assert_allclose(
        np.array(t[0, 0]), np.array(x[0, :4, :4, :].reshape(-1)), rtol=1e-6
    )
    model = MAEViT(patch=4, dim=16, depth=1, num_heads=2, mlp_dim=32,
                   decoder_dim=16, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))["params"]
    assert params["mask_token"].shape == (1, 1, 16)
    mask = jnp.zeros((2, 4), bool).at[:, 1].set(True)
    pred = model.apply({"params": params}, x, mask=mask)
    assert pred.shape == (2, 4, 48) and pred.dtype == jnp.float32
    # masked tokens actually see the mask token: prediction differs from the
    # unmasked forward at the masked position
    pred_unmasked = model.apply({"params": params}, x)
    assert float(jnp.max(jnp.abs(pred[:, 1] - pred_unmasked[:, 1]))) > 0


# ---------------------------------------------------------------------------
# Trainer oracle: seq=2 (ring/ulysses, data2xseq2) vs replicated reference
# ---------------------------------------------------------------------------

def _run(out_dir, data, seq_n, impl="ring", max_epoch=3):
    config.reset_cfg()
    _seq_cfg(config.cfg, out_dir, data=data, seq_n=seq_n, impl=impl,
             max_epoch=max_epoch)
    return trainer.train_model()


def test_seq_matches_replicated_oracle(fresh_cfg, tmp_path):
    """24 steps of the MAE config under data×seq replay the seq-less loss
    stream and land on the same params (the acceptance-criteria oracle).
    Comparisons hold the DATA topology fixed: the per-shard mask RNG fold
    (shared within a seq group, like fsdp's linearized fold) makes the mask
    stream a function of the data axis only."""
    total_steps = 3 * (_EPOCH_SAMPLES // _GLOBAL_BATCH)  # 24 >= 20
    state_ref, _ = _run(tmp_path / "dp", data=2, seq_n=1)
    losses_ref = _window_losses(tmp_path / "dp")
    assert sorted(losses_ref) == list(range(total_steps))
    ref_vec = np.array([losses_ref[g] for g in range(total_steps)])
    assert np.all(ref_vec[:20] > 0), "loss collapsed; stream comparison vacuous"
    leaves_ref = _param_leaves(state_ref)

    # ring: the full 24-step acceptance run; ulysses: one epoch (its full
    # fwd+grad equality is already pinned at module level above and in
    # tests/test_ulysses.py — this arm proves the trainer wiring)
    for data, seq_n, impl, epochs, out in (
        (2, 2, "ring", 3, "seq2ring"),
        (2, 2, "ulysses", 1, "seq2ulysses"),
    ):
        state_s, _ = _run(tmp_path / out, data=data, seq_n=seq_n, impl=impl,
                          max_epoch=epochs)
        losses_s = _window_losses(tmp_path / out)
        steps = epochs * (_EPOCH_SAMPLES // _GLOBAL_BATCH)
        assert sorted(losses_s) == list(range(steps)), out
        s_vec = np.array([losses_s[g] for g in range(steps)])
        np.testing.assert_allclose(ref_vec[:steps], s_vec, rtol=1e-3, atol=1e-5,
                                   err_msg=out)
        if epochs == 3:
            for a, b in zip(leaves_ref, _param_leaves(state_s)):
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5, err_msg=out)

    # the measured 1/seq claim: journaled per-device activation bytes halve
    rep = _activation_record(tmp_path / "dp")
    shard = _activation_record(tmp_path / "seq2ring")
    assert rep["seq"] == 1 and shard["seq"] == 2
    assert shard["l_local"] * 2 == rep["l_global"] == shard["l_global"]
    assert shard["token_bytes"] * 2 <= rep["token_bytes"]
    assert shard["token_global_bytes"] == rep["token_bytes"]


@pytest.mark.slow
def test_seq_composes_with_fsdp(fresh_cfg, tmp_path):
    """data1×fsdp2×seq2: the 3-D mesh trains and replays the data1×fsdp2
    stream — seq composes with the state-sharding axis, and the state_bytes
    + activation_bytes records each show their own 1/N."""
    total_steps = _EPOCH_SAMPLES // _GLOBAL_BATCH  # 8

    def run(out, seq_n, impl):
        config.reset_cfg()
        c = _seq_cfg(config.cfg, tmp_path / out, data=1, seq_n=seq_n, impl=impl,
                     max_epoch=1)
        c.MESH.FSDP = 2
        c.MESH.FSDP_MIN_SIZE = 1
        # the fsdp axis carries batch too: global batch = data × fsdp × BS
        c.TRAIN.BATCH_SIZE = _GLOBAL_BATCH // 2
        c.TEST.BATCH_SIZE = _GLOBAL_BATCH // 2
        state, _ = trainer.train_model()
        return state, _window_losses(tmp_path / out)

    state_ref, losses_ref = run("fsdp2", 1, "ring")
    state_s, losses_s = run("fsdp2seq2", 2, "ring")
    ref_vec = np.array([losses_ref[g] for g in range(total_steps)])
    s_vec = np.array([losses_s[g] for g in range(total_steps)])
    np.testing.assert_allclose(ref_vec, s_vec, rtol=1e-3, atol=1e-5)
    for a, b in zip(_param_leaves(state_ref), _param_leaves(state_s)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
    assert _activation_record(tmp_path / "fsdp2seq2")["seq"] == 2


def test_seq_zero_steady_state_compiles(fresh_cfg, tmp_path):
    """After the first step compiles, further seq-sharded steps compile
    exactly zero new programs (CompileGuard exact=0 — static shapes, ring
    hops included)."""
    from jax.sharding import NamedSharding

    from distribuuuu_tpu.analysis.guards import CompileGuard

    _seq_cfg(fresh_cfg, tmp_path, data=2, seq_n=2, impl="ring")
    mesh = data_mesh(2, 1, 2)
    model = trainer._build_cfg_model()
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, 16)
    step = trainer.make_train_step(model, tx, mesh, topk=5, task="mae")
    rng = np.random.default_rng(0)
    rows = NamedSharding(mesh, P("data"))
    batch = {
        "image": jax.device_put(
            rng.integers(0, 256, (_GLOBAL_BATCH, 16, 16, 3), dtype=np.uint8), rows),
        "label": jax.device_put(rng.integers(0, 1000, _GLOBAL_BATCH).astype(np.int32), rows),
        "weight": jax.device_put(np.ones((_GLOBAL_BATCH,), np.float32), rows),
    }
    lr = jnp.asarray(0.01, jnp.float32)
    key = jax.random.PRNGKey(1)
    state, m = step(state, batch, lr, key)
    jax.device_get(m)
    with CompileGuard(exact=0):
        for _ in range(3):
            state, m = step(state, batch, lr, key)
        jax.device_get(m)


def test_train_step_rejects_unknown_task(fresh_cfg, tmp_path):
    _seq_cfg(fresh_cfg, tmp_path, data=2, seq_n=1)
    mesh = data_mesh(2)
    model = trainer._build_cfg_model()
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, 16)
    with pytest.raises(ValueError, match="TRAIN.TASK"):
        trainer.make_train_step(model, tx, mesh, topk=5, task="segment")


def test_build_rejects_task_arch_mismatch(fresh_cfg, tmp_path):
    """Both holes in the task×arch matrix refuse at build time: an MAE arch
    under the default classify task (pixel output into softmax-CE), and the
    mae task on a logits arch."""
    c = _seq_cfg(fresh_cfg, tmp_path, data=2, seq_n=1)
    c.TRAIN.TASK = "classify"
    with pytest.raises(ValueError, match="pixel"):
        trainer._build_cfg_model()
    c.TRAIN.TASK = "mae"
    c.MODEL.ARCH = "vit_s16"
    with pytest.raises(ValueError, match="mae_"):
        trainer._build_cfg_model()


def test_build_rejects_seq_without_attn_impl(fresh_cfg, tmp_path):
    c = _seq_cfg(fresh_cfg, tmp_path, data=2, seq_n=2)
    c.MODEL.SEQ_ATTN = "none"
    with pytest.raises(ValueError, match="SEQ_ATTN"):
        trainer._build_cfg_model()


def test_build_rejects_bn_model_on_seq_mesh(fresh_cfg, tmp_path):
    c = _seq_cfg(fresh_cfg, tmp_path, data=2, seq_n=2)
    c.MODEL.ARCH = "resnet18"
    c.TRAIN.TASK = "classify"
    with pytest.raises((ValueError, TypeError)):
        # resnet factories don't take seq kwargs (and carry batch_stats):
        # either refusal is loud at build time
        config.cfg.OUT_DIR = str(tmp_path / "bn")
        trainer.train_model()


# ---------------------------------------------------------------------------
# Elastic round-trip: save at seq=2, resume at seq=1 / 2
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.faultinject
def test_seq_elastic_roundtrip(fresh_cfg, tmp_path):
    """Preempt a seq=2 run mid-epoch; resume at seq=2 (bitwise) and seq=1
    (allclose — same data topology, so the sample and mask streams replay).
    State is seq-replicated, so the target-sharding restore makes the
    cross-seq resume free — this proves it."""
    total_steps = 3 * (_EPOCH_SAMPLES // _GLOBAL_BATCH)  # 24

    # Phase A: uninterrupted seq=2 reference
    _seq_cfg(fresh_cfg, tmp_path / "a", data=2, seq_n=2)
    state_a, best_a = trainer.train_model()
    leaves_a = _param_leaves(state_a)
    losses_a = _window_losses(tmp_path / "a")
    assert sorted(losses_a) == list(range(total_steps))

    # Phase B: identical run preempted at global step 11 (epoch 1, step 3)
    config.reset_cfg()
    c = _seq_cfg(config.cfg, tmp_path / "b2", data=2, seq_n=2)
    c.FAULT.INJECT_PREEMPT_STEP = 11
    with pytest.raises(SystemExit) as ei:
        trainer.train_model()
    assert ei.value.code == 143
    mids = ckpt._mid_checkpoints(str(tmp_path / "b2"))
    assert [(e, s) for e, s, _ in mids] == [(1, 3)]
    assert ckpt.verify_checkpoint(mids[0][2])[0] == "ok"
    shutil.copytree(tmp_path / "b2", tmp_path / "b1")

    for data, seq_n, out in ((2, 2, "b2"), (2, 1, "b1")):
        config.reset_cfg()
        _seq_cfg(config.cfg, tmp_path / out, data=data, seq_n=seq_n)
        state_r, best_r = trainer.train_model()
        losses_r = _window_losses(tmp_path / out)
        assert sorted(losses_r) == list(range(total_steps)), (
            f"seq={seq_n}: step stream mismatch"
        )
        loss_vec_a = np.array([losses_a[g] for g in range(total_steps)])
        loss_vec_r = np.array([losses_r[g] for g in range(total_steps)])
        leaves_r = _param_leaves(state_r)
        if seq_n == 2:
            np.testing.assert_array_equal(loss_vec_a, loss_vec_r)
            for a, b in zip(leaves_a, leaves_r):
                np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(loss_vec_a, loss_vec_r, rtol=1e-3, atol=1e-5)
            for a, b in zip(leaves_a, leaves_r):
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
        assert _activation_record(tmp_path / out)["seq"] == seq_n
