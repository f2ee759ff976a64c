"""Checkpointing with the reference's directory/naming/auto-resume contract.

Contract replicated from `/root/reference/distribuuuu/utils.py:319-410`:

- per-epoch checkpoints under ``OUT_DIR/checkpoints/`` named ``ckpt_ep_{E:03d}``
  (Orbax directories instead of ``.pth.tar`` files); after finishing 0-based
  epoch ``E`` the file is named ``E+1`` while the payload records ``E``,
  exactly like the reference (`utils.py:374-384`: ``get_checkpoint(epoch + 1)``
  with ``{"epoch": epoch}``) — so the first checkpoint is ``ckpt_ep_001``
- saved payload: epoch, model state (params + batch_stats — already "unwrapped";
  there is no DDP wrapper to strip in SPMD), optimizer state, best_acc1
- ``best`` holds weights-only state on Acc@1 improvement (`utils.py:386-387`)
- auto-resume picks the highest-numbered checkpoint (`utils.py:337-342`)
- loading a weights-only checkpoint for eval works (`utils.py:406-410`)

Writes go through Orbax **async** checkpointing (SURVEY §5/§7): ``save``
snapshots the arrays then returns, the serialize+commit runs on a background
thread, so the mesh never stalls at an epoch boundary waiting on disk. At
most one save per target is in flight (the next save waits for the previous),
and `wait_for_saves()` blocks until everything is durable — the trainer calls
it before exiting. Multi-host aware: every process calls save, Orbax
coordinates so the write happens once — the analog of the reference's
rank-0-only save gate at `utils.py:369-370`.

Fault-tolerance extensions (docs/FAULT_TOLERANCE.md): mid-epoch *emergency*
checkpoints (``ckpt_mid_ep_{E:03d}_it_{S:06d}``, written on preemption and
pruned once a durable epoch checkpoint dominates them), `restore_latest`
(resume-position ranking across both kinds, with corrupt-checkpoint
fallback), and retry-with-backoff around the Orbax save/restore dispatch.

Elastic & integrity extensions (this layer's distributed-failure story):

- **Elastic restore**: restores are *target-sharding-driven* — every leaf is
  restored with explicit ``ArrayRestoreArgs(sharding=...)`` taken from the
  caller's state templates, so a run saved on an N-device mesh restores onto
  an M-device mesh (Orbax's default resurrects the SAVED mesh from the
  ``_sharding`` file, which breaks the moment the topology changes).
  Checkpoint payloads record the saving topology (``devices``) and, for
  mid-epoch checkpoints, the fleet-wide ``global_samples`` consumed in the
  in-progress epoch plus the ``samples_per_step`` they were consumed at —
  `load_mid_checkpoint` remaps the resume step from the sample offset so a
  2→4 device resume consumes the exact same sample stream.
- **Integrity manifests**: after each save commits, a per-file sha256
  manifest (``dtpu_manifest.json``, covering every serialized array shard)
  is written into the checkpoint directory on a background thread and
  journaled via `obs`. `verify_checkpoint` re-hashes at restore time; a
  failed verify QUARANTINES the directory (rename to ``corrupt_*``, typed
  ``ckpt_quarantined`` journal event) and `restore_latest` falls back to the
  next-oldest candidate. ``python -m distribuuuu_tpu.checkpoint verify
  <dir>`` runs the same check offline.
- **Prune/restore race guard**: the checkpoint a restore has selected is
  registered in-flight and `prune_mid_checkpoints` will not delete it out
  from under the restore.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import re
import threading
import time
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

from distribuuuu_tpu import obs, resilience
from distribuuuu_tpu.logging import logger
from distribuuuu_tpu.runtime import pathio

_NAME_PREFIX = "ckpt_ep_"
_DIR_NAME = "checkpoints"
_BEST_NAME = "best"
_MID_FMT = "ckpt_mid_ep_{epoch:03d}_it_{step:06d}"
_MANIFEST_NAME = "dtpu_manifest.json"
_CORRUPT_PREFIX = "corrupt_"


class ElasticResumeError(RuntimeError):
    """A mid-epoch checkpoint's sample offset cannot be represented on the
    new topology (offset not divisible by the new fleet samples-per-step).
    `restore_latest` skips the checkpoint and falls back — epoch-boundary
    checkpoints are always topology-safe (offset 0)."""


def get_checkpoint_dir(out_dir: str) -> str:
    # Orbax refuses a relative directory ("Checkpoint path should be
    # absolute"), and every shipped config has one (OUT_DIR: ./resnet50)
    return pathio.join(pathio.absolute(out_dir), _DIR_NAME)


def get_checkpoint_path(out_dir: str, epoch: int) -> str:
    return pathio.join(get_checkpoint_dir(out_dir), f"{_NAME_PREFIX}{epoch:03d}")


def get_best_path(out_dir: str) -> str:
    return pathio.join(get_checkpoint_dir(out_dir), _BEST_NAME)


# Exact-name match so Orbax in-progress temp dirs
# (ckpt_ep_XXX.orbax-checkpoint-tmp-<ts>, left behind by a killed run) are
# never mistaken for complete checkpoints during auto-resume.
_CKPT_RE = re.compile(rf"^{_NAME_PREFIX}(\d+)$")
_MID_RE = re.compile(r"^ckpt_mid_ep_(\d+)_it_(\d+)$")


def get_mid_checkpoint_path(out_dir: str, epoch: int, step: int) -> str:
    """Path of a mid-epoch emergency checkpoint (preemption save)."""
    return pathio.join(get_checkpoint_dir(out_dir), _MID_FMT.format(epoch=epoch, step=step))


def _scan_epoch_dirs(d: str) -> list[tuple[int, str]]:
    # pathio, not os: OUT_DIR is commonly gs:// on a pod, and auto-resume
    # must scan it the same way Orbax wrote it (reference parity:
    # `utils.py:340` does this through g_pathmgr.ls for the same reason).
    if not pathio.isdir(d):
        return []
    out = []
    for f in pathio.listdir(d):
        m = _CKPT_RE.match(f)
        if m:
            out.append((int(m.group(1)), pathio.join(d, f)))
    return sorted(out)


def _scan_mid_dirs(d: str) -> list[tuple[int, int, str]]:
    """Committed mid-epoch emergency checkpoints as (epoch, step, path),
    sorted ascending. Same exact-name match as the epoch scan, so Orbax
    in-progress temp dirs never count."""
    if not pathio.isdir(d):
        return []
    out = []
    for f in pathio.listdir(d):
        m = _MID_RE.match(f)
        if m:
            out.append((int(m.group(1)), int(m.group(2)), pathio.join(d, f)))
    return sorted(out)


def _ranked_candidates(
    epochs: list[tuple[int, str]], mids: list[tuple[int, int, str]]
) -> list[tuple[tuple[int, int, int], str, str]]:
    """The ONE ranking of checkpoint candidates, most-advanced first:
    position ``(epoch, step, tiebreak)`` with a complete epoch checkpoint
    outranking an emergency one at the same position. Shared by
    `resume_candidates` (auto-resume) and `watch_candidates` (the serving
    deploy watcher) so "newer" can never mean two different things."""
    candidates: list[tuple[tuple[int, int, int], str, str]] = [
        ((n, 0, 1), "epoch", p) for n, p in epochs
    ]
    candidates += [((e, s, 0), "mid", p) for e, s, p in mids]
    candidates.sort(key=lambda c: c[0], reverse=True)
    return candidates


def _complete_checkpoints(out_dir: str) -> list[tuple[int, str]]:
    return _scan_epoch_dirs(get_checkpoint_dir(out_dir))


def _mid_checkpoints(out_dir: str) -> list[tuple[int, int, str]]:
    return _scan_mid_dirs(get_checkpoint_dir(out_dir))


def has_checkpoint(out_dir: str) -> bool:
    return bool(_complete_checkpoints(out_dir))


def get_last_checkpoint(out_dir: str) -> str:
    """Highest-numbered checkpoint path (reference `utils.py:337-342`)."""
    ckpts = _complete_checkpoints(out_dir)
    if not ckpts:
        raise FileNotFoundError(f"No checkpoints in {get_checkpoint_dir(out_dir)}")
    return ckpts[-1][1]


# ---------------------------------------------------------------------------
# Integrity manifests (per-file checksums over the serialized checkpoint)
# ---------------------------------------------------------------------------

def manifest_path(ckpt_path: str) -> str:
    return pathio.join(ckpt_path, _MANIFEST_NAME)


def _hash_file(path: str) -> tuple[int, str]:
    # streamed, not slurped: OCDBT data shards are multi-GB on real runs and
    # this runs on a background thread beside training (host RAM is shared
    # with the input pipeline's prefetch buffers)
    h = hashlib.sha256()
    n = 0
    with pathio.open_bytes(path) as f:
        while True:
            chunk = f.read(4 * 1024 * 1024)
            if not chunk:
                break
            h.update(chunk)
            n += len(chunk)
    return n, h.hexdigest()


def write_manifest(ckpt_path: str) -> dict:
    """Hash every file of a committed checkpoint directory into
    ``dtpu_manifest.json`` (excluding the manifest itself). Returns the
    manifest dict. The entries are per *file*, which covers every serialized
    array shard (OCDBT data files, metadata, sharding descriptors) — a
    byte-flip anywhere in the directory fails the verify."""
    tic = time.time()
    files: dict[str, dict] = {}
    total = 0
    for rel in pathio.walk_files(ckpt_path):
        if rel == _MANIFEST_NAME or rel.endswith(f"/{_MANIFEST_NAME}"):
            continue
        n, digest = _hash_file(pathio.join(ckpt_path, rel))
        files[rel] = {"bytes": n, "sha256": digest}
        total += n
    manifest = {"version": 1, "algo": "sha256", "files": files}
    pathio.write_text(manifest_path(ckpt_path), json.dumps(manifest, sort_keys=True))
    obs.current().event(
        "manifest", path=str(ckpt_path), files=len(files), bytes=total,
        wall_s=round(time.time() - tic, 4),
    )
    return manifest


def verify_checkpoint(ckpt_path: str) -> tuple[str, list[str]]:
    """Re-hash a checkpoint directory against its manifest.

    Returns ``(status, errors)`` with status ``"ok"`` (manifest present,
    every file matches), ``"unverified"`` (no manifest — pre-manifest
    checkpoint or the async manifest write hasn't landed yet; NOT an error)
    or ``"corrupt"`` (manifest present but unreadable, a file is missing,
    sized differently, or hashes differently; ``errors`` says which).
    Extra files beyond the manifest are tolerated: Orbax may add metadata
    across versions, and an addition cannot corrupt restored bytes.
    """
    mpath = manifest_path(ckpt_path)
    if not pathio.exists(mpath):
        return "unverified", []
    try:
        manifest = json.loads(pathio.read_bytes(mpath).decode("utf-8"))
        entries = manifest["files"]
    except Exception as exc:
        return "corrupt", [f"unreadable manifest: {exc!r}"]
    errors: list[str] = []
    for rel, want in sorted(entries.items()):
        fpath = pathio.join(ckpt_path, rel)
        if not pathio.exists(fpath):
            errors.append(f"{rel}: missing")
            continue
        try:
            n, digest = _hash_file(fpath)
        except OSError as exc:
            errors.append(f"{rel}: unreadable ({exc!r})")
            continue
        if n != want.get("bytes"):
            errors.append(f"{rel}: size {n} != manifest {want.get('bytes')}")
        elif digest != want.get("sha256"):
            errors.append(f"{rel}: sha256 mismatch")
    return ("corrupt", errors) if errors else ("ok", [])


def quarantine_checkpoint(ckpt_path: str, errors: list[str]) -> str | None:
    """Move a corrupt checkpoint aside (``corrupt_<name>``) so no later scan
    retries it, with a typed journal event and a rank-0-visible error. The
    exact-name resume regexes never match the prefix, so a quarantined
    directory is invisible to auto-resume even if the rename target varies.
    Returns the quarantine path, or None when the rename itself failed (the
    caller must still skip the checkpoint).

    Concurrency: in a fleet run every host's agent preflight verifies the
    same resume candidates at once, so two processes can race to quarantine
    the same corrupt directory. Losing that race (the source vanished under
    us because a peer already renamed it) is benign — the checkpoint IS
    quarantined; report it as such instead of journaling a second
    ``ckpt_quarantined`` event for a rename that never happened."""
    parent, name = str(ckpt_path).rstrip("/").rsplit("/", 1)
    target = pathio.join(parent, f"{_CORRUPT_PREFIX}{name}")
    n = 0
    while pathio.exists(target):  # repeated corruption of a recycled name
        n += 1
        target = pathio.join(parent, f"{_CORRUPT_PREFIX}{name}.{n}")
    try:
        pathio.rename(str(ckpt_path), target)
    except Exception as exc:
        if not pathio.exists(str(ckpt_path)):
            logger.warning(
                f"checkpoint {ckpt_path} was already quarantined by a "
                f"concurrent process (fleet preflight race); skipping"
            )
            return None
        logger.error(f"could not quarantine corrupt checkpoint {ckpt_path}: {exc!r}")
        target = None
    logger.error(
        f"Checkpoint {ckpt_path} FAILED integrity verification "
        f"({len(errors)} error(s), first: {errors[0] if errors else '?'}); "
        + (f"quarantined to {target}" if target else "quarantine rename failed")
    )
    obs.current().event(
        "ckpt_quarantined", path=str(ckpt_path),
        quarantine_path=str(target) if target else "",
        errors=errors[:8],
    )
    return target


# Manifest writes for ASYNC saves ride a small background thread that waits
# for Orbax's commit (the rename of the tmp dir is its last act, so once the
# final directory exists its contents are complete). (thread, path) pairs are
# tracked so wait_for_saves can make manifests durable too — but a thread
# whose directory never appeared (failed background write) is skipped, not
# waited out.
_MANIFEST_THREADS: list[tuple[threading.Thread, str]] = []
_manifest_threads_lock = threading.Lock()


def _manifest_after_commit(path: str, deadline_s: float = 900.0) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            if pathio.isdir(path):
                # same transient-I/O policy as the save that produced the
                # checkpoint: one object-store 503 must not leave the
                # directory permanently unverifiable
                resilience.retry(
                    write_manifest, path, retry_on=(OSError,),
                    desc=f"manifest write {path}",
                )
                return
        except Exception as exc:
            logger.warning(f"manifest write for {path} failed: {exc!r}")
            return
        time.sleep(0.05)
    logger.warning(f"manifest writer gave up waiting for {path} to commit")


def _spawn_manifest_writer(path: str) -> None:
    t = threading.Thread(
        target=_manifest_after_commit, args=(path,), daemon=True,
        name="dtpu-ckpt-manifest",
    )
    with _manifest_threads_lock:
        _MANIFEST_THREADS[:] = [(x, p) for x, p in _MANIFEST_THREADS if x.is_alive()]
        _MANIFEST_THREADS.append((t, path))
    t.start()


def _join_manifest_writers() -> None:
    with _manifest_threads_lock:
        pending = list(_MANIFEST_THREADS)
    for t, path in pending:
        if t.is_alive() and pathio.isdir(path):
            t.join(timeout=120.0)


# ---------------------------------------------------------------------------
# Prune/restore race guard
# ---------------------------------------------------------------------------

# Paths a restore has selected and not yet finished reading, with nesting
# counts (restore_latest holds the guard around verify+load, and the inner
# _restore re-enters it). prune_mid_checkpoints consults this so the
# checkpoint under an in-flight restore is never deleted mid-read.
_inflight_lock = threading.Lock()
_restores_in_flight: dict[str, int] = {}


@contextlib.contextmanager
def restore_guard(path: str):
    path = str(path)
    with _inflight_lock:
        _restores_in_flight[path] = _restores_in_flight.get(path, 0) + 1
    try:
        yield
    finally:
        with _inflight_lock:
            n = _restores_in_flight.get(path, 1) - 1
            if n <= 0:
                _restores_in_flight.pop(path, None)
            else:
                _restores_in_flight[path] = n


def restore_in_flight(path: str) -> bool:
    with _inflight_lock:
        return _restores_in_flight.get(str(path), 0) > 0


# Two async checkpointers so an epoch save and a ``best`` refresh can be in
# flight concurrently; each serializes with itself (wait before next save).
_CKPTRS: dict[str, ocp.AsyncCheckpointer] = {}


def _checkpointer(which: str = "epoch") -> ocp.AsyncCheckpointer:
    if which not in _CKPTRS:
        _CKPTRS[which] = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
    return _CKPTRS[which]


def wait_for_saves() -> None:
    """Block until every in-flight async save is committed to disk (and its
    integrity manifest, when the commit landed, is written)."""
    for c in _CKPTRS.values():
        c.wait_until_finished()
    _join_manifest_writers()


def _state_device_count(state: Any) -> int:
    """Fleet device count the state is committed on (the saving topology
    recorded into checkpoint metadata). Falls back to the process-global
    count for host-resident trees (unit-test states)."""
    for leaf in jax.tree.leaves(state.params):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                return len(sharding.device_set)
            except Exception:
                break
    return jax.device_count()


def _snapshot(tree):
    """Independent on-device copies of every jax array in ``tree``.

    Mandatory before an ASYNC save of the live train state: Orbax serializes
    on a background thread while the step loop keeps training, and the jitted
    step DONATES the state — on CPU, where host reads of a device buffer are
    zero-copy views, the background writer reads the very memory the next
    optimizer steps overwrite and commits a *torn* checkpoint (leaves holding
    later-step or reused-buffer bytes) that still passes its own integrity
    manifest, since the manifest hashes whatever bytes landed. Multi-host
    fleets hit this reproducibly: the coordinated commit stretches the write
    window across many steps (caught by tests/test_agent.py's supervised
    recovery chaos tests). The copy is async-dispatched device work — no host
    sync — and, unlike a host-side ``np.asarray`` snapshot, works for
    non-fully-addressable multi-host shardings too.
    """
    return jax.tree.map(
        lambda x: x.copy() if isinstance(x, jax.Array) else x, tree
    )


def save_checkpoint(out_dir: str, epoch: int, state: Any, best_acc1: float, is_best: bool) -> str:
    """Start an async save of a full training checkpoint; refresh ``best`` on
    improvement. Returns once device arrays are snapshotted (the expensive
    serialize+write happens in the background). ``epoch`` is the 0-based epoch
    just finished; the file is named ``epoch+1`` per the reference contract."""
    state = _snapshot(state)
    payload = {
        "epoch": np.int32(epoch),
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "best_acc1": np.float32(best_acc1),
        # saving topology: informational for epoch checkpoints (their resume
        # offset is 0, which every topology can represent), load-bearing for
        # the elastic remap in mid-epoch ones
        "devices": np.int32(_state_device_count(state)),
    }
    path = get_checkpoint_path(out_dir, epoch + 1)
    ckptr = _checkpointer("epoch")
    # the wait is where the PREVIOUS save's background serialize+write
    # surfaces its errors; a transiently failed old checkpoint must not kill
    # a healthy training run (Orbax leaves only a tmp dir, which the resume
    # scan already ignores) — warn and move on to writing the new one
    prev_durable = _wait_tolerating_failure(ckptr, "previous epoch checkpoint")
    if prev_durable:
        # every epoch save issued before this point is committed now, so any
        # emergency checkpoint from an epoch before `epoch` is strictly
        # dominated by a *durable* epoch checkpoint and can be pruned. When
        # the previous write failed, that dominator may not exist — keep the
        # emergency checkpoints as fallback resume points.
        prune_mid_checkpoints(out_dir, before_epoch=epoch)
    tic = time.time()
    resilience.retry(
        ckptr.save, path, payload, force=True, desc=f"checkpoint save {path}"
    )
    # wall_s is the foreground cost (snapshot + dispatch): what the mesh
    # actually stalled for — the background serialize/commit is free
    obs.current().event(
        "checkpoint", ckpt_kind="epoch", path=path, epoch=epoch,
        wall_s=round(time.time() - tic, 4), synchronous=False,
    )
    _spawn_manifest_writer(path)
    if is_best:
        best = _checkpointer("best")
        _wait_tolerating_failure(best, "previous best checkpoint")
        tic = time.time()
        resilience.retry(
            best.save,
            get_best_path(out_dir),
            {"params": state.params, "batch_stats": state.batch_stats},
            force=True,
            desc="best-checkpoint save",
        )
        obs.current().event(
            "checkpoint", ckpt_kind="best", path=get_best_path(out_dir),
            epoch=epoch, wall_s=round(time.time() - tic, 4), synchronous=False,
        )
        _spawn_manifest_writer(get_best_path(out_dir))
    return path


# Transient background-write failures are tolerated (logged, run continues),
# but persistently broken storage must still fail loudly — a 90-epoch run
# whose writes all fail silently would "complete" with no checkpoints.
_MAX_CONSECUTIVE_WAIT_FAILURES = 3
_wait_failures: dict[int, int] = {}  # id(checkpointer) -> consecutive failures


def _wait_tolerating_failure(ckptr: ocp.AsyncCheckpointer, what: str) -> bool:
    """Drain the checkpointer's in-flight save; returns False (after logging)
    when its background write failed instead of re-raising — until the
    failures run consecutive (broken storage, not a blip), which re-raises."""
    try:
        ckptr.wait_until_finished()  # ≤1 in flight; no-op when idle
        _wait_failures.pop(id(ckptr), None)
        return True
    except Exception as exc:
        n = _wait_failures.get(id(ckptr), 0) + 1
        _wait_failures[id(ckptr)] = n
        if n >= _MAX_CONSECUTIVE_WAIT_FAILURES:
            logger.error(
                f"background write of the {what} failed {n} times in a row — "
                f"checkpoint storage looks broken, aborting"
            )
            raise
        logger.error(
            f"background write of the {what} failed ({exc!r}); continuing — "
            f"the resume scan skips its partial directory"
        )
        return False


def save_mid_checkpoint(
    out_dir: str, epoch: int, step: int, state: Any, best_acc1: float, rng_key: Any,
    samples_per_step: int | None = None,
) -> str:
    """Emergency mid-epoch checkpoint for graceful preemption.

    Beyond the per-epoch payload it records the in-progress 0-based ``epoch``,
    the ``step`` (batches of that epoch already consumed — resume skips
    exactly that many) and the host ``rng_key`` (the trainer's dropout key,
    so runs with ``RNG_SEED None`` resume with the same stream).

    ``samples_per_step`` (fleet-wide samples one optimizer step consumes:
    ``BATCH_SIZE × ACCUM_STEPS × mesh devices``) additionally records the
    topology-independent resume position ``global_samples = step ×
    samples_per_step`` — what elastic restore remaps the fast-forward from
    when the relaunch has a different device count.

    Synchronous, unlike the epoch save: the process is about to exit, and
    the retry must cover the *whole* write — a transient failure in the
    background serialize/commit would otherwise surface only after the save
    "succeeded", leaving the preemption window spent and no checkpoint.
    """
    payload = {
        "epoch": np.int32(epoch),
        "step": np.int32(step),
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "best_acc1": np.float32(best_acc1),
        "rng_key": np.asarray(jax.device_get(rng_key)),
        "devices": np.int32(_state_device_count(state)),
    }
    if samples_per_step is not None and samples_per_step > 0:
        payload["samples_per_step"] = np.int32(samples_per_step)
        payload["global_samples"] = np.int64(int(step) * int(samples_per_step))
    path = get_mid_checkpoint_path(out_dir, epoch, step)
    ckptr = _checkpointer("mid")
    _wait_tolerating_failure(ckptr, "previous emergency checkpoint")

    def save_committed():
        ckptr.save(path, payload, force=True)
        ckptr.wait_until_finished()  # durable (or raising) before we return

    tic = time.time()
    resilience.retry(
        save_committed,
        retry_on=(Exception,),
        desc=f"emergency checkpoint save {path}",
    )
    # typed journal event: mid-epoch emergency saves used to be log lines
    # only (ISSUE 3 satellite); wall_s here is the full durable write
    obs.current().event(
        "checkpoint", ckpt_kind="emergency", path=path, epoch=epoch, step=step,
        wall_s=round(time.time() - tic, 4), synchronous=True,
    )
    # inline, not on the background thread: the process is exiting, and the
    # relaunch must be able to integrity-verify this checkpoint
    try:
        write_manifest(path)
    except Exception as exc:
        logger.warning(f"manifest write for emergency checkpoint failed: {exc!r}")
    # Older mid checkpoints of the SAME epoch are strictly dominated by this
    # one (the run that wrote it resumed from at-or-past them), so drop them
    # now. Load-bearing after a topology change: restore_latest ranks mids
    # by raw step number, and steps are incomparable across topologies — a
    # stale pre-resize mid with a bigger step number would otherwise outrank
    # this strictly-more-advanced one on every future relaunch.
    for e2, s2, old in _mid_checkpoints(out_dir):
        if e2 == epoch and old != path:
            if restore_in_flight(old):
                continue  # next save or epoch-boundary prune gets it
            try:
                pathio.rmtree(old)
            except Exception as exc:
                logger.warning(f"could not prune superseded emergency checkpoint {old}: {exc!r}")
    return path


def prune_mid_checkpoints(out_dir: str, before_epoch: int) -> None:
    """Best-effort removal of emergency checkpoints for epochs < before_epoch
    (each is dominated by a committed complete epoch checkpoint by the time
    this is called — see save_checkpoint). Truly best-effort: object-store
    backends raise non-OSError types (tf gfile errors via etils), and a
    failed cleanup must never kill the save path that invoked it."""
    for e, s, path in _mid_checkpoints(out_dir):
        if e < before_epoch:
            if restore_in_flight(path):
                # another thread (or a relaunch helper) is mid-restore from
                # this checkpoint: deleting it now would fail that restore.
                # Skip — the next prune pass gets it once the restore ends.
                logger.warning(
                    f"not pruning {path}: a restore from it is in flight"
                )
                continue
            try:
                pathio.rmtree(path)
            except Exception as exc:
                logger.warning(f"could not prune stale emergency checkpoint {path}: {exc!r}")


def _as_template(tree):
    return jax.tree.map(lambda x: ocp.utils.to_shape_dtype_struct(x), tree)


def _restore_args_for(template):
    """Explicit per-leaf restore args carrying the TARGET sharding.

    This is what makes restore elastic: without it Orbax resurrects the
    sharding recorded at save time from the ``_sharding`` file ("unsafe when
    restoring on a different topology than the checkpoint was saved with",
    per its own warning) — i.e. a checkpoint written on an N-device mesh
    would come back pinned to those N devices. With the caller's templates
    as the source of truth, restored arrays land directly on the new mesh.
    Non-array template leaves (np scalars, host rng keys) restore as numpy.
    """

    def one(t):
        sharding = getattr(t, "sharding", None)
        if sharding is not None:
            return ocp.ArrayRestoreArgs(
                sharding=sharding, global_shape=t.shape, dtype=t.dtype
            )
        return ocp.RestoreArgs(restore_type=np.ndarray)

    return jax.tree.map(one, template)


def _restore(path: str, template: dict):
    """Retryable target-sharding-driven restore: transient object-store
    hiccups are retried; a genuinely corrupt directory exhausts the retries
    and raises (callers that can fall back catch it — see restore_latest)."""
    ckptr = _checkpointer()
    tic = time.time()
    path = pathio.absolute(path)  # Orbax reads nothing through a relative path
    with restore_guard(path):
        restored = resilience.retry(
            ckptr.restore,
            path,
            args=ocp.args.PyTreeRestore(
                item=template, restore_args=_restore_args_for(template)
            ),
            retry_on=(OSError,),
            desc=f"checkpoint restore {path}",
        )
    obs.current().event(
        "restore", path=path, wall_s=round(time.time() - tic, 4)
    )
    return restored


def _payload_names(path: str) -> set[str]:
    """Top-level payload key names of a checkpoint, across orbax metadata
    generations: the modern CheckpointMetadata wrapper, the bare tree
    object, or (oldest) a plain dict tree."""
    meta = _checkpointer().metadata(pathio.absolute(path))
    if hasattr(meta, "item_metadata"):
        return set(meta.item_metadata.tree.keys())
    if hasattr(meta, "tree"):
        return set(meta.tree.keys())
    return set(meta.keys())


def load_checkpoint(path: str, state: Any, load_opt: bool = True):
    """Restore (state, start_epoch, best_acc1) from a checkpoint directory.

    Accepts both full checkpoints and weights-only (``best``-style) ones,
    mirroring the reference's graceful weights-only fallback (`utils.py:391-410`).
    ``load_opt=False`` skips optimizer state (the TRAIN.LOAD_OPT warm-start
    knob, reference `trainer.py:147-149`). Restored arrays adopt the sharding
    of the templates in ``state`` — including onto a mesh with a different
    device count than the one that saved them (elastic restore; epoch
    boundaries are always topology-safe because their resume offset is 0).
    """
    wait_for_saves()  # the path may be a save still committing in background
    names = _payload_names(path)

    template = {"params": _as_template(state.params), "batch_stats": _as_template(state.batch_stats)}
    full = {"epoch", "opt_state", "best_acc1"} <= names
    if full:
        template.update(
            {
                "epoch": np.int32(0),
                "opt_state": _as_template(state.opt_state),
                "best_acc1": np.float32(0.0),
            }
        )
    if "devices" in names:
        template["devices"] = np.int32(0)
    restored = _restore(path, template)
    new_state = state.replace(params=restored["params"], batch_stats=restored["batch_stats"])
    if full:
        if load_opt:
            new_state = new_state.replace(opt_state=restored["opt_state"])
        return new_state, int(restored["epoch"]) + 1, float(restored["best_acc1"])
    return new_state, 0, 0.0


def load_weights(
    path: str,
    params_template: Any,
    batch_stats_template: Any,
    *,
    verify_integrity: bool = True,
):
    """Read-only weights load: ``(params, batch_stats)`` from any checkpoint.

    The serving engine's load path (docs/SERVING.md): accepts every weights
    source the repo produces — converted-torch dirs (scripts/convert_torch.py:
    ``{params, batch_stats}`` only), trained epoch checkpoints (full payload
    with optimizer state) and ``best`` weights-only saves — and restores
    ONLY the params/batch_stats subtrees (``transforms={}`` makes the partial
    item legal), so hosting a trained checkpoint never pays the optimizer
    state's bytes. Leaves land with the templates' shardings (the same
    target-sharding-driven elastic contract as `_restore`); the checkpoint
    directory is never written to — no quarantine, no manifest repair — a
    serving host must not mutate the training run's artifacts. A corrupt
    integrity verify raises instead (refusing to serve poisoned weights);
    "unverified" (no manifest, e.g. a converted dir) loads with a log line.
    """
    if verify_integrity:
        status, errors = verify_checkpoint(path)
        if status == "corrupt":
            raise OSError(
                f"refusing to serve weights from {path}: integrity manifest "
                f"verification failed ({'; '.join(errors[:5])})"
            )
        if status == "unverified":
            logger.info(f"weights {path}: no integrity manifest (load unverified)")

    def one(leaf):
        # jax.ShapeDtypeStruct templates (e.g. eval_shape results with a
        # target sharding attached) pass through untouched — re-templating
        # could drop the sharding the restore is supposed to land on
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        return ocp.utils.to_shape_dtype_struct(leaf)

    template = {
        "params": jax.tree.map(one, params_template),
        "batch_stats": jax.tree.map(one, batch_stats_template),
    }
    ckptr = _checkpointer()
    tic = time.time()
    restored = resilience.retry(
        ckptr.restore,
        path,
        args=ocp.args.PyTreeRestore(
            item=template,
            transforms={},  # partial item: untouched payload keys are skipped
            restore_args=_restore_args_for(template),
        ),
        retry_on=(OSError,),
        desc=f"weights load {path}",
    )
    obs.current().event("restore", path=str(path), wall_s=round(time.time() - tic, 4))
    return restored["params"], restored["batch_stats"]


def load_mid_checkpoint(path: str, state: Any, samples_per_step: int | None = None):
    """Restore an emergency checkpoint: (state, epoch, step, best_acc1,
    rng_key). ``epoch`` is the in-progress 0-based epoch to re-enter and
    ``step`` the number of its batches already consumed *at this run's
    topology*.

    Elastic remap: when the checkpoint recorded a ``global_samples`` offset
    and the caller passes its own ``samples_per_step``, the returned step is
    ``global_samples // samples_per_step`` — the relaunch fast-forwards past
    the exact samples the interrupted run consumed even when its device
    count (and therefore its per-step appetite) changed. An offset the new
    topology cannot hit exactly (not divisible) raises `ElasticResumeError`:
    replaying or skipping a partial step would silently change the sample
    stream, so `restore_latest` falls back to an older checkpoint instead.
    """
    wait_for_saves()
    names = _payload_names(path)
    template = {
        "epoch": np.int32(0),
        "step": np.int32(0),
        "params": _as_template(state.params),
        "batch_stats": _as_template(state.batch_stats),
        "opt_state": _as_template(state.opt_state),
        "best_acc1": np.float32(0.0),
        "rng_key": np.zeros((2,), np.uint32),
    }
    for name, zero in (
        ("devices", np.int32(0)),
        ("samples_per_step", np.int32(0)),
        ("global_samples", np.int64(0)),
    ):
        if name in names:
            template[name] = zero
    restored = _restore(path, template)
    new_state = state.replace(
        params=restored["params"],
        batch_stats=restored["batch_stats"],
        opt_state=restored["opt_state"],
    )
    saved_step = int(restored["step"])
    step = saved_step
    saved_sps = int(restored.get("samples_per_step", 0))
    if samples_per_step and saved_sps and samples_per_step != saved_sps:
        global_samples = int(restored["global_samples"])
        if global_samples % samples_per_step != 0:
            raise ElasticResumeError(
                f"checkpoint {path} was saved at sample offset {global_samples} "
                f"({saved_step} steps × {saved_sps} samples/step); the new "
                f"topology consumes {samples_per_step} samples/step, which "
                f"cannot land on that offset exactly"
            )
        step = global_samples // samples_per_step
        saved_devices = int(restored.get("devices", 0))
        logger.info(
            f"Elastic resume: remapped step {saved_step} "
            f"(@{saved_sps} samples/step"
            + (f", {saved_devices} devices" if saved_devices else "")
            + f") -> step {step} (@{samples_per_step} samples/step) at sample "
            f"offset {global_samples}"
        )
        obs.current().event(
            "elastic_resume", path=path, global_samples=global_samples,
            saved_step=saved_step, saved_samples_per_step=saved_sps,
            step=step, samples_per_step=int(samples_per_step),
            saved_devices=saved_devices,
        )
    return (
        new_state,
        int(restored["epoch"]),
        step,
        float(restored["best_acc1"]),
        np.asarray(restored["rng_key"]),
    )


def resume_candidates(
    out_dir: str, *, step_granular: bool = True
) -> list[tuple[tuple[int, int, int], str, str]]:
    """Every resume candidate in ``out_dir`` as ``(position, kind, path)``,
    most-advanced first — the ranking `restore_latest` walks and the
    dtpu-agent's preflight gate verifies. ``position`` is ``(epoch, step,
    tiebreak)`` with complete epoch checkpoints (``kind == "epoch"``)
    outranking an emergency checkpoint (``"mid"``) at the same position."""
    return _ranked_candidates(
        _complete_checkpoints(out_dir),
        _mid_checkpoints(out_dir) if step_granular else [],
    )


def manifest_hash(ckpt_path: str) -> str:
    """Short content hash of a checkpoint's integrity manifest ("" when the
    manifest is missing/unreadable). Because the manifest lists the sha256 of
    every serialized file, this single digest identifies the checkpoint's
    *bytes* — the version fingerprint the serving deploy path reports in
    ``/healthz`` and its ``deploy_*`` journal records (docs/SERVING.md
    "Continuous deployment")."""
    try:
        return hashlib.sha256(pathio.read_bytes(manifest_path(ckpt_path))).hexdigest()[:16]
    except Exception:
        return ""


def watch_candidates(watch_dir: str) -> list[tuple[tuple[int, int, int], str, str]]:
    """Deployable checkpoints under ``watch_dir`` as ``(position, kind,
    path)``, most-advanced first — the serving deploy watcher's scan
    (serve/deploy.py), sharing `resume_candidates`' position ranking so "an
    older-step checkpoint never deploys over a newer one" means exactly what
    resume means by it.

    ``watch_dir`` may be a training run's OUT_DIR (its ``checkpoints/``
    child is scanned) or the checkpoints directory itself. The exact-name
    regexes already exclude Orbax in-progress temp dirs AND quarantined
    ``corrupt_*`` dirs — both invisible here by construction, no filtering
    needed. A missing/empty dir returns [] (the watcher just polls again).
    """
    d = str(watch_dir)
    if pathio.isdir(pathio.join(d, _DIR_NAME)):
        d = pathio.join(d, _DIR_NAME)
    return _ranked_candidates(_scan_epoch_dirs(d), _scan_mid_dirs(d))


def restore_latest(
    out_dir: str,
    state: Any,
    *,
    step_granular: bool = True,
    skip_corrupt: bool = True,
    load_opt: bool = True,
    verify_integrity: bool = True,
    samples_per_step: int | None = None,
    rollback: int = 0,
):
    """Resume from the most-advanced restorable checkpoint in ``out_dir``.

    Candidates are complete per-epoch checkpoints (resume position
    ``(N, 0)``) and — when ``step_granular`` — mid-epoch emergency
    checkpoints (position ``(epoch, step)``). The highest resume position
    wins; at an equal position a complete epoch checkpoint is preferred over
    an emergency one.

    Robustness, per candidate (each emits a typed journal event plus a
    rank-0-visible warning — a skipped checkpoint is never silent):

    - ``verify_integrity``: the checksum manifest is re-verified first; a
      corrupt candidate is QUARANTINED (renamed ``corrupt_*``,
      ``ckpt_quarantined`` event) and the next-highest tried.
    - ``skip_corrupt``: a candidate that fails to restore anyway (partial
      write the manifest couldn't see — e.g. no manifest yet) is skipped
      (``ckpt_skipped`` event), so one bad directory can never wedge the
      restart loop.
    - Elastic: ``samples_per_step`` (the new topology's fleet-wide samples
      per optimizer step) remaps mid-epoch resume positions from the saved
      sample offset; a position the new topology cannot hit exactly skips
      that candidate (``ckpt_skipped``, reason ``elastic``) and falls back —
      typically to the epoch-boundary checkpoint, which is always safe.

    The selected candidate is held in a `restore_guard` for the whole
    verify+restore, so a concurrent `prune_mid_checkpoints` cannot delete
    it mid-read.

    ``rollback > 0`` (the dtpu-agent's poison-escalation knob,
    ``RESUME.ROLLBACK`` / ``DTPU_RESUME_ROLLBACK``) deliberately skips that
    many of the most-advanced **known-good** candidates — ones that pass the
    integrity gate; corrupt/quarantined directories never spend rollback
    budget — and restores the next-older one, journaling every skip
    (``ckpt_skipped``, reason ``rollback``). A diverged run thus re-enters
    training from *before* the state that keeps poisoning it, instead of
    replaying the newest checkpoint into the same abort forever.

    Returns ``(state, start_epoch, start_step, best_acc1, rng_key | None,
    path)``, or ``None`` when nothing is restorable.
    """
    to_roll_back = max(0, int(rollback))
    for _, kind, path in resume_candidates(out_dir, step_granular=step_granular):
        with restore_guard(path):
            if verify_integrity:
                status, errors = verify_checkpoint(path)
                if status == "corrupt":
                    quarantine_checkpoint(path, errors)  # warns + journals
                    continue
            if to_roll_back > 0:
                # known-good (it survived the integrity gate) but deliberately
                # skipped: the supervisor judged everything this advanced to
                # be inside the poison basin
                to_roll_back -= 1
                logger.warning(
                    f"Rollback: skipping known-good checkpoint {path} "
                    f"({to_roll_back} more to skip; RESUME.ROLLBACK={rollback})"
                )
                obs.current().event(
                    "ckpt_skipped", path=path, reason="rollback",
                    error=f"rollback depth {rollback}",
                )
                continue
            try:
                if kind == "epoch":
                    st, start_epoch, best = load_checkpoint(path, state, load_opt=load_opt)
                    return st, start_epoch, 0, best, None, path
                st, epoch, step, best, rng_key = load_mid_checkpoint(
                    path, state, samples_per_step=samples_per_step
                )
                return st, epoch, step, best, rng_key, path
            except ElasticResumeError as exc:
                # not corruption: the checkpoint is fine, the new topology
                # just can't express its resume offset. Always fall back.
                logger.warning(
                    f"Checkpoint {path} skipped for elastic resume ({exc}); "
                    f"falling back to the next-highest checkpoint"
                )
                obs.current().event(
                    "ckpt_skipped", path=path, reason="elastic", error=str(exc)
                )
            except Exception as exc:
                if not skip_corrupt:
                    raise
                logger.warning(
                    f"Checkpoint {path} failed to restore ({exc!r}); "
                    f"falling back to the next-highest checkpoint"
                )
                obs.current().event(
                    "ckpt_skipped", path=path, reason="restore_failed",
                    error=repr(exc),
                )
    return None


# ---------------------------------------------------------------------------
# CLI: offline integrity verification
# ---------------------------------------------------------------------------

def _looks_like_checkpoint(path: str) -> bool:
    return pathio.exists(manifest_path(path)) or pathio.exists(
        pathio.join(path, "_CHECKPOINT_METADATA")
    )


def _cli_targets(path: str) -> list[str]:
    """Checkpoint directories named by a CLI path: a single checkpoint dir,
    a ``checkpoints/`` dir, or an OUT_DIR containing one."""
    if _looks_like_checkpoint(path):
        return [path]
    scan = path
    if pathio.isdir(pathio.join(path, _DIR_NAME)):
        scan = get_checkpoint_dir(path)
    if not pathio.isdir(scan):
        return []
    out = []
    for name in sorted(pathio.listdir(scan)):
        child = pathio.join(scan, name)
        if (_CKPT_RE.match(name) or _MID_RE.match(name) or name == _BEST_NAME) and pathio.isdir(child):
            out.append(child)
    return out


def main(argv: list[str] | None = None) -> int:
    """``python -m distribuuuu_tpu.checkpoint verify <dir>`` — re-hash one
    checkpoint (or every checkpoint under an OUT_DIR) against its integrity
    manifest. Exit 0 when nothing is corrupt, 1 otherwise. ``--quarantine``
    additionally moves corrupt directories aside the way `restore_latest`
    would."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m distribuuuu_tpu.checkpoint",
        description="Checkpoint integrity tools (docs/FAULT_TOLERANCE.md)",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)
    v = sub.add_parser("verify", help="verify checksum manifests")
    v.add_argument("path", help="checkpoint dir, checkpoints/ dir, or OUT_DIR")
    v.add_argument(
        "--quarantine", action="store_true",
        help="rename corrupt checkpoints to corrupt_* (what auto-resume does)",
    )
    args = parser.parse_args(argv)

    targets = _cli_targets(args.path)
    if not targets:
        print(f"no checkpoints found under {args.path}")
        return 1
    n_corrupt = 0
    for t in targets:
        status, errors = verify_checkpoint(t)
        print(f"{status.upper():10s} {t}")
        for e in errors:
            print(f"           - {e}")
        if status == "corrupt":
            n_corrupt += 1
            if args.quarantine:
                q = quarantine_checkpoint(t, errors)
                if q:
                    print(f"           quarantined -> {q}")
    print(f"{len(targets)} checkpoint(s), {n_corrupt} corrupt")
    return 1 if n_corrupt else 0


if __name__ == "__main__":
    raise SystemExit(main())
