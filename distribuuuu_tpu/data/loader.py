"""Sharded host-side data loaders with background decode and device prefetch.

Distribution model: the reference runs one loader per GPU-process with a
`DistributedSampler` (`/root/reference/distribuuuu/utils.py:141-152,174-184`);
JAX runs one loader per *host* feeding all local devices. Sharding semantics
match the sampler's: a seed+epoch-keyed global permutation (reshuffled each
epoch via `set_epoch`, `trainer.py:33`), split round-robin across processes,
padded to equal shards. Train drops the last incomplete batch
(``drop_last=True``, `utils.py:150`).

Eval improvement over the reference (deliberate, SURVEY §3.3): the reference
pads val shards by *double-counting* tail samples, biasing reported accuracy.
Here padded samples carry ``weight 0`` and the metrics divide by the true
sample count — exact distributed evaluation.

Batches are dicts of numpy arrays ``{image: (B,H,W,3) u8 raw RGB, label: (B,)
i32, weight: (B,) f32}`` where B is the *host* batch (per-device batch ×
local device count). A producer thread decodes ahead (thread pool — PIL
releases the GIL during JPEG decode) into a bounded queue; `prefetch_to_device`
then keeps TRAIN.PREFETCH global device batches in flight so H2D copy overlaps
compute (the pinned-memory/non_blocking analog, `trainer.py:40`).
"""

from __future__ import annotations

import io
import os
import queue
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import jax
import numpy as np
from PIL import Image

from distribuuuu_tpu import obs, resilience
from distribuuuu_tpu.config import cfg, get_default
from distribuuuu_tpu.data import native
from distribuuuu_tpu.data.dataset import DummyDataset, ImageFolder, open_image_dataset
from distribuuuu_tpu.data.transforms import eval_transform_u8, train_transform_u8
from distribuuuu_tpu.logging import logger
from distribuuuu_tpu.obs.trace import phase


def shard_indices(
    total: int,
    *,
    train: bool,
    seed: int,
    epoch: int,
    process_index: int,
    process_count: int,
) -> np.ndarray:
    """The per-host sample-index stream for one (seed, epoch) — the
    DistributedSampler contract `HostDataLoader._shard_indices` documents,
    as a pure function so the dataplane service (distribuuuu_tpu/dataplane/)
    derives the exact same stream dispatcher-side. This function IS the
    sample-order oracle: service-vs-local bitwise equality reduces to both
    sides calling it with the same arguments."""
    shard_size = (total + process_count - 1) // process_count
    if train:
        g = np.random.default_rng(seed + epoch)
        order = g.permutation(total)
    else:
        order = np.arange(total)
    pad = shard_size * process_count - total
    if pad > 0:
        if train:
            order = np.concatenate([order, order[:pad]])
        else:
            order = np.concatenate([order, np.full(pad, -1, dtype=order.dtype)])
    return order[process_index::process_count]


def aug_seed_base(seed: int, epoch: int, process_index: int) -> int:
    """Base of the per-host, per-epoch augmentation-seed stream (the
    reference's seed+rank analog, `utils.py:60-65`); slot ``b*host_batch+i``
    augments with ``base + b*host_batch + i``. Pure for the same reason as
    :func:`shard_indices` — both sides of the dataplane must agree."""
    return ((seed * 1_000_003 + epoch) * 7919 + process_index * 104_729) & 0x7FFFFFFF


def transform_fingerprint(*, train: bool, im_size: int, crop_size: int) -> str:
    """Identity of the decode+augment pipeline a batch was produced by —
    the dataplane cache-key component that keeps a cache shared by many
    jobs from serving eval-transformed pixels to a train stream (or
    native-decoded pixels to a PIL host: the two backends are not bitwise
    aliases, so the backend is part of the identity)."""
    backend = "native" if native.available() else "pil"
    mode = f"train{im_size}" if train else f"eval{im_size}c{crop_size}"
    return f"{backend}:{mode}"


def _qput(out_q: queue.Queue, item, stop: threading.Event) -> bool:
    """Bounded put that gives up when the consumer is gone (never blocks
    forever on a full queue after an aborted epoch). Used by the decode
    producer; the H2D prefetch worker throttles via its ticket semaphore."""
    while not stop.is_set():
        try:
            out_q.put(item, timeout=0.2)
            return True
        except queue.Full:
            continue
    return False


class HostDataLoader:
    """Per-host loader over an ImageFolder shard."""

    def __init__(
        self,
        dataset: "ImageFolder | object",  # any dataset with .samples (+ optional .read_bytes)
        *,
        host_batch: int,
        train: bool,
        im_size: int,
        process_index: int,
        process_count: int,
        workers: int,
        seed: int,
        prefetch_batches: int = 4,
        crop_size: int = 224,
        injector: "resilience.FaultInjector | None" = None,
    ):
        self.dataset = dataset
        self.host_batch = host_batch
        self.train = train
        self.im_size = im_size
        self.process_index = process_index
        self.process_count = process_count
        self.workers = max(1, workers)
        self.seed = seed
        self.prefetch_batches = prefetch_batches
        self.crop_size = crop_size  # eval center-crop (reference hardcodes 224, `utils.py:166`)
        self.use_native = native.available()
        self.epoch = 0
        self.start_batch = 0  # mid-epoch resume fast-forward (set_epoch)
        self.injector = injector if injector is not None else resilience.FaultInjector()

        total = len(dataset)
        self.shard_size = (total + process_count - 1) // process_count
        if train:
            self.num_batches = self.shard_size // host_batch  # drop_last
            if self.num_batches == 0:
                raise ValueError(
                    f"Training dataset ({total} samples / {process_count} "
                    f"host(s) = {self.shard_size} per shard) yields zero "
                    f"batches per epoch: each host consumes {host_batch} "
                    f"samples per step (BATCH_SIZE x ACCUM_STEPS x local "
                    f"devices) with drop_last. Reduce TRAIN.BATCH_SIZE / "
                    f"TRAIN.ACCUM_STEPS."
                )
        else:
            self.num_batches = (self.shard_size + host_batch - 1) // host_batch

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        """Reshuffle determinism hook (reference `trainer.py:33`).

        ``start_batch`` fast-forwards the epoch for step-granular resume: the
        producer starts at that batch index without decoding the skipped
        samples (the shuffle and per-slot augmentation seeds are pure
        functions of (seed, epoch, index), so the replay is exact). On an
        elastic resume the trainer derives it from the checkpoint's *global
        sample offset* (fleet samples consumed this epoch ÷ this topology's
        samples per step, `checkpoint.load_mid_checkpoint`), so the batch
        index is already in THIS topology's units — the loader never needs
        to know the saving topology. An offset past the epoch means the
        remap went wrong; fail loudly rather than silently yield an empty
        epoch.
        """
        if not 0 <= start_batch <= self.num_batches:
            raise ValueError(
                f"set_epoch(start_batch={start_batch}) outside this "
                f"topology's epoch of {self.num_batches} batches"
            )
        # phase-separated, not racy: set_epoch runs between epochs, and the
        # producer thread that reads `epoch` is spawned per-__iter__ and
        # fully drained before the next set_epoch can run — the write and
        # the thread's reads never overlap in time
        self.epoch = epoch  # dtpu-lint: disable=DT201
        self.start_batch = start_batch

    def __len__(self) -> int:
        return self.num_batches

    def _shard_indices(self) -> np.ndarray:
        """DistributedSampler semantics: seeded global perm → round-robin shard,
        wrap-padded to equal length. Padding positions are flagged with -1 for
        eval (masked). Train wrap samples are real duplicates and CAN train
        when ``shard_size % host_batch`` leaves them before the drop_last
        tail — identical to torch's DistributedSampler, which also trains on
        its wrap padding (`utils.py:141-152` parity, not a divergence)."""
        return shard_indices(
            len(self.dataset),
            train=self.train,
            seed=self.seed,
            epoch=self.epoch,
            process_index=self.process_index,
            process_count=self.process_count,
        )

    def _load_one(self, idx: int, slot_seed: int):
        """Retryable per-sample load with graceful degradation.

        Flaky shard reads / decode errors are retried with backoff
        (FAULT.RETRY_*); a sample that fails every attempt is logged and
        substituted rather than killing a pod-scale run (unless FAULT.DEGRADE
        is off). Eval substitutes a weight-0 zero sample — exactly the
        padding semantics, invisible to the exact metrics. Train substitutes
        a *neighboring real sample* instead: the train loss is unweighted
        (torch parity), so a zero image would actively teach "black → class
        0", while a duplicated real sample only reweights the data
        distribution by one draw. If the neighbors are unreadable too (a
        corrupt shard region), train fails loudly — there is no masked way
        to degrade an unweighted loss.
        """
        if idx < 0:  # eval padding slot: zero image, weight 0 (masked in metrics)
            size = self.im_size if self.train else self.crop_size
            return np.zeros((size, size, 3), dtype=np.uint8), 0, 0.0
        try:
            return resilience.retry(
                self._load_one_raw,
                idx,
                slot_seed,
                retry_on=(OSError, ValueError),
                desc=f"sample load idx={idx}",
            )
        except (OSError, ValueError) as exc:
            if not cfg.FAULT.DEGRADE:
                raise
            if self.train:
                total = len(self.dataset.samples)
                for off in (1, 2, 3):  # deterministic fallbacks, single try each
                    alt = (idx + off) % total
                    try:
                        arr, label, _ = self._load_one_raw(alt, slot_seed)
                    except (OSError, ValueError):
                        continue
                    resilience.RUN_STATS.count_substitution()
                    logger.warning(
                        f"sample idx={idx} failed all retries ({exc!r}); "
                        f"substituted neighboring sample idx={alt}"
                    )
                    return arr, label, 1.0
                # no masked degradation exists for the unweighted train loss
                # (a zero sample would train "black → class 0") — fail loudly
                raise
            resilience.RUN_STATS.count_substitution()
            logger.warning(
                f"sample idx={idx} failed all retries ({exc!r}); substituting "
                f"a masked zero sample"
            )
            return np.zeros((self.crop_size, self.crop_size, 3), dtype=np.uint8), 0, 0.0

    def _load_one_raw(self, idx: int, slot_seed: int):
        self.injector.maybe_fail_io(idx)
        name, label = self.dataset.samples[idx]
        # tar shards hand back member bytes (positional pread, no per-image
        # open); plain ImageFolder decodes straight from the path
        data = None
        if hasattr(self.dataset, "read_bytes"):
            data, name = self.dataset.read_bytes(idx)
        if self.use_native and name.lower().endswith((".jpg", ".jpeg")):
            # C++ decode+transform, GIL-free (native/dtpu_decode.cc); falls
            # through to PIL on decode failure (e.g. odd colorspace). Raw u8
            # out — normalization happens on-device (transforms.device_normalize)
            # so the H2D copy is 4x smaller than shipping float32.
            if self.train:
                arr = (
                    native.decode_train_u8_mem(data, self.im_size, slot_seed)
                    if data is not None
                    else native.decode_train_u8(name, self.im_size, slot_seed)
                )
            else:
                arr = (
                    native.decode_eval_u8_mem(data, self.im_size, self.crop_size)
                    if data is not None
                    else native.decode_eval_u8(name, self.im_size, self.crop_size)
                )
            if arr is not None:
                return arr, label, 1.0
        with Image.open(io.BytesIO(data) if data is not None else name) as im:
            im = im.convert("RGB")
            if self.train:
                arr = train_transform_u8(im, self.im_size, rng=random.Random(slot_seed))
            else:
                arr = eval_transform_u8(im, self.im_size, self.crop_size)
        return arr, label, 1.0

    def _produce(self, out_q: queue.Queue, stop: threading.Event, err_box: list) -> None:
        indices = self._shard_indices()
        # per-host, per-epoch augmentation stream (the reference's seed+rank
        # analog, `utils.py:60-65`): distinct crops/flips on every host
        base = aug_seed_base(self.seed, self.epoch, self.process_index)
        try:
            self._produce_batches(out_q, stop, indices, base)
        except BaseException as exc:
            # surface in the consumer via the side channel, NOT the bounded
            # queue: a full queue must not delay a KeyboardInterrupt/
            # SystemExit (or any failure) behind unconsumed batches. stop
            # doubles as the wake-up: the consumer polls err_box on timeout.
            err_box.append(exc)
            stop.set()
        else:
            # end-marker: waits for queue space unless the consumer is gone
            _qput(out_q, None, stop)

    def decode_batch(self, b: int, *, indices=None, base=None, pool=None) -> dict:
        """Decode batch ``b`` of the current (seed, epoch) stream.

        The one decode path both the in-process producer and the dataplane
        decode worker (distribuuuu_tpu/dataplane/worker.py) run — which is
        what makes a service-fed stream bitwise-identical to local decode.
        ``indices``/``base``/``pool`` are loop-hoisted by callers that decode
        many batches; one-shot callers omit them.
        """
        if indices is None:
            indices = self._shard_indices()
        if base is None:
            base = aug_seed_base(self.seed, self.epoch, self.process_index)
        chunk = indices[b * self.host_batch : (b + 1) * self.host_batch]
        slot0 = b * self.host_batch
        seeds = [base + slot0 + i for i in range(len(chunk))]
        if pool is not None:
            results = list(pool.map(self._load_one, chunk, seeds))
        else:
            results = [self._load_one(i, s) for i, s in zip(chunk, seeds)]
        images = np.stack([r[0] for r in results])
        labels = np.array([r[1] for r in results], dtype=np.int32)
        weights = np.array([r[2] for r in results], dtype=np.float32)
        if not self.train and len(chunk) < self.host_batch:
            # pad final eval batch to a static shape (weight 0)
            short = self.host_batch - len(chunk)
            images = np.concatenate([images, np.zeros((short, *images.shape[1:]), images.dtype)])
            labels = np.concatenate([labels, np.zeros((short,), labels.dtype)])
            weights = np.concatenate([weights, np.zeros((short,), weights.dtype)])
        return {"image": images, "label": labels, "weight": weights}

    def _produce_batches(self, out_q, stop, indices, base) -> None:
        with ThreadPoolExecutor(self.workers) as pool:
            for b in range(self.start_batch, self.num_batches):
                if stop.is_set():
                    return
                if self.train and len(indices) < (b + 1) * self.host_batch:
                    break  # defensive: drop_last tail (num_batches bounds it)
                batch = self.decode_batch(b, indices=indices, base=base, pool=pool)
                if not _qput(out_q, batch, stop):
                    return

    @staticmethod
    def _raise_producer_error(exc: BaseException) -> None:
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            # control-flow exceptions keep their identity so Ctrl-C /
            # sys.exit in a worker aborts the run the normal way
            raise exc
        # fail the run like the reference's torch DataLoader would
        # (a silent short epoch would desync multi-host batch counts)
        raise RuntimeError("data loader worker failed") from exc

    def __iter__(self) -> Iterator[dict]:
        out_q: queue.Queue = queue.Queue(maxsize=self.prefetch_batches)
        stop = threading.Event()
        err_box: list = []
        producer = threading.Thread(
            target=self._produce, args=(out_q, stop, err_box), daemon=True
        )
        producer.start()
        try:
            while True:
                if err_box:  # checked before draining: failures preempt
                    self._raise_producer_error(err_box[0])  # buffered batches
                t_wait = time.monotonic()
                try:
                    batch = out_q.get(timeout=0.2)
                    # producer-bound wait: how long this consumer sat on an
                    # empty decode queue (journaled per epoch as a counter —
                    # the "is the input pipeline the bottleneck?" number)
                    obs.current().add_wait(
                        "decode_wait_s", time.monotonic() - t_wait
                    )
                except queue.Empty:
                    obs.current().add_wait("decode_wait_s", time.monotonic() - t_wait)
                    if err_box:
                        self._raise_producer_error(err_box[0])
                    if not producer.is_alive():
                        # producer is gone: re-check err_box first — the
                        # append happens-before thread death, so an error
                        # raised after the check above is visible here (a
                        # silent short epoch would desync multi-host counts)
                        if err_box:
                            self._raise_producer_error(err_box[0])
                        # clean exit between queue drain and sentinel (or
                        # killed): hand over what it left, then stop
                        # instead of polling forever
                        while True:
                            try:
                                batch = out_q.get_nowait()
                            except queue.Empty:
                                return
                            if batch is None:
                                return
                            yield batch
                    continue
                if batch is None:
                    break
                yield batch
        finally:
            # wake/stop the producer even when the consumer abandons the
            # epoch early, then reap it so threads never leak across epochs
            stop.set()
            producer.join(timeout=5.0)


# Marker key: a loader that yields a batch containing this key promises the
# batch object is immutable and replayed verbatim, so prefetch_to_device may
# reuse its device copy instead of re-shipping identical bytes. Only
# DummyLoader makes that promise; a real loader that recycles buffers in
# place must NOT set it (it would train on stale device data).
REPLAY_CONST = "__dtpu_replay_const__"


class DummyLoader:
    """DUMMY_INPUT path: one pre-generated host batch replayed each step —
    the loop measures pure compute, like the reference's in-memory random
    dataset (`utils.py:109-118`)."""

    def __init__(self, host_batch: int, im_size: int, num_batches: int, batch: dict | None = None):
        self.num_batches = max(1, num_batches)
        self.start_batch = 0
        self._batch = batch if batch is not None else DummyDataset(im_size=im_size).sample_batch(host_batch)
        self._batch[REPLAY_CONST] = True

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        self.start_batch = start_batch

    def __len__(self) -> int:
        return self.num_batches

    def __iter__(self):
        for _ in range(self.start_batch, self.num_batches):
            yield self._batch


def _dummy_tokens(host_batch: int) -> dict | None:
    """TRAIN.TASK "lm" under DUMMY_INPUT: one batch of rows of LM.SEQ_LEN + 1
    ids uniform over the held vocabulary slice (inputs and labels are one leaf
    shifted); None for the image tasks, which keep `DummyDataset`'s batch."""
    if cfg.TRAIN.TASK != "lm":
        return None
    rng = np.random.default_rng(0)
    return {"tokens": rng.integers(0, cfg.LM.VOCAB, (host_batch, cfg.LM.SEQ_LEN + 1)).astype(np.int32)}


def _no_token_dataset() -> None:
    if cfg.TRAIN.TASK == "lm":
        raise ValueError(
            "TRAIN.TASK 'lm' has no dataset reader yet: set MODEL.DUMMY_INPUT True "
            "(synthetic token rows), or feed train_epoch a loader of {'tokens': int32[rows, LM.SEQ_LEN + 1]}"
        )


def _topology(mesh=None):
    """(process_index, process_count, local BATCH devices, global BATCH
    devices) — from the mesh actually being trained on when given, so a
    submesh run (elastic resume onto fewer devices than the host has,
    `runtime.mesh.data_mesh`) sizes its host batches by the mesh, not the
    whole fleet. Devices along a ``seq`` axis cooperate on ONE batch shard
    (`parallel/seq.py`), so the counts divide out the seq extent — the host
    batch is sized by the distinct shards this host feeds, and the batch
    replicates along seq at `prefetch_to_device` (whose sharding spec never
    names the seq axis)."""
    if mesh is None:
        return jax.process_index(), jax.process_count(), jax.local_device_count(), jax.device_count()
    if "seq" in mesh.axis_names:
        local_seq = max(int(mesh.local_mesh.shape["seq"]), 1)
        global_seq = max(int(mesh.shape["seq"]), 1)
    else:
        local_seq = global_seq = 1
    return (
        jax.process_index(),
        jax.process_count(),
        int(mesh.local_mesh.devices.size) // local_seq,
        int(mesh.devices.size) // global_seq,
    )


def _service_address() -> str:
    """The dataplane service address this process should stream from.

    ``DTPU_DATA_SERVICE`` (set by the fleet controller for co-scheduled
    gangs, dataplane/service.py for ad-hoc runs) overrides ``DATA.SERVICE``;
    ``""``/``"local"`` both mean decode on this host."""
    addr = os.environ.get("DTPU_DATA_SERVICE", "").strip()
    if not addr and "DATA" in cfg:
        addr = str(cfg.DATA.SERVICE).strip()
    return "" if addr.lower() in ("", "local", "fleet") else addr


def _service_loader(root: str, *, train: bool, host_batch: int, im_size: int,
                    crop_size: int, proc: int, nproc: int):
    """A ServiceLoader for the resolved DATA.SERVICE address (None when the
    run is configured for local decode)."""
    address = _service_address()
    if not address:
        return None
    from distribuuuu_tpu.dataplane.client import ServiceLoader

    return ServiceLoader(
        address,
        root=root,
        train=train,
        host_batch=host_batch,
        im_size=im_size,
        crop_size=crop_size,
        process_index=proc,
        process_count=nproc,
        seed=cfg.RNG_SEED or 0,
        workers=cfg.TRAIN.WORKERS,
        prefetch_batches=cfg.TRAIN.PREFETCH * 2,
    )


def construct_train_loader(mesh=None):
    """Train loader (reference `construct_train_loader`, `utils.py:121-152`)."""
    proc, nproc, local_dev, global_dev = _topology(mesh)
    # per optimizer step each device consumes BATCH_SIZE × ACCUM_STEPS samples
    step_batch = cfg.TRAIN.BATCH_SIZE * cfg.TRAIN.ACCUM_STEPS
    host_batch = step_batch * local_dev
    if cfg.MODEL.DUMMY_INPUT:
        # TRAIN.DUMMY_EPOCH_SAMPLES synthetic samples per epoch (default 1000,
        # like the reference's DummyDataset, `utils.py:109-118`). At global
        # batches above it this floors to a single step per epoch — raise it
        # for whole-loop throughput measurements.
        return DummyLoader(
            host_batch,
            cfg.TRAIN.IM_SIZE,
            num_batches=cfg.TRAIN.DUMMY_EPOCH_SAMPLES
            // max(1, step_batch * global_dev),
            batch=_dummy_tokens(host_batch),
        )
    _no_token_dataset()
    root = os.path.join(cfg.TRAIN.DATASET, cfg.TRAIN.SPLIT)
    service = _service_loader(
        root, train=True, host_batch=host_batch, im_size=cfg.TRAIN.IM_SIZE,
        crop_size=cfg.TEST.CROP_SIZE, proc=proc, nproc=nproc,
    )
    if service is not None:
        return service
    dataset = open_image_dataset(root)
    return HostDataLoader(
        dataset,
        host_batch=host_batch,
        train=True,
        im_size=cfg.TRAIN.IM_SIZE,
        process_index=proc,
        process_count=nproc,
        workers=cfg.TRAIN.WORKERS,
        seed=cfg.RNG_SEED or 0,
        prefetch_batches=cfg.TRAIN.PREFETCH * 2,
    )


def construct_val_loader(mesh=None):
    """Val loader (reference `construct_val_loader`, `utils.py:155-184`)."""
    if cfg.TEST.CROP_SIZE > cfg.TEST.IM_SIZE:
        # resize_shorter makes the shorter side exactly IM_SIZE; a larger crop
        # would silently zero-pad eval images and degrade reported accuracy
        raise ValueError(
            f"TEST.CROP_SIZE ({cfg.TEST.CROP_SIZE}) must be <= TEST.IM_SIZE "
            f"({cfg.TEST.IM_SIZE})"
        )
    proc, nproc, local_dev, global_dev = _topology(mesh)
    host_batch = cfg.TEST.BATCH_SIZE * local_dev
    if cfg.MODEL.DUMMY_INPUT:
        return DummyLoader(
            host_batch,
            cfg.TEST.CROP_SIZE,
            num_batches=cfg.TRAIN.DUMMY_EPOCH_SAMPLES
            // max(1, cfg.TEST.BATCH_SIZE * global_dev),
            batch=_dummy_tokens(host_batch),
        )
    _no_token_dataset()
    # Reference quirk kept for migration compat: its val loader reads
    # TRAIN.DATASET + TEST.SPLIT and TEST.DATASET is unused (`utils.py:157`),
    # so reference users only ever set TRAIN.DATASET. Honor TEST.DATASET only
    # when it was explicitly changed from the default.
    val_root = (
        cfg.TEST.DATASET
        if cfg.TEST.DATASET != get_default("TEST.DATASET")
        else cfg.TRAIN.DATASET
    )
    root = os.path.join(val_root, cfg.TEST.SPLIT)
    service = _service_loader(
        root, train=False, host_batch=host_batch, im_size=cfg.TEST.IM_SIZE,
        crop_size=cfg.TEST.CROP_SIZE, proc=proc, nproc=nproc,
    )
    if service is not None:
        return service
    dataset = open_image_dataset(root)
    return HostDataLoader(
        dataset,
        host_batch=host_batch,
        train=False,
        im_size=cfg.TEST.IM_SIZE,
        process_index=proc,
        process_count=nproc,
        workers=cfg.TRAIN.WORKERS,
        seed=cfg.RNG_SEED or 0,
        prefetch_batches=cfg.TRAIN.PREFETCH * 2,
        crop_size=cfg.TEST.CROP_SIZE,
    )


def prefetch_to_device(iterator, mesh, prefetch: int = 2):
    """Keep N global device batches in flight ahead of compute.

    Each host batch (numpy) becomes a globally-sharded `jax.Array` on the
    mesh's ``data`` axis via `make_array_from_process_local_data`. Transfers
    run on a dedicated thread so H2D overlaps the running step (the TPU
    analog of pinned-memory ``non_blocking=True`` copies, reference
    `trainer.py:40`) — on slow host↔device links a synchronous per-step copy
    would serialize with compute and dominate the loop.

    A batch carrying the :data:`REPLAY_CONST` marker (`DummyLoader`'s
    replayed batch — a promise the object is immutable and yielded verbatim)
    is transferred once and the device copy reused: the DUMMY_INPUT path is
    defined as "measures pure compute", and re-shipping identical bytes
    every step would measure the link instead. Identity alone is NOT enough
    — a loader recycling buffers in place would alias stale device data —
    so unmarked batches are always re-shipped.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distribuuuu_tpu.parallel.fsdp import batch_axes

    # On a ('data', 'fsdp') mesh the batch shards over BOTH axes (fsdp
    # composes with dp — every device computes a distinct slice), and the
    # committed layout must match the step's in_specs or every batch pays a
    # reshard collective at step entry.
    bx = batch_axes(mesh)
    shardings: dict[int, NamedSharding] = {}  # by rank: rows over the batch axes, the rest whole

    def to_device(batch):
        # every array leaf the batch holds (images, labels and weights; a
        # token batch's ids), the replay marker aside
        out = {}
        for name, leaf in batch.items():
            if name == REPLAY_CONST:
                continue
            if leaf.ndim not in shardings:
                shardings[leaf.ndim] = NamedSharding(mesh, P(bx, *([None] * (leaf.ndim - 1))))
            out[name] = jax.make_array_from_process_local_data(shardings[leaf.ndim], leaf)
        return out

    done = object()
    # The in-flight bound: the worker takes a ticket BEFORE starting each
    # transfer and the consumer returns it when it picks the batch up, so
    # (queued + mid-transfer) <= prefetch and peak global batches alive is
    # ``prefetch`` + the one the consumer holds — the same PREFETCH+1 bound
    # the old synchronous implementation gave (works for prefetch=1 too,
    # which a bounded-queue size could not express). The queue itself is
    # unbounded; the semaphore is the only throttle.
    q: queue.Queue = queue.Queue()
    tickets = threading.BoundedSemaphore(max(1, prefetch))
    # stop: an abandoned epoch (step failure, KeyboardInterrupt) must not
    # leave the worker blocked forever holding device batches, nor leave the
    # upstream HostDataLoader generator (its own producer thread) unclosed
    stop = threading.Event()

    def _take_ticket() -> bool:
        while not stop.is_set():
            if tickets.acquire(timeout=0.2):
                return True
        return False

    def worker():
        it = None
        last_host = None
        last_dev = None
        try:
            it = iter(iterator)
            for batch in it:
                if not _take_ticket():
                    break
                if batch is last_host:
                    dev = last_dev  # marked replay batch: ship once
                else:
                    # dispatch-side H2D cost on the dedicated transfer
                    # thread (the copy itself may still be in flight)
                    with phase("h2d_transfer"):
                        dev = to_device(batch)
                    if REPLAY_CONST in batch:
                        # memoize ONLY marked batches: holding a reference to
                        # every real batch would pin ~one extra host+device
                        # batch for the whole epoch with no reuse possible
                        last_host, last_dev = batch, dev
                q.put(dev)
            else:
                q.put(done)
        except BaseException as e:  # propagate into the training loop
            q.put(e)
        finally:
            # close the upstream generator even on abandonment, so e.g.
            # HostDataLoader's generator-finally runs and stops its producer
            close = getattr(it, "close", None)
            if close is not None:
                close()

    t = threading.Thread(target=worker, daemon=True, name="dtpu-h2d-prefetch")
    t.start()
    try:
        while True:
            # producer-starvation wall: how long the STEP LOOP sat here
            # waiting for a device batch. Fed to telemetry as the
            # ``data_wait_s`` counter, whose per-window delta becomes the
            # window record's ``data_wait_frac`` — the data-wait alarm's
            # signal (docs/OBSERVABILITY.md). Host clock around a queue get:
            # no device sync.
            with phase("data_wait"):
                item = q.get()
            if item is done:
                break
            if isinstance(item, BaseException):
                raise item
            tickets.release()  # hand the worker the slot this batch occupied
            yield item
    finally:
        stop.set()
        t.join(timeout=5.0)  # reap: abandoned epochs must not leak workers
