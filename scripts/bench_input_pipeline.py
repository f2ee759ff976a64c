#!/usr/bin/env python
"""Input-pipeline throughput benchmark: native C++ decode vs PIL.

SURVEY §7 names input throughput the wall-clock hard part: a v5e-16 needs
>10k img/s/host of decoded+augmented 224² images (the reference leans on
torch's C++ DataLoader workers, `/root/reference/distribuuuu/utils.py:121-152`).
This script measures, on this host:

  1. single-thread decode+train-transform rate — native vs PIL
  2. thread-scaling (both paths release the GIL during decode)
  3. the real `ShardedLoader` end-to-end feed rate (decode → batch → queue)

and prints per-core rates plus the core count needed to hit 10k img/s/host.

Usage: python scripts/bench_input_pipeline.py [--images 256] [--secs 6]

``--service`` benches the disaggregated dataplane instead (docs/DATA.md):
synthetic tar shards → an in-host dtpu-dataplane service at 1/2/4 decode
workers → client-side `ServiceLoader` img/s, vs the local `HostDataLoader`
end-to-end rate, and prints the worker count needed for the ~42k img/s a
v5e-16 pod consumes at `resnet50.train`'s 2622.2 img/s/chip (PERF_LEDGER.jsonl,
PR 30). Emits the same
one-line JSON blob contract as the default mode.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from PIL import Image

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distribuuuu_tpu.data import native  # noqa: E402
from distribuuuu_tpu.data.transforms import train_transform_u8  # noqa: E402


def make_dataset(root: str, n: int, classes: int = 4, hw=(500, 400)) -> list[str]:
    """Synthetic ImageNet-shaped JPEGs (typical ILSVRC file is ~500×400)."""
    rng = np.random.default_rng(0)
    paths = []
    for i in range(n):
        cls_dir = os.path.join(root, f"class_{i % classes}")
        os.makedirs(cls_dir, exist_ok=True)
        # Low-frequency content → realistic JPEG entropy (~50-150 KB files)
        small = rng.integers(0, 255, (hw[1] // 8, hw[0] // 8, 3), np.uint8)
        img = Image.fromarray(small).resize(hw, Image.BILINEAR)
        p = os.path.join(cls_dir, f"img_{i:04d}.jpg")
        img.save(p, quality=85)
        paths.append(p)
    return paths


def bench_fn(fn, paths: list[str], secs: float, workers: int) -> float:
    """Sustained img/s of fn(path, slot_seed) over `paths` for ~secs."""
    n_done = 0
    start = time.perf_counter()
    if workers == 1:
        i = 0
        while time.perf_counter() - start < secs:
            fn(paths[i % len(paths)], i)
            i += 1
        n_done = i
    else:
        with ThreadPoolExecutor(workers) as pool:
            while time.perf_counter() - start < secs:
                chunk = [(paths[(n_done + j) % len(paths)], n_done + j) for j in range(64)]
                list(pool.map(lambda a: fn(*a), chunk))
                n_done += len(chunk)
    return n_done / (time.perf_counter() - start)


def native_train(path: str, seed: int):
    """The loader's default path: region/DCT-scaled decode, u8 out."""
    arr = native.decode_train_u8(path, 224, seed)
    assert arr is not None
    return arr


def native_f32(path: str, seed: int):
    """Round-1 path: full decode + host normalize, f32 out (for comparison)."""
    arr = native.decode_train(path, 224, seed)
    assert arr is not None
    return arr


def pil_train(path: str, seed: int):
    with Image.open(path) as im:
        return train_transform_u8(im.convert("RGB"), 224, rng=random.Random(seed))


def bench_loader(root: str, secs: float) -> float:
    """End-to-end HostDataLoader feed rate (img/s): decode → batch → queue."""
    from distribuuuu_tpu.data.dataset import ImageFolder
    from distribuuuu_tpu.data.loader import HostDataLoader

    loader = HostDataLoader(
        ImageFolder(root),
        host_batch=64,
        train=True,
        im_size=224,
        process_index=0,
        process_count=1,
        workers=max(2, os.cpu_count() or 1),
        seed=0,
    )
    n, epoch, start = 0, 0, time.perf_counter()
    while time.perf_counter() - start < secs:
        loader.set_epoch(epoch)
        epoch += 1
        for batch in loader:
            n += batch["image"].shape[0]
            if time.perf_counter() - start >= secs:
                break
    return n / (time.perf_counter() - start)


POD_IMG_PER_S = 42_000  # v5e-16 at resnet50.train's 2622.2 img/s/chip (PERF_LEDGER.jsonl, PR 30)


def make_shards(root: str, src: str, shard_size: int = 64) -> str:
    """Pack the synthetic tree into tar shards via the production packer —
    one writer of the TarImageFolder layout (scripts/make_tar_shards.py),
    so the bench always measures the layout trainers actually read."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from make_tar_shards import pack

    dst = os.path.join(root, "shards")
    pack(src, dst, shard_size)
    return dst


def bench_service(shard_root: str, secs: float, workers: int,
                  host_batch: int = 64) -> float:
    """Sustained client-side img/s through a w-worker dataplane service.

    Subprocess decode workers (the deployment shape — real processes, no
    shared GIL) with a cold cache per measurement: each worker count gets a
    fresh service, and epochs advance so the cache never serves what this
    run decoded (the number is decode throughput, not cache bandwidth)."""
    from distribuuuu_tpu.dataplane.client import ServiceLoader
    from distribuuuu_tpu.dataplane.service import DataPlaneService

    svc = DataPlaneService(
        workers=workers, worker_threads=max(1, (os.cpu_count() or 2) // workers),
        in_process=False, cache_bytes=64 << 20,
    ).start()
    try:
        loader = ServiceLoader(
            svc.address, root=shard_root, train=True, host_batch=host_batch,
            im_size=224, crop_size=224, process_index=0, process_count=1,
            seed=0, fallback=False,
        )
        n, epoch, start = 0, 0, time.perf_counter()
        # one warmup batch absorbs the workers' cold connect
        loader.set_epoch(epoch)
        it = iter(loader)
        next(it)
        start = time.perf_counter()
        n = 0
        while time.perf_counter() - start < secs:
            for batch in it:
                n += batch["image"].shape[0]
                if time.perf_counter() - start >= secs:
                    break
            epoch += 1
            loader.set_epoch(epoch)
            it = iter(loader)
        return n / (time.perf_counter() - start)
    finally:
        svc.stop()


def run_service_mode(args) -> None:
    cores = os.cpu_count() or 1
    with tempfile.TemporaryDirectory() as root:
        src = os.path.join(root, "src")  # keep the shard dir out of the
        paths = make_dataset(src, args.images)  # ImageFolder's class scan
        shard_root = make_shards(root, src)
        print(f"dataset: {len(paths)} JPEGs in tar shards, host cores={cores}")
        rows = {}
        per_worker = 0.0
        for w in (1, 2, 4):
            rate = bench_service(shard_root, args.secs, w)
            rows[f"service_w{w}"] = round(rate, 1)
            per_worker = max(per_worker, rate / w)
            print(f"  service workers={w}: {rate:8.1f} img/s client-side")
        local = bench_loader(src, args.secs)
        rows["local_e2e"] = round(local, 1)
        print(f"  local loader e2e:  {local:8.1f} img/s")
    rows["img_per_s_per_worker"] = round(per_worker, 1)
    rows["workers_for_pod"] = int(math.ceil(POD_IMG_PER_S / max(1.0, per_worker)))
    print(
        f"\nservice path: {per_worker:.0f} img/s/worker → "
        f"{rows['workers_for_pod']} worker(s) of this host's shape for "
        f"{POD_IMG_PER_S / 1000:.0f}k img/s/pod"
    )
    print(json.dumps({"bench": "input_pipeline_service", **rows}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--secs", type=float, default=6.0)
    ap.add_argument("--service", action="store_true",
                    help="bench the dataplane service instead of raw decode")
    args = ap.parse_args()

    if args.service:
        run_service_mode(args)
        return

    assert native.available(), "run scripts/build_native.sh first"
    cores = os.cpu_count() or 1

    with tempfile.TemporaryDirectory() as root:
        paths = make_dataset(root, args.images)
        kb = np.mean([os.path.getsize(p) for p in paths]) / 1024
        print(f"dataset: {len(paths)} JPEGs, mean {kb:.0f} KB, host cores={cores}")

        rows = {}
        for name, fn in [("native", native_train), ("native_f32", native_f32), ("pil", pil_train)]:
            for w in sorted({1, 2, cores}):
                rate = bench_fn(fn, paths, args.secs, w)
                rows[f"{name}_w{w}"] = round(rate, 1)
                print(f"  {name:10s} workers={w}: {rate:8.1f} img/s")
        e2e = bench_loader(root, args.secs)
        rows["loader_e2e"] = round(e2e, 1)
        print(f"  loader end-to-end:  {e2e:8.1f} img/s")

    per_core = rows["native_w1"]
    rows["cores_for_10k"] = round(10_000 / per_core, 1)
    print(
        f"\nnative path: {per_core:.0f} img/s/core → "
        f"{rows['cores_for_10k']} cores for 10k img/s/host "
        f"(speedup vs PIL: {per_core / rows['pil_w1']:.2f}x)"
    )
    print(json.dumps({"bench": "input_pipeline", **rows}))


if __name__ == "__main__":
    main()
