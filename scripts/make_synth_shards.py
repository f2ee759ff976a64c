#!/usr/bin/env python
"""Generate a synthetic ImageNet-shaped tar-shard dataset for real-data
on-chip throughput measurement (VERDICT r2 #2).

No-egress environments can't fetch ILSVRC, but the decode→assemble→H2D→step
pipeline doesn't care what the pixels show — only that the JPEGs have
ImageNet-like file sizes (~50-150 KB at ~500x400) so decode cost is
realistic. Emits ``<dst>/train`` and ``<dst>/val`` TarImageFolder splits
with a ``classes.txt`` manifest. Idempotent: exits 0 without touching
anything if both splits already hold shards.

    python scripts/make_synth_shards.py --dst /tmp/dtpu_synth_shards \
        [--train-images 10240] [--val-images 1024] [--classes 8]
"""

from __future__ import annotations

import argparse
import io
import os
import sys
import tarfile
import time

import numpy as np
from PIL import Image

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def write_split(dst: str, n: int, classes: list[str], shard_size: int, seed: int) -> float:
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "classes.txt"), "w") as f:
        f.write("\n".join(classes) + "\n")
    rng = np.random.default_rng(seed)
    tf, n_shards, total_bytes = None, 0, 0
    for i in range(n):
        if i % shard_size == 0:
            if tf is not None:
                tf.close()
            tf = tarfile.open(os.path.join(dst, f"shard-{n_shards:05d}.tar"), "w")
            n_shards += 1
        # low-frequency noise upsampled -> realistic JPEG entropy/size
        small = rng.integers(0, 255, (50, 63, 3), np.uint8)
        img = Image.fromarray(small).resize((500, 400), Image.BILINEAR)
        buf = io.BytesIO()
        img.save(buf, format="JPEG", quality=85)
        data = buf.getvalue()
        total_bytes += len(data)
        info = tarfile.TarInfo(f"{classes[i % len(classes)]}/img_{i:06d}.jpg")
        info.size = len(data)
        tf.addfile(info, io.BytesIO(data))
    if tf is not None:
        tf.close()
    return total_bytes / max(n, 1) / 1024


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dst", required=True)
    ap.add_argument("--train-images", type=int, default=10240)
    ap.add_argument("--val-images", type=int, default=1024)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--shard-size", type=int, default=512)
    args = ap.parse_args()

    # Completion marker, written LAST: a .tar existing is not "done" — a run
    # killed mid-write (an outer timeout) would otherwise poison
    # every later session with a truncated shard that "already exists".
    # The marker records the generation parameters, so a rerun with different
    # sizes regenerates instead of silently reusing a mismatched dataset.
    gen_args = (
        f"train-images={args.train_images} val-images={args.val_images} "
        f"classes={args.classes} shard-size={args.shard_size}\n"
    )
    marker = os.path.join(args.dst, ".complete")
    if os.path.isfile(marker):
        with open(marker) as f:
            existing = f.read()
        if existing == gen_args:
            print(f"{args.dst}: shards already present, nothing to do")
            return
        print(f"{args.dst}: complete but generated with {existing.strip()!r} != requested")
    if os.path.isdir(args.dst):
        import shutil

        print(f"{args.dst}: regenerating")
        shutil.rmtree(args.dst)

    classes = [f"class_{c:03d}" for c in range(args.classes)]
    t0 = time.perf_counter()
    kb = write_split(os.path.join(args.dst, "train"), args.train_images, classes,
                     args.shard_size, seed=0)
    write_split(os.path.join(args.dst, "val"), args.val_images, classes,
                args.shard_size, seed=1)
    with open(marker, "w") as f:
        f.write(gen_args)
    print(
        f"wrote {args.train_images}+{args.val_images} JPEGs (mean {kb:.0f} KB) "
        f"-> {args.dst} in {time.perf_counter() - t0:.0f}s"
    )


if __name__ == "__main__":
    main()
