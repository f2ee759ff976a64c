"""Storage-abstracted path I/O for everything that touches ``OUT_DIR``.

The reference routes all checkpoint/config/log I/O through iopath's
``g_pathmgr`` (`/root/reference/distribuuuu/utils.py:12`, `utils.py:340`,
`config.py:70-78`) precisely so OUT_DIR can be non-POSIX — on real pods it
is typically ``gs://``. The TPU-native analog is `etils.epath` (the same
path layer Orbax uses internally for its own writes), so the auto-resume
scan, config provenance dump, and rank-0 log file work against local disk
and object stores through one code path.

Only OUT_DIR artifacts go through here. Dataset roots stay `os.*`: input
pipelines read local host storage by design (the reference's ImageFolder
does too), and the hot decode loop must not pay a VFS indirection.
"""

from __future__ import annotations

import os
from typing import IO

from etils import epath


def is_remote(path: str) -> bool:
    """True for URL-style paths (gs://, s3://, ...) that bare ``os`` breaks on."""
    return "://" in str(path)


def absolute(path: str) -> str:
    """A local path made absolute against the working directory; URL-style
    paths pass through unchanged."""
    return str(path) if is_remote(path) else os.path.abspath(path)


def makedirs(path: str) -> None:
    epath.Path(path).mkdir(parents=True, exist_ok=True)


def isdir(path: str) -> bool:
    return epath.Path(path).is_dir()


def listdir(path: str) -> list[str]:
    """Child basenames of a directory (the ``os.listdir`` contract)."""
    return [p.name for p in epath.Path(path).iterdir()]


def join(path: str, *parts: str) -> str:
    return str(epath.Path(path).joinpath(*parts))


def rmtree(path: str) -> None:
    """Recursively delete a directory if it exists (local or object store)."""
    p = epath.Path(path)
    if p.exists():
        p.rmtree()


def exists(path: str) -> bool:
    return epath.Path(path).exists()


def walk_files(path: str) -> list[str]:
    """All file paths under a directory tree, as ``/``-joined paths relative
    to ``path``, sorted. The checkpoint-manifest enumeration: stable order on
    every backend so two walks of identical content hash identically."""
    root = epath.Path(path)
    out: list[str] = []

    def _walk(p: "epath.Path", rel: str) -> None:
        for child in p.iterdir():
            child_rel = f"{rel}/{child.name}" if rel else child.name
            if child.is_dir():
                _walk(child, child_rel)
            else:
                out.append(child_rel)

    _walk(root, "")
    return sorted(out)


def read_bytes(path: str) -> bytes:
    return epath.Path(path).read_bytes()


def open_bytes(path: str):
    """Open a file for streamed binary reading (checkpoint-manifest hashing:
    the files can be multi-GB, so callers read chunked, never slurp)."""
    return epath.Path(path).open("rb")


def write_text(path: str, text: str) -> None:
    """Atomic-enough small-file write: object stores commit at close; local
    filesystems get a same-directory temp file + rename so a reader never
    sees a torn manifest."""
    if is_remote(path):
        with open_write(path) as f:
            f.write(text)
        return
    import os as _os

    tmp = f"{path}.tmp.{_os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    _os.replace(tmp, path)


def remove(path: str) -> None:
    """Delete a single file; a missing file is fine (signal-file cleanup)."""
    try:
        epath.Path(path).unlink()
    except FileNotFoundError:
        pass


def rename(src: str, dst: str) -> None:
    """Rename/move a file or directory tree (quarantine path). Local: one
    ``os.replace``-style rename. Object stores: epath's copy+delete."""
    epath.Path(src).rename(dst)


def open_write(path: str) -> IO[str]:
    """Open ``path`` for text writing. On object stores the content becomes
    visible at ``close()`` (no partial writes), which is exactly right for
    provenance dumps; callers that stream (the log handler) flush best-effort
    and rely on close for durability."""
    return epath.Path(path).open("w")


def open_next_part(base: str) -> tuple[IO[str], int]:
    """Open ``base`` if absent, else the lowest absent ``base.partN`` (N≥1).

    The append-less object-store idiom shared by the telemetry journal and
    the remote log writer (docs/OBSERVABILITY.md): each durability commit
    closes the current object and continues into the next part, and a
    relaunch into the same OUT_DIR must continue the sequence rather than
    truncate what an earlier launch committed. Returns ``(stream, N)`` with
    N == 0 for ``base`` itself. Readers reassemble parts in order.
    """
    part = 0
    target = base
    while exists(target):
        part += 1
        # not a new namespace claim: this walks continuations of the
        # caller's OWN base name (itself already a .partN the caller owns),
        # so the census has nothing to bound here — ownership was decided
        # by whoever named `base`
        target = f"{base}.part{part}"  # dtpu-lint: disable=DT204
    return open_write(target), part
