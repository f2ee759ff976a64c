"""The one table of hardware peaks, keyed by ``device_kind``. A device that is
not in ``peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in {_PATH}: add its published peaks with their source"
        )
    return table[device_kind]
