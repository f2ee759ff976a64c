"""Find a cell's files by name. Whatever belongs to one configuration, one
cell or one per-layer metric is a file of its own under ``benchmark/``, so a
later PR adds files and edits none."""

from __future__ import annotations

import glob
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} does not exist")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_metric_modules() -> list:
    names = sorted(
        os.path.basename(p)[:-3]
        for p in glob.glob(os.path.join(HERE, "layer_metrics", "*.py"))
        if not os.path.basename(p).startswith("_")
    )
    return [load_module("layer_metrics", n) for n in names]
