"""The seams of a configuration: its input kind makes the parent's pools byte for byte, and the
harness's own files name no batch key, no image size and no class count."""

import hashlib
import os
import re

import pytest

from benchmark import harness, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# sha256 over every batch's leaves (name, dtype, shape, bytes; names sorted) of the pool that
# PR 27's ``traffic.make_pool(seed, pool_batches, global_batch, 224, 1000)`` made for the cell
# (recorded from commit 29e4856 in the sandbox before the generator moved)
PARENT_DIGESTS = {
    ("resnet50.train", 7): "a6cd719ead0cf2fe0484ef760bf83e92920751232bf28452a75de27af489752f",
    ("resnet50.train", 2**31 + 17): "4b6ccf427b1d95668df9596e47333e2965514bc59d6e0be4ac74d9aac402874c",
    ("vit_b16.train", 7): "5c8b78e31a9fe3a50e08ad4fa9d92f08efc3f9e39303dcd628a9bfdc542f59e6",
    ("vit_b16.train", 2**31 + 17): "47bddacf76502aceef7a2ba281c61be73529151a2b3df4fecf942ca5c2a6f519",
    ("resnet50.train_dp4", 7): "ddf46baae8ee015ecd1faf7d6537cc112a477681ceb9ea998b712cf3eba92d5e",
    ("resnet50.train_dp4", 2**31 + 17): "7be71ecd6a5d0599a61cbb42e98929f25e59c6230cb42930dae5885d3b9d667d",
}


def digest(pool) -> str:
    h = hashlib.sha256()
    for batch in pool:
        for key in sorted(batch):
            leaf = batch[key]
            h.update(f"{key}:{leaf.dtype.str}:{leaf.shape};".encode())
            h.update(leaf.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("workload,seed", sorted(PARENT_DIGESTS))
def test_a_seeds_pool_is_the_parents_bytes(workload, seed):
    """Both mixes (pools of 8 and of 4) at the cells' own sizes, a small seed and one past 2**31."""
    cell, config = harness.load_cell(workload)
    settings = harness.settings_for(cell, config, rehearse=False)
    global_batch = settings["TRAIN"]["BATCH_SIZE"] * cell["chips"]
    pool = traffic.make_pool(config["input"], seed, cell["mix"]["pool_batches"], global_batch, settings)
    assert len(pool) == cell["mix"]["pool_batches"]
    assert digest(pool) == PARENT_DIGESTS[workload, seed]


def test_a_leaf_that_does_not_lead_with_the_rows_is_refused(tmp_path, monkeypatch):
    from benchmark import files

    os.makedirs(tmp_path / "inputs")
    (tmp_path / "inputs" / "zz_bad.py").write_text(
        "import numpy as np\n\n\ndef make_pool(seed, pool_batches, global_batch, settings):\n"
        "    return [{'rows': np.zeros((global_batch, 2)), 'table': np.zeros((3, global_batch))}]\n")
    monkeypatch.setattr(files, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="table"):
        traffic.make_pool("zz_bad", 1, 1, 4, {})


FORBIDDEN = re.compile(r"""\bim_size\b|\bnum_classes\b|["']image["']|["']label["']""")


def harness_files():
    out = []
    for folder in (BENCH, os.path.join(BENCH, "layer_metrics")):
        out += [os.path.join(folder, n) for n in sorted(os.listdir(folder)) if n.endswith(".py")]
    return out


@pytest.mark.parametrize("path", harness_files(), ids=lambda p: os.path.relpath(p, BENCH))
def test_the_harness_names_no_batch_key_image_size_or_class_count(path):
    """Those belong to ``inputs/image.py``, the two references and the two ``flops/`` files."""
    with open(path) as f:
        found = [(i + 1, line.strip()) for i, line in enumerate(f) if FORBIDDEN.search(line)]
    assert not found, found
