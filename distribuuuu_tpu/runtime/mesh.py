"""Device-mesh construction.

The reference's parallelism topology is implicit in its process layout (one
process per GPU, DDP over all of them, `trainer.py:134`). Here topology is an
explicit `jax.sharding.Mesh`. The framework's core is data-parallel over a
1-D ``('data',)`` mesh, growing to ``('data', 'fsdp')`` when parameter/
optimizer-state sharding is on (cfg.MESH.FSDP > 1, `parallel/fsdp.py`) and
to ``('data'[, 'fsdp'], 'seq')`` when activations shard their token
dimension (cfg.MESH.SEQ > 1, `parallel/seq.py`); `create_mesh` is general
over named axes so richer layouts (model/stage/expert axes, see
`distribuuuu_tpu/parallel/`) use the same entry point.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh

# Axis order of the training mesh: ('data'[, 'fsdp'][, 'seq']). fsdp sits
# inside data so mesh_utils places its all-gather/reduce-scatter ring on
# tight ICI; seq is LAST — ring attention's ppermute neighbor hops are the
# most latency-sensitive traffic of all, so the seq groups get the innermost
# (tightest, typically host-local) ring.


def create_mesh(axes: dict[str, int], devices=None) -> Mesh:
    """Build a Mesh from ordered ``{axis_name: size}``; one size may be -1.

    -1 is inferred from the remaining device count (like a reshape wildcard).
    Uses `mesh_utils.create_device_mesh` for ICI-aware device ordering on real
    TPU topologies; there its errors propagate (a topology it cannot lay out
    is not silently flattened). Non-TPU devices (CPU meshes) have no
    topology to honour and take the flat device list when it refuses.

    ``devices`` (default: all of `jax.devices()`) lets callers build a mesh
    over an explicit subset — how `data_mesh` realizes an undersized
    ``MESH.DATA`` for elastic-resume runs and tests.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = dict(axes)
    wildcards = [k for k, v in sizes.items() if v == -1]
    if len(wildcards) > 1:
        raise ValueError(f"At most one -1 axis allowed, got {wildcards}")
    known = math.prod(v for v in sizes.values() if v != -1)
    if wildcards:
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {sizes}")
        sizes[wildcards[0]] = n // known
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(f"Mesh {sizes} needs {total} devices, have {n}")

    from jax.experimental import mesh_utils

    shape = tuple(sizes.values())
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        if devices[0].platform == "tpu":
            raise
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(sizes.keys()))


def data_mesh(data: int = -1, fsdp: int = 1, seq: int = 1) -> Mesh:
    """The framework's training mesh (cfg.MESH.DATA / MESH.FSDP / MESH.SEQ).

    ``fsdp=1, seq=1`` (the defaults) is the original 1-D ``('data',)``
    data-parallel mesh, bit-for-bit. ``fsdp>1`` (or -1: all remaining
    devices) adds a ``'fsdp'`` axis — batches shard over both axes, params
    and optimizer state shard over ``fsdp`` (see `parallel/fsdp.py`).
    ``seq>1`` adds a trailing ``'seq'`` axis — ACTIVATIONS shard their token
    dimension over it (`parallel/seq.py`); the batch replicates along seq
    (a seq group cooperates on one batch shard), so ``seq`` multiplies the
    device count without multiplying the global batch. ``seq`` has no -1
    wildcard: the sequence split is a model-shape decision, never a
    remainder.

    ``data=-1`` spans all devices not claimed by fsdp/seq. Explicit sizes
    whose product is smaller than the fleet build a mesh over the first
    ``data*fsdp*seq`` devices — the elastic-restore affordance (resume a run
    saved on N devices onto an M-device submesh of this host, see
    docs/FAULT_TOLERANCE.md) and the CPU test harness's way of emulating
    differently-sized slices. Deliberately loud: leaving chips idle is only
    ever intentional.
    """
    devices = jax.devices()
    seq = int(seq or 1)
    if seq < 0:
        raise ValueError(
            "MESH.SEQ has no -1 wildcard: the sequence split must divide the "
            "model's token count, so pick it explicitly"
        )
    if fsdp in (0, 1):
        axes: dict[str, int] = {"data": data}
    else:
        if data == -1 and fsdp == -1:
            # "shard state over everything": pure FSDP, data axis trivial
            data = 1
        axes = {"data": data, "fsdp": fsdp}
    if seq > 1:
        axes = {**axes, "seq": seq}
    sizes = list(axes.values())
    want = -1 if any(v == -1 for v in sizes) else math.prod(sizes)
    if 0 < want < len(devices):
        from distribuuuu_tpu.logging import logger

        shape = " x ".join(f"MESH.{k.upper()}={v}" for k, v in axes.items())
        if jax.process_count() > 1:
            # devices[:want] would leave some hosts with zero local mesh
            # devices and the loader dividing by a zero host batch — fail
            # here with the real story instead
            raise ValueError(
                f"{shape} < {len(devices)} "
                f"devices is only supported on single-host runs: a submesh "
                f"over the first {want} devices would leave some of the "
                f"{jax.process_count()} hosts with no mesh-local devices. "
                f"Relaunch with a host count matching the target topology."
            )
        logger.warning(
            f"{shape} uses {want} of "
            f"{len(devices)} visible devices (submesh; the rest stay idle)"
        )
        return create_mesh(axes, devices=devices[:want])
    return _check_seq_host_local(create_mesh(axes), seq)


def _check_seq_host_local(mesh: Mesh, seq: int) -> Mesh:
    """Refuse a multi-host mesh whose seq groups span hosts.

    The loader shards samples by PROCESS (`data/loader.py`), while a seq
    group must see identical batch bytes on every member — a group spanning
    two hosts would stitch ring/Ulysses attention across MISMATCHED samples
    and train garbage with no error. Host-local groups (the seq axis fully
    inside each host's local mesh — it is the innermost axis, so any
    standard per-host device block satisfies this) make the replicated
    transfer correct by construction.
    """
    if seq > 1 and jax.process_count() > 1:
        local_seq = int(mesh.local_mesh.shape["seq"])
        if local_seq != seq:
            raise ValueError(
                f"MESH.SEQ={seq} spans hosts (this host's local mesh holds "
                f"only {local_seq} of the seq axis): members of one seq "
                f"group would be fed different per-host sample shards. Pick "
                f"MESH.SEQ dividing the per-host device count."
            )
    return mesh
