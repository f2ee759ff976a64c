"""dtpu-dataplane: disaggregated pod-scale input service (docs/DATA.md).

The per-host thread-producer loader (data/loader.py) is a per-host ceiling:
at `resnet50.train`'s 2622.2 img/s/chip (PERF_LEDGER.jsonl, PR 30) a v5e-16
pod needs ~42k decoded+augmented images/sec, more than one host's cores can decode. This package is the
tf.data-service-shaped answer (Audibert et al., 2023): decode once on a
horizontally scalable CPU worker tier, serve many hosts, epochs and
concurrent fleet-queue jobs from one cache.

- `dispatcher.Dispatcher` owns the seed+epoch-keyed global permutation
  (`data.loader.shard_indices` — the same pure function local decode runs,
  so the sample stream is bitwise-identical by construction) and leases
  batch indices to decode workers with visit-once accounting.
- `worker.run_worker` is the decode loop: lease → `HostDataLoader
  .decode_batch` (the exact local decode path) → push the encoded frame
  back.
- `client.ServiceLoader` is the trainer-side drop-in (``DATA.SERVICE``),
  feeding the existing `prefetch_to_device` double-buffering unchanged,
  with retry/backoff on every socket path and local-decode fallback when
  the dispatcher dies.
- `service.DataPlaneService` ties it together behind the ``dtpu-dataplane``
  console script (same ``--cfg``/overrides contract as every other CLI).
"""

from distribuuuu_tpu.dataplane.client import ServiceLoader
from distribuuuu_tpu.dataplane.dispatcher import BatchCache, Dispatcher, LeaseTable
from distribuuuu_tpu.dataplane.service import DataPlaneService

__all__ = [
    "BatchCache",
    "DataPlaneService",
    "Dispatcher",
    "LeaseTable",
    "ServiceLoader",
]
