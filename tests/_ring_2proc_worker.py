"""Worker for tests/test_multihost_ring.py — NOT a pytest module.

Each of 2 processes owns 4 CPU devices; the global mesh is a single
8-device ``seq`` axis, so the ring's ppermute neighbor exchanges cross the
process boundary (devices 3→4 and 7→0) — the thing the in-process ring
tests cannot exercise. Every rank checks its local output shards against a
locally computed full attention and prints RING2PROC OK.

Usage: _ring_2proc_worker.py <rank> <port>
"""

import functools
import os
import sys

rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# cross-process CPU collectives need the gloo backend (same knob
# runtime/dist.setup_distributed sets for trainer runs)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=rank
)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from distribuuuu_tpu.parallel import ring_attention  # noqa: E402

assert jax.process_count() == 2 and jax.device_count() == 8

mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
B, H, L, D = 2, 2, 64, 8
rng = np.random.default_rng(0)  # same full tensors on both ranks
q, k, v = (
    rng.standard_normal((B, H, L, D)).astype(np.float32) for _ in range(3)
)
sharding = NamedSharding(mesh, P(None, None, "seq", None))


def shard(full):
    return jax.make_array_from_callback(full.shape, sharding, lambda i: full[i])


def reference(q, k, v, causal):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * D**-0.5
    if causal:
        s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


for causal in (False, True):
    ring = jax.jit(
        jax.shard_map(
            functools.partial(ring_attention, axis_name="seq", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "seq", None),) * 3,
            out_specs=P(None, None, "seq", None),
            check_vma=False,
        )
    )
    out = ring(shard(q), shard(k), shard(v))
    ref = reference(q, k, v, causal)
    for s in out.addressable_shards:
        np.testing.assert_allclose(
            np.asarray(s.data, np.float32), ref[s.index], rtol=2e-5, atol=2e-5,
            err_msg=f"rank {rank} causal={causal} shard {s.index}",
        )

print(f"RING2PROC OK rank={rank}", flush=True)
