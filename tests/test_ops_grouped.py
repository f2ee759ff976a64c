"""The grouped-product kernel pair (`ops/grouped.py`: `dtpu_moe_gmm`,
`dtpu_moe_tgmm`) against plain per-block einsums, and the route table.

Interpret mode is asked for here, call by call; nothing infers it. What the
chip's compiler makes of the kernels, and the route `held_experts` takes for a
described TPU, is `tests/test_chip_compile.py`'s (one file loads libtpu).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.ops import grouped

HELD, K, N, BLOCK, BLOCKS = 4, 128, 256, 16, 8
# live blocks of each held expert in a round of 8: the blocks of an expert follow one another, the
# rest of the round is dead
LAYOUTS = {
    "ragged": [2, 1, 3, 1],
    "an_expert_with_no_slot": [2, 0, 3, 1],
    "dead_blocks_at_the_end": [1, 0, 2, 0],
    "all_on_one_expert": [0, 0, 8, 0],
    "nothing_landed_here": [0, 0, 0, 0],
}


def _layout(counts):
    """``(expert [BLOCKS] int32, live_blocks)`` as `held_experts.one_round` lays a round out: a block's
    expert by the layout's ends, the blocks past the end on the last held expert."""
    ends = np.cumsum(counts)
    expert = np.minimum(np.searchsorted(ends, np.arange(BLOCKS), side="right"), HELD - 1)
    return jnp.asarray(expert, jnp.int32), jnp.int32(ends[-1])


def _per_block(rows, expert, live_blocks, w, transposed):
    """The same product as plain XLA: every block against a gathered copy of its expert's weights."""
    blocks = rows.reshape(BLOCKS, BLOCK, rows.shape[1])
    spec = "brk,bnk->brn" if transposed else "brk,bkn->brn"
    out = jnp.einsum(spec, blocks, w[expert].astype(rows.dtype), preferred_element_type=jnp.float32)
    return jnp.where((jnp.arange(BLOCKS) < live_blocks)[:, None, None], out, 0.0).reshape(rows.shape[0], -1)


def _operands(dtype, transposed, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((BLOCKS * BLOCK, K)), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((HELD, N, K) if transposed else (HELD, K, N)), jnp.float32)
    weight = jnp.asarray(rng.standard_normal((BLOCKS * BLOCK, N)), jnp.float32)
    return rows, w, weight


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


@pytest.mark.parametrize("transposed", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_gmm_is_each_block_times_its_experts_weights(layout, transposed):
    expert, live = _layout(LAYOUTS[layout])
    rows, w, _ = _operands(jnp.float32, transposed)
    got = grouped.gmm(rows, expert, live, w, transposed=transposed, interpret=True)
    assert got.shape == (BLOCKS * BLOCK, N) and got.dtype == jnp.float32
    assert _rel(got, _per_block(rows, expert, live, w, transposed)) <= 2e-5
    assert not np.any(np.asarray(got[int(live) * BLOCK:]))  # dead blocks are zeros, not what the buffer held


@pytest.mark.parametrize("layout", LAYOUTS)
def test_tgmm_sums_an_experts_blocks_and_zeroes_an_expert_with_none(layout):
    counts = LAYOUTS[layout]
    expert, live = _layout(counts)
    lhs, _, rhs = _operands(jnp.float32, False)
    got = grouped.tgmm(lhs, rhs, expert, live, HELD, interpret=True)
    assert got.shape == (HELD, K, N) and got.dtype == jnp.float32
    block_of = lambda a: a.reshape(BLOCKS, BLOCK, -1)
    each = jnp.einsum("brk,brn->bkn", block_of(lhs), block_of(rhs))
    want = jnp.zeros((HELD, K, N)).at[expert].add(jnp.where((jnp.arange(BLOCKS) < live)[:, None, None], each, 0.0))
    assert _rel(got, want) <= 2e-5
    for e, count in enumerate(counts):
        assert bool(np.any(np.asarray(got[e]))) == (count > 0)  # exactly zero where no block is the expert's


def test_the_visits_walk_every_expert_once_at_least_and_every_live_block_once():
    for counts in LAYOUTS.values():
        expert, live = _layout(counts)
        e, block, real = (np.asarray(a) for a in grouped._visits(expert, live, HELD))
        assert len(e) == BLOCKS + HELD and np.all(np.diff(e) >= 0) and set(e) == set(range(HELD))
        assert sorted(block[real == 1]) == list(range(int(live)))
        assert np.all(np.asarray(expert)[block[real == 1]] == e[real == 1])
        assert np.all(block <= max(int(live) - 1, 0))  # an idle step fetches nothing new


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("transposed", [False, True], ids=["w", "w_transposed"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_grouped_product_value_and_every_gradient(layout, transposed, dtype):
    counts = LAYOUTS[layout]
    expert, live = _layout(counts)
    rows, w, weight = _operands(dtype, transposed, seed=1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2

    def loss(fn):
        return lambda rows, w: jnp.sum(fn(rows, w) * weight)

    kernels = lambda rows, w: grouped.grouped_product(rows, expert, live, w, transposed, True)
    plain = lambda rows, w: _per_block(rows, expert, live, w, transposed)
    assert _rel(kernels(rows, w), plain(rows, w)) <= tol
    (d_rows, d_w), (d_rows_want, d_w_want) = (jax.grad(loss(f), argnums=(0, 1))(rows, w) for f in (kernels, plain))
    assert d_rows.dtype == rows.dtype and d_w.dtype == w.dtype and d_w.shape == w.shape
    if int(live):
        assert _rel(d_rows, d_rows_want) <= tol and _rel(d_w, d_w_want) <= tol
    for e, count in enumerate(counts):
        if not count:
            assert not np.any(np.asarray(d_w[e]))  # an expert with no slot: a zero gradient, exactly
    assert not np.any(np.asarray(d_rows, np.float32)[int(live) * BLOCK:])


@pytest.mark.parametrize("device_kind,block,k,n,itemsize,want", [
    ("TPU v5 lite", 256, 1024, 2688, 2, True),   # the cell's first product, and by symmetry its second
    ("TPU v5 lite", 256, 2688, 1024, 2, True),
    ("cpu", 256, 1024, 2688, 2, False),          # not traced for TPUs
    ("TPU v5 lite", 256, 1000, 2688, 2, False),  # a width that is no whole 128-lane group
    ("TPU v5 lite", 256, 1024, 2600, 2, False),
    ("TPU v5 lite", 8, 1024, 2688, 2, False),    # a block that is no whole bfloat16 sublane tile
    ("TPU v5 lite", 8, 1024, 2688, 4, True),
    ("TPU v5 lite", 256, 65536, 128, 2, False),  # no tile of the weight gradient fits
], ids=lambda v: str(v))
def test_the_route_table(device_kind, block, k, n, itemsize, want):
    assert grouped.grouped_product_fuses(device_kind, block, k, n, itemsize) is want


def test_tiles_are_whole_lane_groups_that_divide_the_width_and_fit():
    assert grouped.gmm_tile(256, 1024, 2688, 2) == 2688 and grouped.gmm_tile(256, 2688, 1024, 2) == 1024
    assert grouped.tgmm_tile(256, 1024, 2688, 2) == 896 and grouped.tgmm_tile(256, 2688, 1024, 2) == 512
    assert grouped.gmm_tile(256, 1024, 100, 2) is None and grouped.tgmm_tile(256, 1024, 100, 2) is None
    for k, n in ((1024, 2688), (2688, 1024), (4096, 4096)):
        tile = grouped.gmm_tile(256, k, n, 2)
        assert n % tile == 0 and tile % 128 == 0
        assert 2 * k * tile * 2 <= grouped.TILE_VMEM_BYTES < grouped.VMEM_LIMIT_BYTES
