"""Ahead-of-time compiles of the simjoin kernels for a TPU v5e that is
described, not attached, plus the CPU parity of the pair-list split.

Interpret mode cannot show what the chip's compiler refuses: unaligned
output blocks, or a scalar-prefetched pair list that overflows SMEM.
These tests lower the batched entry points the executors dispatch
(``interpret=False``) at the shapes they stack, and compile them for one
v5e chip. The topology is described inside a module-scoped fixture —
never while a module is imported — so every pytest worker collects the
same tests and only the worker given this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.simjoin import ops, prune
from repro.kernels.simjoin.ref import count_pairs_ref
from repro.kernels.simjoin.simjoin import BLOCK, PAIR_CAP, SENTINEL


@pytest.fixture(scope="module")
def v5e_chip():
    """A sharding on one chip of a described (not attached) v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("same", [True, False])
@pytest.mark.parametrize("d", [2, 3])
def test_dense_batch_compiles_for_v5e(v5e_chip, d, same):
    """The dense grid's SMEM accumulator over an ``"arbitrary"`` grid,
    in the loop over an executor's (GROUP, d, N) group stack."""
    compiled = ops.count_similar_pairs_batch.lower(
        _spec(v5e_chip, (ops.GROUP, d, 8 * BLOCK)),
        _spec(v5e_chip, (ops.GROUP, d, 3 * BLOCK)), _spec(v5e_chip, ()),
        eps=500, same=same, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("same", [True, False])
@pytest.mark.parametrize("d", [2, 3])
def test_pruned_batch_compiles_for_v5e(v5e_chip, d, same):
    """The block-sparse grid with a short prefetched pair list, stacked
    as ``_batches_block`` stacks it: (k, 1, 3L) for L = 8 rows."""
    packed = ops.pack_pairs_np(np.zeros((1, 3), np.int32), 8)
    compiled = ops.count_similar_pairs_pruned_batch.lower(
        _spec(v5e_chip, (ops.GROUP, d, 16 * BLOCK)),
        _spec(v5e_chip, (ops.GROUP, d, 16 * BLOCK)),
        _spec(v5e_chip, (ops.GROUP,) + packed.shape), _spec(v5e_chip, ()),
        eps=300, same=same, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("calls", [1, 2])
@pytest.mark.parametrize("d", [2, 3])
def test_pruned_pair_list_at_cap_compiles_for_v5e(v5e_chip, d, calls):
    """A pair list at the SMEM cap (one call of PAIR_CAP rows) and one
    twice as long (split into two calls under ``lax.map``)."""
    to_len = calls * PAIR_CAP
    packed = ops.pack_pairs_np(np.zeros((to_len, 3), np.int32), to_len)
    assert packed.shape == (calls, 3 * PAIR_CAP)
    compiled = ops.count_similar_pairs_pruned_batch.lower(
        _spec(v5e_chip, (ops.GROUP, d, 64 * BLOCK)),
        _spec(v5e_chip, (ops.GROUP, d, 64 * BLOCK)),
        _spec(v5e_chip, (ops.GROUP,) + packed.shape), _spec(v5e_chip, ()),
        eps=500, same=True, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("same", [True, False])
def test_pair_list_beyond_cap_splits_with_same_count(same):
    """CPU interpret mode: a pair list longer than PAIR_CAP runs as two
    kernel calls, with live rows in both, and counts what the dense grid
    and the jnp oracle count."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 300, (500, 2)).astype(np.int32)
    b = a if same else rng.integers(0, 300, (400, 2)).astype(np.int32)
    at, bt = ops.pad_cm_np(a, SENTINEL), ops.pad_cm_np(b, -SENTINEL)
    live = np.array([(i, j, 1) for i in range(at.shape[1] // BLOCK)
                     for j in range(bt.shape[1] // BLOCK)], np.int32)
    rows = np.zeros((2 * PAIR_CAP, 3), np.int32)
    half = len(live) // 2
    rows[7:7 + half] = live[:half]
    rows[PAIR_CAP + 100:PAIR_CAP + 100 + len(live) - half] = live[half:]
    got = int(ops.count_similar_pairs_pruned(
        jnp.asarray(at), jnp.asarray(bt),
        jnp.asarray(rows.reshape(2, 3 * PAIR_CAP)), 20, same))
    dense = int(ops.count_similar_pairs(jnp.asarray(a), jnp.asarray(b), 20,
                                        same))
    want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(b), 20, same))
    assert got == dense == want > 0


def test_pack_pairs_splits_at_cap():
    """The host layout splits a padded list longer than the cap into
    PAIR_CAP-row calls and keeps a shorter one as a single call."""
    long = np.ones((PAIR_CAP + 1, 3), np.int32)
    to_len = prune.padded_pair_len(long.shape[0])
    packed = ops.pack_pairs_np(long, to_len)
    assert packed.shape == (to_len // PAIR_CAP, 3 * PAIR_CAP)
    assert packed.reshape(-1, 3)[:PAIR_CAP + 1].all()
    assert not packed.reshape(-1, 3)[PAIR_CAP + 1:].any()
    short = ops.pack_pairs_np(np.ones((5, 3), np.int32), 8)
    assert short.shape == (1, 24)
