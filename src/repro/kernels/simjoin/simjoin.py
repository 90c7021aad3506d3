"""Blocked L1 similarity-join kernel (pl.pallas_call + BlockSpec).

The paper's workload joins sparse-array cells by an L1(eps) predicate
(§2.2). On CPU that is pointer-chasing over cell lists; the TPU-native
formulation tiles the two coordinate sets into 128-aligned VMEM blocks laid
out coordinate-major ((d, N) so the lane dimension is the 128-wide cell
block) and evaluates the |a_i - b_j| <= eps predicate as dense (128, 128)
VPU blocks. Each call returns its total match count: the grid's reduced
axes are ``"arbitrary"`` and every step adds its block pair's count to one
int32 scalar that stays resident in SMEM across the grid.

Self-join mode masks the upper triangle (i < j) using global indices so each
unordered pair counts once. Padded cells use +/- sentinel coordinates whose
distance always exceeds eps.

Two kernel variants share the block-pair body:

  * ``simjoin_dense_count`` — the dense grid: every ``(Na/128, Nb/128)``
    block pair is evaluated (kept for parity testing and as the fallback
    when coordinates are not spatially coherent);
  * ``simjoin_pruned_count`` — the block-sparse grid: the host
    sorts each coordinate set spatially, computes per-block bounding
    boxes, keeps only block pairs whose minimal L1 box distance is
    ``<= eps`` (``repro.kernels.simjoin.prune``), and scalar-prefetches
    the surviving ``(i, j, valid)`` pair list into SMEM as a flat int32
    array (the in-repo ``paged_attention`` ``PrefetchScalarGridSpec``
    pattern) so the grid iterates ONLY live pairs — O(live pairs)
    instead of O(all block pairs) work. SMEM bounds one call's list at
    :data:`PAIR_CAP` rows; the host splits longer lists into
    ``PAIR_CAP``-row calls, run under ``lax.map``, whose counts are
    summed.

Pallas interpret mode serves CPU devices only; :func:`interpret_mode` is
the one place that decides it, from the platform of the devices a caller
runs on.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128
SENTINEL = 1 << 20

#: Most pair-list rows one pruned-kernel call takes. The list is
#: scalar-prefetched into SMEM (1 MiB on TPU v5e) as a flat ``(3P,)``
#: int32 array: 2^15 rows take 384 KiB. The v5e compiler refuses the
#: flat list at 2^17 rows.
PAIR_CAP = 1 << 15


def interpret_mode(devices: Optional[Sequence[Any]] = None) -> bool:
    """Whether the simjoin kernels run in Pallas interpret mode on
    ``devices`` (jax's default devices when ``None``): on CPU devices
    only — every other platform compiles the kernel. A device set that
    mixes platforms is a ``ValueError``."""
    devs = list(devices) if devices is not None else jax.devices()
    platforms = {d.platform for d in devs}
    if len(platforms) != 1:
        raise ValueError(f"devices span platforms {sorted(platforms)}; "
                         f"a join runs on one platform")
    return platforms == {"cpu"}


def _block_pair_count(a_ref, b_ref, i_block, j_block, *, eps: int,
                      same: bool, ndim: int):
    """Shared block-pair body: L1 matches between one (d, BLOCK) pair,
    with self-join dedup (global ``i < j``) reconstructed from the
    pair's block indices — ``program_id`` on the dense grid, the
    scalar-prefetched pair list on the block-sparse grid."""
    dist = jnp.zeros((BLOCK, BLOCK), jnp.int32)
    for k in range(ndim):
        ak = a_ref[k, :]                       # (BLOCK,)
        bk = b_ref[k, :]
        dist = dist + jnp.abs(ak[:, None] - bk[None, :])
    hit = dist <= eps
    if same:
        i = i_block * BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK, BLOCK), 0)
        j = j_block * BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK, BLOCK), 1)
        hit = jnp.logical_and(hit, i < j)
    return jnp.sum(hit.astype(jnp.int32))


def _dense_kernel(a_ref, b_ref, out_ref, *, eps: int, same: bool,
                  ndim: int):
    """a_ref/b_ref: (d, BLOCK) int32 blocks; out_ref: (1,) int32 SMEM
    accumulator, zeroed on the first grid step."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        out_ref[0] = 0

    out_ref[0] += _block_pair_count(a_ref, b_ref, i, j, eps=eps,
                                    same=same, ndim=ndim)


def _resolve(interpret: Optional[bool]) -> bool:
    """An explicit interpret flag, or :func:`interpret_mode` of jax's
    default devices."""
    return interpret_mode() if interpret is None else bool(interpret)


def simjoin_dense_count(a: jax.Array, b: jax.Array, eps: int, same: bool,
                        interpret: Optional[bool] = None) -> jax.Array:
    """a: (d, Na), b: (d, Nb) int32, Na/Nb multiples of BLOCK (padded with
    sentinels by ops.py). Returns the scalar int32 match count over every
    block pair."""
    d, na = a.shape
    _, nb = b.shape
    assert na % BLOCK == 0 and nb % BLOCK == 0, (na, nb)
    kernel = functools.partial(_dense_kernel, eps=eps, same=same, ndim=d)
    out = pl.pallas_call(
        kernel,
        grid=(na // BLOCK, nb // BLOCK),
        in_specs=[
            pl.BlockSpec((d, BLOCK), lambda i, j: (0, i)),
            pl.BlockSpec((d, BLOCK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_resolve(interpret),
        name="simjoin_dense",
    )(a, b)
    return out[0]


def _pruned_kernel(pairs_ref, a_ref, b_ref, out_ref, *, eps: int,
                   same: bool, ndim: int):
    """pairs_ref: (3P,) int32 scalar-prefetched rows ``(block_i, block_j,
    valid)`` flattened; a_ref/b_ref: the (d, BLOCK) blocks the pair list
    selected; out_ref: (1,) int32 SMEM accumulator. Rows padded onto a
    bucket's pair list carry ``valid == 0`` and add nothing."""
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        out_ref[0] = 0

    @pl.when(pairs_ref[3 * p + 2] != 0)
    def _accumulate():
        out_ref[0] += _block_pair_count(
            a_ref, b_ref, pairs_ref[3 * p], pairs_ref[3 * p + 1], eps=eps,
            same=same, ndim=ndim)


def _pruned_call(a: jax.Array, b: jax.Array, flat_pairs: jax.Array,
                 eps: int, same: bool, interpret: bool) -> jax.Array:
    """One pruned-kernel call over a flat ``(3P,)`` pair list with
    ``P <= PAIR_CAP``; returns the scalar int32 match count."""
    d = a.shape[0]
    n_pairs = flat_pairs.shape[0] // 3
    kernel = functools.partial(_pruned_kernel, eps=eps, same=same, ndim=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((d, BLOCK), lambda p, pr: (0, pr[3 * p])),
            pl.BlockSpec((d, BLOCK), lambda p, pr: (0, pr[3 * p + 1])),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="simjoin_pruned",
    )(flat_pairs, a, b)
    return out[0]


def simjoin_pruned_count(a: jax.Array, b: jax.Array, pairs: jax.Array,
                         eps: int, same: bool,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse simjoin: evaluate ONLY the listed block pairs.
    ``a``: (d, Na), ``b``: (d, Nb) int32 coordinate-major sets,
    Na/Nb multiples of BLOCK, spatially sorted and sentinel-padded on
    host (``prune.spatial_sort`` + ``ops.pad_cm_np``); ``pairs``: the
    ``(m, 3L)`` int32 SMEM layout of a ``(block_i, block_j, valid)``
    row list (``ops.pack_pairs_np``): ``m`` kernel calls of ``L <=``
    :data:`PAIR_CAP` flattened rows each, run under ``lax.map``. The
    host lays the list out flat because an in-graph ``(P, 3) -> (3P,)``
    reshape is a relayout whose compile time grows with ``P``. Returns
    the scalar int32 match count."""
    _, na = a.shape
    _, nb = b.shape
    assert na % BLOCK == 0 and nb % BLOCK == 0, (na, nb)
    n_calls, width = pairs.shape
    assert n_calls > 0 and width > 0, \
        "empty pair list: skip the kernel call entirely"
    assert width % 3 == 0 and width // 3 <= PAIR_CAP, pairs.shape
    interpret = _resolve(interpret)
    per_call = jax.lax.map(
        lambda flat: _pruned_call(a, b, flat, eps, same, interpret), pairs)
    return per_call.sum().astype(jnp.int32)
