"""Grouped matmuls over rows sorted by expert, for the routed experts one
chip holds (Pallas, TPU).

``held_experts_gmm(lhs (m, k), rhs (g, k, n), group_sizes (g,))``: rows
``[o_i, o_i + s_i)`` of ``lhs`` times ``rhs[i]``, where ``s_i`` is group
i's size and ``o_i`` the sum of the sizes before it (``transpose_rhs``:
``rhs`` is (g, n, k) and ``rhs[i]`` is transposed). The input gradient of
that product is the same kernel with the transposed ``rhs``.

``held_experts_tgmm(lhs (m, k), rhs (m, n), group_sizes (g,))``: for each
group, ``lhs[rows_i]^T @ rhs[rows_i]`` -> (g, k, n), the weight gradient;
an empty group gives zeros.

The groups need not fill the buffer. Rows past their end are neither read
into a product (``tgmm``) nor written (``gmm``: their contents are
undefined, and a caller masks them). The grid runs over the row tiles
that hold some group's rows, a number known only at run time, so the work
scales with the rows routed here, rounded to tiles, and not with the
buffer's static size. A tile that two groups share is visited once by
each, and each stores only its own rows.

Tiles: ``gmm`` takes the whole contraction and the whole output width in
one block where they fit, so a group's weight block stays in VMEM across
its row tiles and each row tile is read once; ``tgmm`` holds a (k, n)
accumulator block of at most 2**20 elements. After megablox
(``jax.experimental.pallas.ops.tpu.megablox``), with the same group
metadata and without its sharded-group offset. Under ``vmap`` each kernel
runs once per batch element (``jax.custom_batching.sequential_vmap``): the
grid's length depends on the group sizes, which differ between elements.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_TILES = (256, 128, 64, 32, 16, 8)
_VMEM_LIMIT = 64 * 1024 * 1024


def _row_tile(m: int) -> int:
    for tm in _ROW_TILES:
        if m % tm == 0:
            return tm
    raise ValueError(f"{m} rows: not a multiple of 8")


def _divisors(n: int):
    """Block widths for a dimension of size n, widest first: multiples of
    128 that divide it, else n itself."""
    return [b for b in range(n, 0, -128) if n % b == 0 and b % 128 == 0] \
        or [n]


def _gmm_tiles(k: int, n: int, budget: int = 4 << 20):
    """(tk, tn): the whole width and contraction if their block fits the
    budget (elements), else the widest that do."""
    for tn in _divisors(n):
        for tk in _divisors(k):
            if tk * tn <= budget:
                return tk, tn
    return _divisors(k)[-1], _divisors(n)[-1]


def _tgmm_tiles(k: int, n: int, budget: int = 1 << 20):
    best = (_divisors(k)[-1], _divisors(n)[-1])
    for tk in _divisors(k):
        for tn in _divisors(n):
            if tk * tn <= budget and tk * tn > best[0] * best[1]:
                best = (tk, tn)
    return best


def group_metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    """(offsets (g+1,), group of each grid step, row tile of each grid
    step), and the number of grid steps to run. A group's steps cover the
    row tiles its rows touch; ``visit_empty`` gives an empty group one
    step (``tgmm`` must write its zeros). Steps past the count repeat the
    last entries and are never run."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    tiles = (ends + tm - 1) // tm - starts // tm
    tiles = jnp.where(group_sizes == 0, int(visit_empty), tiles)
    steps = tiles_m + g - 1
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=steps)
    # a row tile is visited once by the group owning its first row and
    # once more by each other group that starts inside it
    shared = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        shared |= group_sizes == 0
    extra = jnp.zeros(tiles_m + 1, jnp.int32).at[
        jnp.where(shared, starts // tm, tiles_m)].add(1)[:tiles_m]
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                            total_repeat_length=steps)
    return (offsets, group_ids, m_tile_ids), jnp.sum(tiles)


def _rows_of(meta, step, tm: int, width: int):
    """Mask of the rows of a step's row tile that belong to its group."""
    offsets, group_ids, m_tile_ids = meta
    g = group_ids[step]
    row = m_tile_ids[step] * tm + lax.broadcasted_iota(jnp.int32,
                                                       (tm, width), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = _row_tile(m)
    tk, tn = _gmm_tiles(k, n)
    n_k = k // tk
    meta, n_steps = group_metadata(group_sizes, m, tm, visit_empty=False)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
               acc_ref):
        step, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                        preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _store():
            mask = _rows_of((offsets, group_ids, m_tile_ids), step, tm, tn)
            out_ref[...] = jnp.where(
                mask, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_map(j, step, kk, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], kk

    def rhs_map(j, step, kk, offsets, group_ids, m_tile_ids):
        if transpose_rhs:
            return group_ids[step], j, kk
        return group_ids[step], kk, j

    def out_map(j, step, kk, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], j

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    item = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_steps, n_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + rhs.size + m * n) * item),
        interpret=interpret,
        name="held_experts_gmm",
    )(*meta, lhs, rhs)


def _tgmm(lhs, rhs, group_sizes, *, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[1]
    g = group_sizes.shape[0]
    tm = _row_tile(m)
    tk, tn = _tgmm_tiles(k, n)
    meta, n_steps = group_metadata(group_sizes, m, tm, visit_empty=True)

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
               acc_ref):
        step = pl.program_id(2)
        last = pl.num_programs(2) - 1
        group = group_ids[step]

        @pl.when((step == 0) |
                 (group_ids[jnp.maximum(step - 1, 0)] != group))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[group + 1] > offsets[group])
        def _accumulate():
            meta_ = (offsets, group_ids, m_tile_ids)
            x = jnp.where(_rows_of(meta_, step, tm, tk),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            y = jnp.where(_rows_of(meta_, step, tm, tn),
                          rhs_ref[...].astype(jnp.float32), 0.0)
            acc_ref[...] += lax.dot(
                x.swapaxes(0, 1).astype(lhs_ref.dtype),
                y.astype(rhs_ref.dtype), preferred_element_type=jnp.float32)

        @pl.when((step == last) |
                 (group_ids[jnp.minimum(step + 1, last)] != group))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(j, kk, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], kk

    def rhs_map(j, kk, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], j

    def out_map(j, kk, step, offsets, group_ids, m_tile_ids):
        return group_ids[step], kk, j

    item = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, n_steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n + g * k * n) * item),
        interpret=interpret,
        name="held_experts_tgmm",
    )(*meta, lhs, rhs)


def _sequential(fn, **static):
    return jax.custom_batching.sequential_vmap(functools.partial(fn,
                                                                 **static))


def held_experts_gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
                     interpret: bool = False):
    """(m, k) rows sorted by group, times their group's (k, n) block of
    ``rhs`` -> (m, n) in ``lhs``'s dtype (f32 accumulation); rows past the
    groups are undefined."""
    return _sequential(_gmm, transpose_rhs=transpose_rhs,
                       interpret=interpret)(lhs, rhs, group_sizes)


def held_experts_tgmm(lhs, rhs, group_sizes, *, interpret: bool = False):
    """Per group, ``lhs[rows]^T @ rhs[rows]`` -> (g, k, n) in ``lhs``'s
    dtype; rows past the groups are not read."""
    return _sequential(_tgmm, interpret=interpret)(lhs, rhs, group_sizes)
