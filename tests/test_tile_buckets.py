"""Slot-bucketed CSR-k tiles: bit-for-bit vs monolithic, byte-model wins.

Bucketing (sparse/csrk.bucket_tiles) groups tiles by 128-rounded nnz and
drops each bucket's trailing all-padding slots.  Padding slots multiply by
val 0 into a clamped x entry, so removing them cannot change any partial sum
— the kernel result must be IDENTICAL at the bit level, while modeled bytes
strictly shrink whenever per-tile nnz varies.
"""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.formats import (CSRMatrix, bucket_tiles, build_csrk,
                                tiles_from_csrk)
from repro.core.spmv import prepare
from repro.kernels import ops, ref


def _varied_case(rng, m=96, n=96):
    """Matrix with strong per-row nnz variance → tiles land in ≥ 2 buckets."""
    dense = ((rng.random((m, n)) < 0.04) * rng.standard_normal((m, n)))
    dense[: m // 8] = rng.standard_normal((m // 8, n))  # dense head rows
    dense = dense.astype(np.float32)
    A = CSRMatrix.fromdense(dense)
    x = rng.standard_normal(n).astype(np.float32)
    return A, dense, x


def test_bucket_partition_and_slot_rounding(rng):
    A, _, _ = _varied_case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    assert buckets.num_buckets >= 2, "case should exercise >1 bucket"
    # tile_ids partition range(num_tiles)
    all_ids = np.sort(np.concatenate([np.asarray(i) for i in buckets.tile_ids]))
    np.testing.assert_array_equal(all_ids, np.arange(tiles.num_tiles))
    for b in buckets.buckets:
        assert b.slots % 128 == 0 or b.slots == tiles.slots
        assert b.slots <= tiles.slots
        assert b.remainder_nnz == 0  # remainder lives on the bucket set
    assert buckets.remainder_nnz == tiles.remainder_nnz
    assert buckets.modeled_bytes() <= tiles.modeled_bytes()


def test_bucketed_kernel_bit_for_bit_f32(rng):
    A, dense, x = _varied_case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    y_mono = ops.spmv_csrk(tiles, jnp.asarray(x), interpret=True)
    y_buck = ops.spmv_csrk_bucketed(buckets, jnp.asarray(x), interpret=True)
    # identical floats, not merely allclose: same adds in the same order
    np.testing.assert_array_equal(
        np.asarray(y_mono).view(np.int32), np.asarray(y_buck).view(np.int32)
    )
    np.testing.assert_allclose(np.asarray(y_buck), dense @ x,
                               rtol=2e-3, atol=2e-4)


def test_bucketed_kernel_bit_for_bit_batched(rng):
    A, dense, x = _varied_case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=8, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    X = jnp.asarray(rng.standard_normal((A.n, 4)).astype(np.float32))
    y_mono = ops.spmv_csrk(tiles, X, interpret=True)
    y_buck = ops.spmv_csrk_bucketed(buckets, X, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y_mono).view(np.int32), np.asarray(y_buck).view(np.int32)
    )


def test_bucketed_oracle_matches_monolithic_oracle(rng):
    A, _, x = _varied_case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=4, k=3))
    buckets = bucket_tiles(tiles)
    y1 = ref.spmv_csrk_tiles(tiles, jnp.asarray(x))
    y2 = ref.spmv_csrk_buckets(buckets, jnp.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(y1).view(np.int32), np.asarray(y2).view(np.int32)
    )


def test_bucketing_strictly_reduces_modeled_bytes_on_varied(rng):
    A, _, _ = _varied_case(rng)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    nnz_t = np.asarray(tiles.tile_nnz)
    assert nnz_t.std() > 0
    assert buckets.modeled_bytes() < tiles.modeled_bytes()
    assert buckets.padding_overhead() < tiles.padding_overhead()


def test_uniform_tiles_single_bucket():
    """Uniform rows → every tile rounds to the same slot count → 1 bucket,
    no modeled-byte change (compaction only helps under variance)."""
    dense = np.eye(64, dtype=np.float32)
    A = CSRMatrix.fromdense(dense)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    assert buckets.num_buckets == 1
    assert buckets.modeled_bytes() == tiles.modeled_bytes()


def test_pinned_bucket_slots():
    """Hand-checked layout: 4 tiles of 8 rows; rows in tile 0 carry 1 nnz
    (8 nnz → 128 slots) and tile 3 carries dense 32-col rows (256 nnz → 256
    slots) — two buckets with pinned slot widths."""
    m, n = 32, 32
    dense = np.zeros((m, n), np.float32)
    for i in range(m):
        dense[i, i % n] = 1.0          # every row non-empty
    dense[24:32, :] = 1.0              # last tile: 8 rows × 32 = 256 nnz
    A = CSRMatrix.fromdense(dense)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))  # R = 8
    assert tiles.num_tiles == 4 and tiles.rows_per_tile == 8
    buckets = bucket_tiles(tiles)
    assert buckets.num_buckets == 2
    assert sorted(buckets.bucket_slots()) == [128, 256]
    x = jnp.asarray(np.arange(n, dtype=np.float32))
    y = ops.spmv_csrk_bucketed(buckets, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ np.arange(n), rtol=1e-6)


def test_prepare_layouts_agree_bitwise(rng):
    A, _, x = _varied_case(rng)
    op_b = prepare(A, device="tpu_v5e", reorder="bandk", format="csrk")
    op_m = prepare(A, device="tpu_v5e", reorder="bandk", format="csrk",
                   tile_layout="monolithic")
    assert op_b.tile_buckets is not None and op_m.tile_buckets is None
    y_b = op_b.apply_original(jnp.asarray(x))
    y_m = op_m.apply_original(jnp.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(y_b).view(np.int32), np.asarray(y_m).view(np.int32)
    )
    assert op_b.modeled_bytes() <= op_m.modeled_bytes()
    with pytest.raises(ValueError):
        prepare(A, device="tpu_v5e", format="csrk", tile_layout="nope")


def test_bucketed_survives_jit_closure(rng):
    """CSRkTileBuckets is a pytree: jit-compiled closures accept it."""
    import jax

    A, dense, x = _varied_case(rng, m=64, n=64)
    tiles = tiles_from_csrk(build_csrk(A, srs=4, ssrs=2, k=3))
    buckets = bucket_tiles(tiles)
    f = jax.jit(lambda b, v: ref.spmv_csrk_buckets(b, v))
    np.testing.assert_allclose(np.asarray(f(buckets, jnp.asarray(x))),
                               dense @ x, rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# the chunk table rides with the buckets and leaves every bit in place
# ---------------------------------------------------------------------------

def _banded_random(rng, n=2048, half_band=300, per_row=6):
    """Random banded matrix: each row holds its diagonal and ``per_row``
    columns drawn from ``[i − half_band, i + half_band]``, not symmetric."""
    rows, cols = [], []
    for i in range(n):
        lo, hi = max(i - half_band, 0), min(i + half_band + 1, n)
        c = np.unique(np.append(rng.integers(lo, hi, size=per_row), i))
        rows += [i] * len(c)
        cols += list(c)
    dense = np.zeros((n, n), np.float32)
    dense[rows, cols] = rng.standard_normal(len(rows))
    return CSRMatrix.fromdense(dense), dense


def _sweep_all(buckets):
    """The buckets with every 128-block of every window listed: every tile
    sweeps every chunk, as the kernel did before it read a table."""
    nb = 2 * buckets.window // 128
    out = []
    for b in buckets.buckets:
        T = b.num_tiles
        cb = np.concatenate([np.full((T, 1), nb), np.tile(np.arange(nb), (T, 1))], 1)
        out.append(dataclasses.replace(b, col_blocks=jnp.asarray(cb, jnp.int32)))
    return dataclasses.replace(buckets, buckets=tuple(out))


@pytest.fixture(scope="module")
def grid300():
    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.obs import MetricsRegistry, using_registry

    reg = MetricsRegistry()
    with using_registry(reg):
        op = prepare(grid_laplacian_2d(300, 300), device="tpu_v5e", format="csrk")
    return op, reg


@pytest.mark.parametrize("case,chunk", [("grid300", 256), ("grid300", 512),
                                        ("banded", 128), ("banded", 512)])
def test_chunk_table_bucketed_bit_for_bit(rng, request, case, chunk):
    """The kernel with the built table equals the full sweep bit for bit:
    the ecology1 structure at 300 × 300 after Band-k, and a random band."""
    if case == "grid300":
        op = request.getfixturevalue("grid300")[0]
        buckets, n = op.tile_buckets, op.tiles.shape[1]
    else:
        A, _ = _banded_random(rng)
        buckets, n = bucket_tiles(tiles_from_csrk(build_csrk(A, srs=8, ssrs=4, k=3))), A.n
    visited = sum(int(b.chunks_visited(chunk).sum()) for b in buckets.buckets)
    assert visited < buckets.num_tiles * 2 * buckets.window // chunk
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    run = lambda bk, v: np.asarray(ops.spmv_csrk_bucketed(
        bk, v, gather_chunk=chunk, interpret=True)).view(np.int32)
    np.testing.assert_array_equal(run(buckets, x), run(_sweep_all(buckets), x))
    if chunk == 512:
        X = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
        np.testing.assert_array_equal(run(buckets, X), run(_sweep_all(buckets), X))


def test_chunk_table_sliced_with_buckets_and_gauged(grid300):
    """Each bucket keeps its tiles' table rows, and ``prepare`` gauges the
    share of a full sweep the kernel visits."""
    from repro.kernels.gather import pick_chunk

    op, reg = grid300
    tiles, buckets = op.tiles, op.tile_buckets
    cb = np.asarray(tiles.col_blocks)
    for b, ids in zip(buckets.buckets, buckets.tile_ids):
        np.testing.assert_array_equal(np.asarray(b.col_blocks), cb[np.asarray(ids)])
    # every real slot's block is listed, and only those
    lc, nnz_t = np.asarray(tiles.local_col), np.asarray(tiles.tile_nnz)
    for t in range(0, tiles.num_tiles, 97):
        want = np.unique(lc[t, :nnz_t[t]] // 128)
        np.testing.assert_array_equal(cb[t, 1:1 + cb[t, 0]], want)
    chunk = pick_chunk(tiles.window, op.params.gather_chunk)
    share = reg.get("prepare", "csrk.onehot_share")
    sweep = tiles.num_tiles * 2 * tiles.window // chunk
    assert share == pytest.approx(100 * tiles.chunks_visited(chunk).sum() / sweep)
    assert 0 < share < 100
