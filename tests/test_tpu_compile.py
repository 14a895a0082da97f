"""Every Pallas SpMV kernel compiles for a TPU v5e at deployment shapes.

Nothing runs: each case lowers a kernel for a described (not attached) v5e
chip and checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  This catches misaligned blocks, unsupported ops and
VMEM overruns that interpret mode cannot see, at no chip time.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU compiler library, so
under several pytest workers only the worker running this file loads it.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather import WHOLE_X_MAX_COLS
from repro.kernels.gather import pick_chunk
from repro.kernels.spmv_csrk import spmv_csrk_tiles_pallas, x_tiles_per_step
from repro.kernels.spmv_diahybrid import spmv_dia_pallas
from repro.kernels.spmv_segsum import spmv_segsum_pallas
from repro.kernels.spmv_sellcs import spmv_sellcs_pallas

#: ecology1 at its published size (1,000,000 rows, 4,996,000 nnz) after
#: Band-k and the v5e tuner: its two slot buckets as (tiles T, slots S), rows
#: per tile R and the x-window block width W.  The one-tile bucket is the
#: case a whole grid step has to be padded for.
ECOLOGY1 = dict(buckets=((1, 384), (8928, 640)), R=112, W=6784, n=1_000_000)

#: At most 12 of a tile's 128-column window blocks hold one of its columns:
#: the ``col_blocks`` table is one count column and 12 block columns.
TABLE_WIDTH = 13

#: The same matrix as Band-k orders it on the chip's host: a narrower
#: window, so 256-column one-hot chunks (6784 = 53·128 allows only 128).
ECOLOGY1_CHIP = dict(ECOLOGY1, W=4864, table=TABLE_WIDTH)

#: ecology1 in its own grid order, which ``prepare(reorder="auto")`` keeps:
#: three slot buckets, a 2176-column window (17 blocks, so 128-column
#: chunks) and at most 6 listed blocks a tile.
ECOLOGY1_NATURAL = dict(buckets=((1, 256), (17, 512), (8911, 640)), R=112, W=2176,
                        n=1_000_000, table=7)

#: HPCG's 104³ grid (1,124,864 rows, 27-point) after Band-k on a build
#: host: its largest slot bucket at R = 56, S = 1536 and a window of 91,264
#: columns (83,200 on the chip's host), whose x blocks at 8 tiles a step
#: would overrun VMEM; 75 table blocks, as the chip's host has them.
HPCG104 = dict(T=17_911, S=1536, R=56, W=91_264, n=1_124_864, table=76, steps=1)

#: The 104³ grid in its own order, which ``prepare(reorder="auto")`` keeps:
#: a 22,016-column window, 4 tiles' x blocks a grid step, at most 11 listed
#: blocks a tile; 19,263 of its 20,087 tiles hold 1536 slots.
HPCG104_NATURAL = dict(T=19_263, S=1536, R=56, W=22_016, n=1_124_864, table=12, steps=4)

VALUE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _x(n_pad, B, sharding):
    return _spec((n_pad,) if B == 1 else (n_pad, B), jnp.float32, sharding)


def _assert_mosaic(fn, *args, **statics):
    text = fn.lower(*args, **statics, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("vd", ["f32", "bf16", "int8"])
def test_csrk_compiles_at_ecology1(one_chip, vd, B):
    R, W = ECOLOGY1["R"], ECOLOGY1["W"]
    L = (-(-ECOLOGY1["n"] // W) + 1) * W
    for T, S in ECOLOGY1["buckets"]:
        scale = (_spec((T, S // 128), jnp.float32, one_chip)
                 if vd == "int8" else None)
        _assert_mosaic(
            spmv_csrk_tiles_pallas,
            _spec((T, S), VALUE_DTYPES[vd], one_chip),
            _spec((T, S), jnp.int32, one_chip),
            _spec((T, S), jnp.int32, one_chip),
            _spec((T,), jnp.int32, one_chip),
            _spec((T, TABLE_WIDTH), jnp.int32, one_chip),
            _x(L, B, one_chip),
            scale,
            rows_per_tile=R, window=W, gather_chunk=512,
        )


@pytest.mark.parametrize("B", [1, 8])
def test_sellcs_compiles_at_its_limit(one_chip, B):
    n = WHOLE_X_MAX_COLS["sellcs"]
    T, C, W = n // 8, 8, 128           # C-row chunks, rows capped at 128 nnz
    _assert_mosaic(
        spmv_sellcs_pallas,
        _spec((T, C, W), jnp.float32, one_chip),
        _spec((T, C, W), jnp.int32, one_chip),
        _x(n, B, one_chip),
        _spec((T, C, W // 128), jnp.float32, one_chip),
        gather_chunk=512,
    )


@pytest.mark.parametrize("B", [1, 8])
def test_segsum_compiles_at_its_limit(one_chip, B):
    # powerlaw_zipf at n = 65,536: 4.9M nnz in 512-slot chunks, ≤ 120 rows each
    n = WHOLE_X_MAX_COLS["segsum"]
    T, S, R = 9639, 512, 120
    _assert_mosaic(
        spmv_segsum_pallas,
        _spec((T, S), jnp.bfloat16, one_chip),
        _spec((T, S), jnp.int32, one_chip),
        _spec((T, S), jnp.int32, one_chip),
        _x(n, B, one_chip),
        segs_per_chunk=R, gather_chunk=512,
    )


@pytest.mark.parametrize("B", [1, 8])
def test_diahybrid_compiles_at_its_limit(one_chip, B):
    # stencil_fringe at n = 2^20 (side 1024): the nine 9-point diagonals
    n, side, row_tile = WHOLE_X_MAX_COLS["diahybrid"], 1024, 256
    offsets = tuple(sorted(dy * side + dx for dy in (-1, 0, 1) for dx in (-1, 0, 1)))
    lead = -offsets[0]
    span = -(-(offsets[-1] + lead) // 128) * 128
    L = n + span
    _assert_mosaic(
        spmv_dia_pallas,
        _spec((len(offsets), n), jnp.float32, one_chip),
        _x(L, B, one_chip),
        offsets=offsets, lead=lead, row_tile=row_tile,
    )


@pytest.mark.parametrize("order", ["band_k", "natural"])
@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("vd", ["f32", "bf16", "int8"])
def test_csrk_chunk_table_compiles_at_ecology1(one_chip, vd, B, order):
    """Band-k's order on the chip's host: 256-column chunks, two 128-column
    blocks each, so a listed block can fall in the chunk visited last.  The
    grid's own order: 128-column chunks and three slot buckets."""
    g = {"band_k": ECOLOGY1_CHIP, "natural": ECOLOGY1_NATURAL}[order]
    R, W = g["R"], g["W"]
    L = (-(-g["n"] // W) + 1) * W
    for T, S in g["buckets"]:
        scale = (_spec((T, S // 128), jnp.float32, one_chip)
                 if vd == "int8" else None)
        _assert_mosaic(
            spmv_csrk_tiles_pallas,
            _spec((T, S), VALUE_DTYPES[vd], one_chip),
            _spec((T, S), jnp.int32, one_chip),
            _spec((T, S), jnp.int32, one_chip),
            _spec((T,), jnp.int32, one_chip),
            _spec((T, g["table"]), jnp.int32, one_chip),
            _x(L, B, one_chip),
            scale,
            rows_per_tile=R, window=W, gather_chunk=512,
        )


@pytest.mark.parametrize("order", ["band_k", "natural"])
def test_csrk_compiles_at_hpcg104_level0(one_chip, order):
    """Band-k's window is so wide that it takes one tile's x blocks a grid
    step, over eight sub-steps of each block of the tile streams; the grid's
    own order takes four.  The described chip does not check VMEM (8 a step
    compiles here too), so the rule is pinned as well."""
    g = {"band_k": HPCG104, "natural": HPCG104_NATURAL}[order]
    T, S = g["T"], g["S"]
    assert x_tiles_per_step(S, g["W"], 3, pick_chunk(g["W"], 512)) == g["steps"]
    L = (-(-g["n"] // g["W"]) + 1) * g["W"]
    _assert_mosaic(
        spmv_csrk_tiles_pallas,
        _spec((T, S), jnp.float32, one_chip),
        _spec((T, S), jnp.int32, one_chip),
        _spec((T, S), jnp.int32, one_chip),
        _spec((T,), jnp.int32, one_chip),
        _spec((T, g["table"]), jnp.int32, one_chip),
        _x(L, 1, one_chip),
        None,
        rows_per_tile=g["R"], window=g["W"], gather_chunk=512,
    )
