"""Shared in-kernel building blocks of the Pallas SpMV kernels.

The CSR-k, SELL-C-σ and segsum kernels reduce a tile of nnz slots the same
way, and this module is the single home for it:

1. the tile's value stream is loaded as f32 (:func:`dequant`; int8 grouped
   scales are applied one 128-lane group at a time);
2. ``x[col]`` is gathered as one-hot matmuls on the MXU (:func:`gather`):
   SpMV is bandwidth-bound, so spending idle MXU FLOPs to avoid scattered
   memory access is the right trade on TPU;
3. the per-slot products are reduced into output rows by a second one-hot
   matmul, in fixed 128-slot groups (:func:`reduce_rows`).

Layout (Mosaic's): a tile is a ``[1, S]`` lane vector of slots, and column
and row indices stay on lanes.  x arrives transposed as ``[P·B, L]`` — the B
right-hand sides on sublanes, columns on lanes — so every one-hot is
``[chunk, S]`` with the chunk's columns on sublanes and the tile's slots on
lanes, and every output is a lane-dense ``[B, rows]`` block.

Precision: every MXU operand is exact in bf16, so no result depends on how
the compiler treats an f32 matmul (on v5e Mosaic rounds f32 operands of a
default-precision dot to bf16).  f32 data is cut into three terms whose sum
is exactly the value (:func:`split3`): an f32 x outside the kernel
(:func:`split_f32`, P = 3), and the slot products inside the reduce.  A
one-hot is exact in bf16, so one bf16 pass with f32 accumulation returns
each x term exactly and their f32 sum rebuilds x bit for bit; the reduce
multiplies exact terms and accumulates in f32, an f32 summation.  Each is
one MXU pass, where an f32 ``Precision.HIGHEST`` dot would take six.  A bf16
x is gathered as is (P = 1).

The execution mode itself follows the platform (:func:`resolve_interpret`).
The matmul operands are bf16 when compiled for the MXU and f32 in the
interpreter (:func:`gather_dtype`): XLA:CPU has no bf16 × bf16 → f32 dot for
every shape, and the terms are exact in both, so both give the same values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128

#: Precision of both one-hot matmuls: one MXU pass, exact because every
#: operand is exact in bf16 (see the module doc).
ONE_PASS = jax.lax.Precision.DEFAULT

#: x columns each whole-x kernel may address (``prepare`` refuses wider
#: matrices).  These kernels hold all of x in VMEM — 32–48 bytes per column
#: at up to eight right-hand sides — while the CSR-k kernel is bounded by its
#: banded window and has no such limit.  SELL-C-σ and segsum also sweep all
#: of x in every tile's one-hot gather, so their work grows as n·nnz and
#: their limit is set by time per SpMV, not by VMEM: at 65,536 columns one
#: SpMV already takes 0.26 s (segsum, 4.9M nnz) and 0.30 s (SELL-C-σ, 0.28M
#: nnz) on one TPU v5e.  The DIA plane reads a window per row block and is
#: limited by VMEM alone.
WHOLE_X_MAX_COLS = {"sellcs": 1 << 16, "segsum": 1 << 16, "diahybrid": 1 << 20}


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas execution mode: an explicit choice, else the platform's.

    ``None`` follows ``jax.default_backend()``: the CPU runs the kernels in
    the Pallas interpreter, a TPU compiles them with Mosaic.  Any other
    platform has no Pallas path here and raises — nothing silently drops to
    the interpreter on an accelerator.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise ValueError(
        f"no Pallas execution mode for platform {backend!r} "
        "(cpu → interpret, tpu → compiled)"
    )


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pick_chunk(S: int, chunk: int) -> int:
    """Largest 128-multiple ≤ ``chunk`` that divides ``S``; falls back to S.

    ``S`` (a window or x width) is a multiple of 128 by construction, so the
    128 fallback always divides it; the final ``S`` fallback only triggers
    for non-aligned S (possible in hand-built tests).
    """
    chunk = max(min(chunk, S) // LANE * LANE, LANE)
    while chunk > LANE and S % chunk:
        chunk -= LANE
    return chunk if S % chunk == 0 else S


def pad_tiles(arrays, tiles_per_step: int):
    """Zero-pad ``[T, ...]`` tile streams to at least one full grid step.

    Mosaic cannot lay out a ``[tiles_per_step, S]`` block over an array with
    fewer rows.  Padding tiles have value 0 and are inert; only small slot
    buckets (T < tiles_per_step) take this copy.
    """
    T = arrays[0].shape[0]
    if T >= tiles_per_step:
        return arrays
    return [None if a is None else
            jnp.pad(a, [(0, tiles_per_step - T)] + [(0, 0)] * (a.ndim - 1))
            for a in arrays]


def gather_dtype(interpret: bool):
    """Operand dtype of the one-hot gather matmul (see the module doc)."""
    return jnp.float32 if interpret else jnp.bfloat16


def split3(v: jax.Array):
    """Three f32 terms, each exact in bf16, with ``(hi + mid) + lo == v``.

    ``hi`` and ``mid`` are cut by masking the low 16 bits, not by an
    f32 → bf16 → f32 round trip: XLA:TPU may drop such a round trip as
    excess precision, which would leave ``hi == v`` and zero the other
    terms.  The 24 significand bits split 8 + 8 + ≤ 8, so every term is
    exact in bf16 however it is later cast.
    """
    def top8(u):
        bits = jax.lax.bitcast_convert_type(u, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    hi = top8(v)
    r = v - hi
    mid = top8(r)
    return hi, mid, r - mid


def split_f32(xT: jax.Array) -> tuple[jax.Array, int]:
    """x as the kernels' gather operand: ``[B, L]`` → (``[P·B, L]``, P).

    f32 x becomes its three :func:`split3` terms stacked as bf16 (P = 3);
    any other dtype passes through with P = 1.
    """
    if xT.dtype != jnp.float32:
        return xT, 1
    parts = [p.astype(jnp.bfloat16) for p in split3(xT)]
    return jnp.concatenate(parts, axis=0), 3


def dequant(vals: jax.Array, scale) -> jax.Array:
    """A ``[N, S]`` value block as f32, int8 grouped scales applied.

    ``scale`` (``[N, S/128]`` f32 or None) holds one symmetric scale per 128
    slots (``repro.sparse.csrk.INT8_GROUP``); bf16/f32 streams pass None and
    only upcast.  Accumulation downstream is always f32.
    """
    v = vals.astype(jnp.float32)
    if scale is None:
        return v
    return jnp.concatenate(
        [v[:, g * LANE:(g + 1) * LANE] * scale[:, g:g + 1]
         for g in range(scale.shape[1])],
        axis=1,
    )


def _onehot_dot(xs: jax.Array, lc: jax.Array, col0, dot_dtype) -> jax.Array:
    """One one-hot pass: ``xs [P·B, chunk]`` holds x columns ``[col0, col0 +
    chunk)``; returns ``[P·B, S]`` with each slot's term where ``lc`` falls
    in the chunk and exact zeros elsewhere."""
    chunk, S = xs.shape[1], lc.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, S), 0) + col0
    onehot = (cols == lc).astype(dot_dtype)                        # [chunk, S]
    return jnp.dot(xs.astype(dot_dtype), onehot, precision=ONE_PASS,
                   preferred_element_type=jnp.float32)


def _sum_parts(g: jax.Array, parts: int) -> jax.Array:
    """``[P·B, S]`` gathered terms → ``[B, S]``: x rebuilt from its P terms."""
    B = g.shape[0] // parts
    out = g[:B]
    for p in range(1, parts):
        out = out + g[p * B:(p + 1) * B]
    return out


def gather(x_ref, lc: jax.Array, *, base: int, chunk: int, parts: int,
           dot_dtype):
    """``x[:, lc − base]`` for one tile, as chunked one-hot matmuls.

    Every chunk of the ref is swept, whatever columns the tile holds: the
    whole-x path of SELL-C-σ and segsum, whose columns may lie anywhere.

    Args:
      x_ref: ``[P·B, W]`` ref of the x columns ``[base, base + W)``, in the
        :func:`split_f32` layout.
      lc: ``[1, S]`` int32 column indices on lanes.  Slots outside
        ``[base, base + W)`` gather 0.
      chunk: x columns per one-hot (a 128-multiple dividing W).
      parts: P.
      dot_dtype: operand dtype of the matmul (:func:`gather_dtype`).

    Returns:
      ``[B, S]`` f32, exact: every slot sums one term and zeros.
    """
    PB, W = x_ref.shape
    S = lc.shape[1]

    def body(i, acc):
        c0 = pl.multiple_of(i * chunk, LANE)
        return acc + _onehot_dot(x_ref[:, pl.ds(c0, chunk)], lc, base + c0,
                                 dot_dtype)

    g = jax.lax.fori_loop(
        0, W // chunk, body, jnp.zeros((PB, S), jnp.float32)
    )
    return _sum_parts(g, parts)


def gather_listed(x_refs, lc: jax.Array, blocks_ref, tile, *, chunk: int,
                  parts: int, dot_dtype):
    """``x[:, lc]`` for one tile, visiting only the chunks its columns use.

    The CSR-k path: a tile reads a banded window, and most of its chunks
    hold none of its columns.  Those would add exact zeros, so skipping
    them leaves every slot's value bit for bit as :func:`gather` gives it.

    Args:
      x_refs: ``[P·B, W]`` refs of consecutive x blocks; together they hold
        columns ``[0, len(x_refs)·W)`` of ``lc``'s index space.
      lc: ``[1, S]`` int32 column indices on lanes.
      blocks_ref / tile: row ``tile`` of a
        :attr:`~repro.sparse.csrk.CSRkTiles.col_blocks` table — a count,
        then that many ascending 128-column blocks holding the tile's real
        columns.  A chunk is visited once if any of its blocks is listed.
      chunk, parts, dot_dtype: as for :func:`gather`.

    Returns:
      ``[B, S]`` f32.  A slot whose column lies in no listed block (a
      padding slot) gathers 0.
    """
    PB, W = x_refs[0].shape
    S = lc.shape[1]
    per, nq = chunk // LANE, W // chunk

    def visit(q, acc):
        ref = q // nq
        c0 = pl.multiple_of((q - ref * nq) * chunk, LANE)
        xs = x_refs[0][:, pl.ds(c0, chunk)]
        for k, r in enumerate(x_refs[1:], 1):
            xs = jnp.where(ref == k, r[:, pl.ds(c0, chunk)], xs)
        return acc + _onehot_dot(xs, lc, q * chunk, dot_dtype)

    def body(i, carry):
        acc, prev = carry
        q = blocks_ref[tile, 1 + i] // per
        acc = jax.lax.cond(q != prev, visit, lambda q, a: a, q, acc)
        return acc, q

    g, _ = jax.lax.fori_loop(
        0, blocks_ref[tile, 0], body,
        (jnp.zeros((PB, S), jnp.float32), jnp.int32(-1)),
    )
    return _sum_parts(g, parts)


def gather_take(x_refs, lc: jax.Array, parts: int) -> jax.Array:
    """``gather_mode="take"``: the same values through ``jnp.take``.

    ``x_refs`` are the contiguous x pieces starting at column 0.  Mosaic has
    no general lane gather, so this path runs in interpret mode only
    (``prepare`` refuses it on a TPU).
    """
    xw = jnp.concatenate([r[...] for r in x_refs], axis=1).astype(jnp.float32)
    return _sum_parts(jnp.take(xw, lc[0], axis=1), parts)


def reduce_rows(contrib: jax.Array, lr: jax.Array, rows: int, dot_dtype):
    """Sum ``[B, S]`` slot products into ``[B, rows]`` by row id ``lr [1, S]``.

    The products are cut into their :func:`split3` terms (stacked ``[3B,
    S]``), so the one-hot matmul multiplies exactly; one matmul per 128-slot
    group, accumulated in group order.  The fixed group shape makes a tile's
    result independent of how many all-padding groups trail it (padding
    contributes exact zeros), so slot buckets and the monolithic layout
    agree bit for bit.
    """
    B, S = contrib.shape
    terms = jnp.concatenate(split3(contrib), axis=0).astype(dot_dtype)  # [3B, S]
    y = jnp.zeros((3 * B, rows), jnp.float32)
    for g in range(S // LANE):
        sl = slice(g * LANE, (g + 1) * LANE)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) == lr[:, sl]
        ).astype(dot_dtype)                                         # [rows, 128]
        y = y + jax.lax.dot_general(
            terms[:, sl], onehot, (((1,), (1,)), ((), ())),
            precision=ONE_PASS, preferred_element_type=jnp.float32,
        )
    return (y[:B] + y[B:2 * B]) + y[2 * B:]


def tile_rows(v, lc, lr, x_refs, bases, *, rows, chunk, parts, gather_mode,
              dot_dtype, blocks=None):
    """One tile end to end: ``Σ_s v[s]·x[:, lc[s]]`` into rows ``lr``.

    ``x_refs``/``bases`` cover the tile's column range in order; returns
    ``[B, rows]`` f32.  ``blocks`` — ``(table_ref, tile)`` of a
    ``col_blocks`` table, for consecutive refs starting at base 0 — limits
    the one-hot gather to the chunks it lists (:func:`gather_listed`);
    without it every chunk is swept.
    """
    if gather_mode == "take":
        g = gather_take(x_refs, lc, parts)
    elif blocks is not None:
        g = gather_listed(x_refs, lc, *blocks, chunk=chunk, parts=parts,
                          dot_dtype=dot_dtype)
    else:
        g = gather(x_refs[0], lc, base=bases[0], chunk=chunk, parts=parts,
                   dot_dtype=dot_dtype)
        for ref, b in zip(x_refs[1:], bases[1:]):
            g = g + gather(ref, lc, base=b, chunk=chunk, parts=parts,
                           dot_dtype=dot_dtype)
    return reduce_rows(v * g, lr, rows, dot_dtype)


#: The most scoped VMEM a kernel asks for: what one v5e core can grant of
#: its 128 MiB, less room for the compiler's own.
VMEM_CAP = 100 << 20


def vmem_limit(nbytes: int) -> int:
    """Scoped-VMEM request for a kernel whose buffers total ``nbytes``.

    Twice the estimate plus headroom for Mosaic's temporaries, clamped to
    :data:`VMEM_CAP`.
    """
    return int(min(max(2 * nbytes + (8 << 20), 16 << 20), VMEM_CAP))
