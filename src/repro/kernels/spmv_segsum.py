"""Pallas TPU kernel for speculative segmented-sum CSR SpMV.

Mapping, following the CSR-k kernel's idiom (spmv_csrk.py):
  * equal-size nnz chunks → tiles; :data:`TILES_PER_STEP` per grid step
  * x[col_idx] gather     → one-hot matmuls on the MXU over the whole x
  * per-segment sum       → the shared one-hot reduce, [S] slots → [R]
    speculative partials (:mod:`repro.kernels.gather`)

The kernel is *speculative* in Liu & Vinter's sense: each chunk reduces its
slots by local segment id without knowing whether a segment is a whole row
or a fragment of one.  The cheap patch happens outside the launch (ops.py):
one scatter-add of the ``[T · R]`` partials through ``seg_row`` sums every
row's fragments, however many chunks it spans.  No per-row padding exists
anywhere, so the launch cost is O(nnz) even for empty-row / power-law
matrices — the regime where SELL-C-σ's per-chunk width padding explodes.

Like SELL-C-σ there is no banded-window guarantee, so x is held whole in
VMEM (:data:`~repro.kernels.gather.WHOLE_X_MAX_COLS`, enforced by
``prepare``); the registry routes accordingly.

Checked in interpret mode against ``ref.spmv_segsum``
(tests/test_irregular_formats.py sweeps the adversarial families and dtypes)
and compiled for v5e at the whole-x limit (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (
    dequant, gather_dtype, pad_tiles, pick_chunk, resolve_interpret, round_up,
    split_f32, tile_rows, vmem_limit,
)

#: Chunks per grid step: one aligned ``[8, S]`` slab per slot stream.
TILES_PER_STEP = 8


def _kernel(
    vals_ref,   # [TB, S]
    col_ref,    # [TB, S]
    lseg_ref,   # [TB, S]
    *rest,      # ([scale_ref [TB, G],] x_ref [P·B, n_pad], y_ref [TB·B, Rp])
    tiles: int,
    batch: int,
    rows: int,
    chunk: int,
    parts: int,
    gather_mode: str,
    has_scale: bool,
    dot_dtype,
):
    scale_ref = rest[0] if has_scale else None
    x_ref, y_ref = rest[-2:]
    v = dequant(vals_ref[...], None if scale_ref is None else scale_ref[...])
    lc, lseg = col_ref[...], lseg_ref[...]
    for j in range(tiles):
        y = tile_rows(
            v[j:j + 1], lc[j:j + 1], lseg[j:j + 1], (x_ref,), (0,),
            rows=rows, chunk=chunk, parts=parts, gather_mode=gather_mode,
            dot_dtype=dot_dtype,
        )
        y_ref[j * batch:(j + 1) * batch, :] = y.astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("segs_per_chunk", "gather_chunk", "gather_mode", "interpret"),
)
def spmv_segsum_pallas(
    vals: jax.Array,      # [T, S]
    col_idx: jax.Array,   # [T, S]
    local_seg: jax.Array, # [T, S]
    x_padded: jax.Array,  # [n_pad] or [n_pad, B] — padded to a 128 multiple
    val_scale: jax.Array | None = None,  # [T, S/group] f32, int8 values only
    *,
    segs_per_chunk: int,
    gather_chunk: int = 512,
    gather_mode: str = "onehot",
    interpret: bool | None = None,
) -> jax.Array:
    """Run the segmented-sum kernel over all chunks.

    Args:
      vals / col_idx / local_seg: [T, S] equal-size chunk streams from
        :class:`repro.sparse.segsum.SegSumCSR` (tail padding slots carry
        val 0 and are inert).  ``vals`` may be f32, bf16, or int8; int8
        requires ``val_scale`` (per-group f32 scales, dequantized in-kernel
        with f32 accumulation).
      x_padded: [n_pad] vector or [n_pad, B] block, padded to a 128 multiple
        by ops.py.
      segs_per_chunk: R, static from the container.

    Returns:
      Speculative partials of [T · R] (resp. [T · R, B]) in (chunk, local
      segment) order.  The caller MUST apply the carry/patch pass — a
      scatter-add through ``seg_row`` (see :func:`repro.kernels.ops.
      spmv_segsum`) — to obtain y; partials of rows spanning chunks are not
      yet summed here.
    """
    interpret = resolve_interpret(interpret)
    vector = x_padded.ndim == 1
    xT = x_padded[None, :] if vector else x_padded.T               # [B, n_pad]
    B, n_pad = xT.shape
    xg, parts = split_f32(xT)
    T, S = vals.shape
    R = segs_per_chunk
    Rp = round_up(R, 8)
    TB = TILES_PER_STEP
    steps = -(-T // TB)
    vals, col_idx, local_seg, val_scale = pad_tiles(
        [vals, col_idx, local_seg, val_scale], TB
    )
    chunk = pick_chunk(n_pad, gather_chunk)

    tile_spec = pl.BlockSpec((TB, S), lambda t: (t, 0))
    in_specs = [tile_spec] * 3
    operands = [vals, col_idx, local_seg]
    if val_scale is not None:
        in_specs.append(pl.BlockSpec((TB, val_scale.shape[1]), lambda t: (t, 0)))
        operands.append(val_scale)
    kernel = functools.partial(
        _kernel, tiles=TB, batch=B, rows=Rp, chunk=chunk, parts=parts,
        gather_mode=gather_mode, has_scale=val_scale is not None,
        dot_dtype=gather_dtype(interpret),
    )
    vmem = (2 * 4 * TB * S * 4 + 2 * max(parts * B, 16) * n_pad * 4
            + 3 * chunk * S * 4)
    y = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=in_specs + [pl.BlockSpec((parts * B, n_pad), lambda t: (0, 0))],
        out_specs=pl.BlockSpec((TB * B, Rp), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * TB * B, Rp), x_padded.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
        name="spmv_segsum",
    )(*operands, xg)
    y = y[:T * B].reshape(T, B, Rp)[:, :, :R]
    y = y.transpose(0, 2, 1).reshape(T * R, B)
    return y[:, 0] if vector else y
