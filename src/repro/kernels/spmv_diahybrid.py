"""Pallas TPU kernel for the DIA plane of the partially-diagonal hybrid.

Mapping:
  * one row block       → one grid step ([n_diag, row_tile] value block,
    ``row_tile`` a 128-multiple so the ``[B, row_tile]`` output is lane-dense)
  * x[col] per diagonal → one aligned load of the x window the block's
    diagonals can reach, then a static lane shift per diagonal (col = row +
    offset, so a diagonal's x reads are unit-stride — no gather at all, the
    whole point of extracting dense diagonals)
  * accumulation        → per-slot f32 products, reduced over the diagonal
    axis with ``jnp.sum`` — the formulation the oracle (``ref._dia_plane``)
    uses too, so both reduce each row's products in diagonal order

x arrives transposed (``[B, L]``) and extended with a ``lead = max(0,
−min_offset)`` zero margin on the left and zeros on the right, so every
shifted slice is in range and off-matrix reads are inert zeros (matching the
container's zeroed plane).  The CSR remainder is NOT handled here — ops.py
adds it through the CSR segment-sum path after the launch, per the hybrid's
design.

x is held whole in VMEM (:data:`~repro.kernels.gather.WHOLE_X_MAX_COLS`,
enforced by ``prepare``), but each grid step reads only an
``row_tile + span`` window of it, so the work per step is O(n_diag ·
row_tile), not O(n) — diagonal structure restores the locality that Band-k
windows give CSR-k.

Checked in interpret mode against ``ref.spmv_diahybrid``
(tests/test_irregular_formats.py) and compiled for v5e at the whole-x limit
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import LANE, resolve_interpret, round_up, vmem_limit


def _kernel(
    diag_ref,  # [n_diag, RT]
    x_ref,     # [B, L] extended x
    y_ref,     # [B, RT]
    *,
    shifts: Tuple[int, ...],
    row_tile: int,
    span: int,
):
    i0 = pl.multiple_of(pl.program_id(0) * row_tile, LANE)
    xw = x_ref[:, pl.ds(i0, row_tile + span)]                # [B, RT + span]
    width = row_tile + span
    d = diag_ref[...].astype(jnp.float32)                    # [n_diag, RT]
    contrib = jnp.stack([                                    # static unroll
        d[k:k + 1] * pltpu.roll(xw, (width - s) % width, 1)[:, :row_tile]
        for k, s in enumerate(shifts)
    ])                                                       # [n_diag, B, RT]
    y_ref[...] = jnp.sum(contrib, axis=0).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("offsets", "lead", "row_tile", "interpret")
)
def spmv_dia_pallas(
    diag_vals: jax.Array,  # [n_diag, m_pad] f32 | bf16
    x_ext: jax.Array,      # [L] or [L, B] extended x (lead margin + right pad)
    *,
    offsets: Tuple[int, ...],
    lead: int,
    row_tile: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the DIA-plane kernel over all row blocks.

    Args:
      diag_vals: [n_diag, m_pad] plane, rows padded to a ``row_tile``
        multiple (padding rows are zero → inert).
      x_ext: f32 extended x from ops.py: ``lead`` zeros, then x, zeros on
        the right so that ``L ≥ m_pad + round_up(max_offset + lead, 128)``.
      offsets / lead / row_tile: static geometry (offsets ascending,
        ``row_tile`` a 128-multiple).

    Returns:
      The DIA-plane partial y of [m_pad] (resp. [m_pad, B]) in f32; the
      caller truncates to m and adds the CSR remainder.
    """
    interpret = resolve_interpret(interpret)
    vector = x_ext.ndim == 1
    xT = x_ext[None, :] if vector else x_ext.T                     # [B, L]
    B, L = xT.shape
    n_diag, m_pad = diag_vals.shape
    shifts = tuple(off + lead for off in offsets)
    span = round_up(max(max(shifts), 1), LANE)
    kernel = functools.partial(
        _kernel, shifts=shifts, row_tile=row_tile, span=span
    )
    vmem = (2 * n_diag * row_tile * 4 + 2 * max(B, 8) * L * 4
            + (n_diag + 2) * max(B, 8) * (row_tile + span) * 4)
    y = pl.pallas_call(
        kernel,
        grid=(m_pad // row_tile,),
        in_specs=[
            pl.BlockSpec((n_diag, row_tile), lambda t: (0, t)),
            pl.BlockSpec((B, L), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, row_tile), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((B, m_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
        name="spmv_dia",
    )(diag_vals, xT)
    return y[0] if vector else y.T
