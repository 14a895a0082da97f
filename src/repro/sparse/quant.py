"""Grouped-scale int8 quantization of the tile views' value streams.

The same per-group symmetric-scale idiom GPTQ-style kernels use: values are
split into fixed-size groups along the streaming axis, each group stores one
f32 scale = max|v|/127 and int8 codes q = round(v/scale).  The sparse tile
views (:mod:`repro.sparse.csrk`, :mod:`repro.sparse.sellcs`,
:mod:`repro.sparse.segsum`) quantize their value streams with these helpers
so the Pallas kernels move 1 byte per nonzero value instead of 4;
accumulation stays f32 (dequantize-then-multiply inside the kernel).
"""
from __future__ import annotations

import numpy as np

#: Slots per int8 scale group (= the TPU lane count; slot counts are always
#: padded to multiples of 128, so groups tile the slot axis exactly).
INT8_GROUP = 128


def quantize_int8_grouped(vals, group: int = INT8_GROUP):
    """Symmetric per-group int8 quantization along the last axis (host-side).

    Args:
      vals: numpy array whose last-axis length is a multiple of ``group``
        (both tile views pad slots to 128 multiples, so this always holds).
      group: values per scale group.

    Returns:
      ``(q, scales)`` — ``q`` int8 with ``vals.shape``; ``scales`` float32
      with the last axis reduced by ``group``.  All-zero groups get scale 1.0
      so dequantization stays exact for padding slots.
    """
    v = np.asarray(vals, np.float32)
    if v.shape[-1] % group:
        raise ValueError(f"last axis {v.shape[-1]} not a multiple of group {group}")
    g = v.reshape(v.shape[:-1] + (v.shape[-1] // group, group))
    amax = np.abs(g).max(axis=-1)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.rint(g / scales[..., None]).clip(-127, 127).astype(np.int8)
    return q.reshape(v.shape), scales


def dequantize_int8_grouped(q, scales, group: int = INT8_GROUP):
    """Inverse of :func:`quantize_int8_grouped` (host-side numpy)."""
    q = np.asarray(q, np.float32)
    s = np.repeat(np.asarray(scales, np.float32), group, axis=-1)
    return q * s
