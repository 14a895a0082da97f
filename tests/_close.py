"""Tolerance for kernel-vs-oracle checks that sum the same f32 products.

The Pallas kernels reduce a tile's slot products on the MXU — one-hot
matmuls in 128-slot groups — while the oracles in ``repro.kernels.ref`` use
``segment_sum``.  Both multiply the same dequantized f32 values by the same
exactly-gathered x, so only the order of the f32 additions differs, and with
it the last bits.  The bound below is a few ulps of the output's magnitude:
far tighter than any value-dtype error bound, far looser than reordering.
"""
import numpy as np

RTOL = 1e-5


def assert_reordered_sum_close(actual, desired):
    actual = np.asarray(actual, np.float32)
    desired = np.asarray(desired, np.float32)
    scale = max(float(np.abs(desired).max(initial=0.0)), 1e-30)
    np.testing.assert_allclose(actual, desired, rtol=RTOL, atol=RTOL * scale)
