"""Host mesh construction.

Defined as a function (never a module-level constant) so importing this
module never touches jax device state — smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    model = max(min(model, n), 1)
    data = n // model
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))
