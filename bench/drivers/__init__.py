"""The general traffic generator: drives a prepared operator as a traffic file says.

A traffic file (``traffic/<name>.json``) names a ``driver`` and its
parameters; a new mix of an existing driver is a new data file only, and a
new driver is a module ``drivers/<driver>.py`` found by that name.  Each
defines ``Driver(op, mat, traffic, seed, solver=None)`` with one surface:

* ``warm()`` runs every shape the window uses;
* ``window(seconds, span)`` measures and returns host-clock figures
  (``attempted``, and what the end-to-end and per-layer metrics read);
* ``answers()`` copies the checked answers to the host and drops the
  device state;
* ``checks(ref, answers)`` compares them with the reference and returns
  ``{name: (value, limit)}`` plus the count of answers over their limit.

``solver`` replaces the library's solver where a driver runs one (the
control runs the plain reference solver); drivers without one ignore it.
"""
from __future__ import annotations

import importlib

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def worst(values) -> float:
    """The largest value; NaN if any is NaN; inf when there is none."""
    if not values:
        return float("inf")
    arr = np.asarray(values, np.float64)
    return float("nan") if np.isnan(arr).any() else float(arr.max())


def driver_class(name: str):
    if not name.isidentifier():
        raise ValueError(f"traffic driver {name!r} is not a module name")
    return importlib.import_module(f"{__name__}.{name}").Driver


def make_driver(op, mat, traffic: dict, seed: int, solver=None):
    """The traffic file's driver over ``op``."""
    return driver_class(traffic["driver"])(op, mat, traffic, seed, solver=solver)
