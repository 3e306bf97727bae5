"""Traffic mixes and the seeded draws they share.

A traffic mix is a data file, ``chipbench/traffic/<name>.json``, holding
only parameters.  Its ``kind`` names the loop that drives the cell,
``chipbench/loops/<kind>.py``, which reads the rest of the mix and says
in its docstring how it drives the cell.

A new mix of an existing kind is a new data file.  A new kind is a new loop
file beside the others; the harness finds it by the name, and no file that
is already here changes.
"""
from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose, from the full-width seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def popular_codes(rng: np.random.Generator, n: int, top: int,
                  exponent: float) -> np.ndarray:
    """``n`` distinct codes among the ``top`` most popular, drawn by Zipf
    popularity (the code id is its popularity rank)."""
    w = 1.0 / np.arange(1, top + 1, dtype=np.float64) ** exponent
    return np.sort(rng.choice(top, size=n, replace=False, p=w / w.sum())
                   ).astype(np.int32)
