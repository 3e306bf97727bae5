"""The benchmark's one traffic generator.

A traffic mix is a JSON file under ``chipbench/traffic/`` holding only
parameters; its ``kind`` says which loop drives the cell:

- ``closed_study``: one client runs the configuration's study again as soon
  as the previous one is on the host, for the whole window.

A new kind is a new loop beside ``study_cell.py`` and a branch of
``harness._driver``.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Mapping

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


def study_codes(seed: int, study: Mapping, zipf: float) -> Dict[str, np.ndarray]:
    """The study's code lists for this seed: the drugs of interest by
    popularity among the most popular codes, the fracture codes uniformly
    among codes of middling rank (an outcome is rare)."""
    r = rng_for(seed, 1)
    lo, hi = study["fracture_code_ranks"]

    def rare(n):
        return np.sort(r.choice(np.arange(lo, hi), size=n, replace=False)
                       ).astype(np.int32)

    return {"prevalent": popular_codes(r, int(study["prevalent_drugs"]),
                                       int(study["code_pool"]), zipf),
            "fracture_acts": rare(int(study["fracture_act_codes"])),
            "fracture_diags": rare(int(study["fracture_diag_codes"]))}
