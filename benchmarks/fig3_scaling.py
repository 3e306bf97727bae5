"""Paper Figure 3: extraction tasks (a)-(g) — horizontal scaling + the
normalized-SQL (SAS-Oracle stand-in) baseline.

Two reproductions:
  1. *Baseline comparison* (the paper's dashed lines): each task is run
     against (i) the SCALPEL3 flat columnar table (one up-front flatten) and
     (ii) the normalized star schema with joins at query time — isolating
     exactly the paper's variable.  Wall-clock on this container is
     meaningful here (same device, same data).
  2. *Horizontal scaling* (the solid lines): tasks re-run with the data
     row-sharded over n ∈ {1,2,4,8} forced host devices, one child process
     per n.  The children are pinned to the CPU (``JAX_PLATFORMS=cpu``):
     the parent has already touched JAX and, on a TPU host, holds the chip.
     Their rows are labelled ``platform=cpu``; the scaling evidence is
     per-shard work (rows/bytes per executor ~ 1/n), with CPU wall time for
     transparency only.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict, List

import jax
import numpy as np

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.core import (  # noqa: E402
    DCIR_SCHEMA, PMSI_MCO_SCHEMA, diagnoses, drug_dispenses, exposures,
    flatten_star, fractures, hospital_stays, lookup_join, medical_acts_dcir,
    medical_acts_pmsi, patients, sort_events,
)
from repro.core.columnar import ColumnarTable  # noqa: E402
from repro.data.synthetic import SyntheticConfig, generate_dcir, generate_pmsi  # noqa: E402

TASKS = ("a_patients", "b_drugs", "c_prevalent", "d_exposures",
         "e_acts", "f_diagnoses", "g_fractures")


def _block(x):
    jax.block_until_ready(jax.tree.leaves(x))
    return x


def _time(fn: Callable, repeat: int = 3) -> float:
    fn()  # warmup/compile
    ts = []
    for _ in range(repeat):
        t0 = time.time()
        _block(fn())
        ts.append(time.time() - t0)
    return float(np.median(ts))


def make_tasks(cfg: SyntheticConfig, dcir, pmsi, flat_dcir, flat_pmsi,
               normalized: bool) -> Dict[str, Callable]:
    """Task set (a)-(g).  normalized=True re-joins the star schema inside
    every query (the SAS-Oracle execution model)."""
    P = cfg.n_patients

    def dcir_source():
        if not normalized:
            return flat_dcir
        return flatten_star(DCIR_SCHEMA, dcir)[0]   # join at query time

    def pmsi_source():
        if not normalized:
            return flat_pmsi
        return flatten_star(PMSI_MCO_SCHEMA, pmsi)[0]

    prevalent_codes = list(range(65))

    def c_prevalent():
        drugs = drug_dispenses(codes=prevalent_codes)(dcir_source())
        from repro.core.transformers import observation_period
        first = observation_period(drugs, P)
        return first.filter(first.columns["start"] < 14_600 + 365)

    def g_fract():
        acts = medical_acts_dcir()(dcir_source())
        diag = diagnoses()(pmsi_source())
        return fractures(acts, diag, list(range(30)), list(range(40)))

    return {
        "a_patients": lambda: patients(dcir["IR_BEN"]),
        "b_drugs": lambda: drug_dispenses()(dcir_source()),
        "c_prevalent": c_prevalent,
        "d_exposures": lambda: exposures(
            drug_dispenses()(dcir_source()), P, purview_days=60),
        "e_acts": lambda: medical_acts_pmsi()(pmsi_source()),
        "f_diagnoses": lambda: diagnoses()(pmsi_source()),
        "g_fractures": g_fract,
    }


def run_baseline(n_patients: int = 2_000, seed: int = 0) -> List[Dict]:
    """Reproduction 1: flat-columnar vs normalized-join per task."""
    cfg = SyntheticConfig(n_patients=n_patients, seed=seed)
    dcir, pmsi = generate_dcir(cfg), generate_pmsi(cfg)
    flat_dcir, _ = flatten_star(DCIR_SCHEMA, dcir)
    flat_pmsi, _ = flatten_star(PMSI_MCO_SCHEMA, pmsi)
    rows = []
    scalpel = make_tasks(cfg, dcir, pmsi, flat_dcir, flat_pmsi, normalized=False)
    sqlish = make_tasks(cfg, dcir, pmsi, flat_dcir, flat_pmsi, normalized=True)
    for name in TASKS:
        t_flat = _time(scalpel[name])
        t_norm = _time(sqlish[name])
        rows.append({
            "task": name,
            "scalpel3_s": round(t_flat, 4),
            "normalized_join_s": round(t_norm, 4),
            "speedup": round(t_norm / max(t_flat, 1e-9), 2),
        })
    return rows


_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import (DCIR_SCHEMA, flatten_star, drug_dispenses,
                        medical_acts_dcir, exposures)
from repro.data.synthetic import SyntheticConfig, generate_dcir

n = {n_shards}
cfg = SyntheticConfig(n_patients={n_patients}, seed=0)
dcir = generate_dcir(cfg)
flat, _ = flatten_star(DCIR_SCHEMA, dcir)
mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
sh = NamedSharding(mesh, P("data"))
rep = NamedSharding(mesh, P())
cap = -(-flat.capacity // n) * n
flat = flat.pad_to(cap)
flat = jax.tree.map(
    lambda x: jax.device_put(x, sh if getattr(x, "ndim", 0) >= 1 else rep), flat)

ext = drug_dispenses()
acts = medical_acts_dcir()
def task_b(t): return ext(t, compact=False)
def task_e(t): return acts(t, compact=False)
def task_d(t): return exposures(ext(t, compact=False), cfg.n_patients, 60)

out = {{}}
for name, fn in (("b_drugs", task_b), ("e_acts", task_e), ("d_exposures", task_d)):
    jfn = jax.jit(fn)
    r = jfn(flat); jax.block_until_ready(jax.tree.leaves(r))
    ts = []
    for _ in range(3):
        t0 = time.time(); r = jfn(flat); jax.block_until_ready(jax.tree.leaves(r))
        ts.append(time.time() - t0)
    c = jfn.lower(flat).compile()
    ca = c.cost_analysis() or {{}}
    out[name] = {{
        "wall_s": float(np.median(ts)),
        "per_device_flops": float(ca.get("flops", 0.0)),
        "per_device_bytes": float(ca.get("bytes accessed", 0.0)),
    }}
print(json.dumps(out))
"""


def run_scaling(n_patients: int = 2_000,
                shard_counts=(1, 2, 4, 8)) -> List[Dict]:
    """Reproduction 2: per-executor work vs shard count (CPU child
    processes on forced host devices; see the module docstring)."""
    rows = []
    for n in shard_counts:
        code = _WORKER.format(src=SRC, n_shards=n, n_patients=n_patients)
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = SRC
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        if out.returncode != 0:
            rows.append({"shards": n, "error": out.stderr[-500:]})
            continue
        data = json.loads(out.stdout.strip().splitlines()[-1])
        for task, d in data.items():
            rows.append({"shards": n, "task": task, "platform": "cpu", **{
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in d.items()}})
    return rows


if __name__ == "__main__":
    print("== baseline (flat vs normalized-join) ==")
    for r in run_baseline():
        print(r)
    print("== scaling ==")
    for r in run_scaling():
        print(r)
