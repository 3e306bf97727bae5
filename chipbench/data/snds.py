"""The benchmark's SNDS star (DCIR + PMSI-MCO), generated on the device.

Same tables and columns as ``repro.core.schema`` (DCIR: ER_PRS, ER_PHA,
ER_CAM, IR_BEN; PMSI-MCO: MCO_B, MCO_D, MCO_A), at the paper's density of
about 1,034 rows per patient over 3 years (15 B events over 14.5 M patients).
The benchmark owns this copy so that no change to ``repro.data.synthetic``
moves the yardstick.

Every table size, every time slice's row count and every 1:N join's output
size is the same for every seed: the per-patient flow counts, the per-stay
(diagnoses, acts) pairs, the flow dates and the flow kinds are fixed
multisets (drawn once from ``SIZES_SEED``) that ``--seed`` only permutes.
So the planner stamps the same capacities on every run, every seed reuses
the compiled programs, and the work per run does not move with the seed.
Codes, patients of stays, stay dates, demographics and deaths come from the
seed.  Code popularity is Zipf over each vocabulary, the code id being the
popularity rank (a frequency-ordered dictionary).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.columnar import NULL_INT, ColumnarTable

#: seed of the fixed size multisets; never the run's seed
SIZES_SEED = 20_191_007
NULL = int(NULL_INT)


@dataclasses.dataclass(frozen=True)
class StarSpec:
    """The generator's parameters, read from a configuration file."""

    n_patients: int
    flows_per_patient: float
    flows_lognormal_sigma: float
    max_flows_per_patient: int
    stays_per_patient: float
    diags_per_stay: float
    acts_per_stay: float
    n_drug_codes: int
    n_act_codes: int
    n_diag_codes: int
    n_atc_classes: int
    zipf_exponent: float
    p_flow_is_drug: float
    p_flow_is_act: float
    p_null_code: float
    p_dead: float
    study_start: int
    follow_up_days: int

    @classmethod
    def from_config(cls, cfg: Mapping) -> "StarSpec":
        return cls(n_patients=int(cfg["n_patients"]), **cfg["generator"])

    @property
    def study_end(self) -> int:
        return self.study_start + self.follow_up_days


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The seed-independent part of the star: fixed multisets and totals."""

    flow_counts: np.ndarray     # (n_patients,) flows per patient, sorted
    stay_pairs: np.ndarray      # (n_stays, 2) diagnoses and acts per stay
    n_flows: int
    n_pha: int
    n_cam: int
    n_stays: int
    n_diag: int
    n_act: int

    def rows(self, n_patients: int) -> int:
        """Valid rows of the whole star (all seven tables)."""
        return (self.n_flows + self.n_pha + self.n_cam + n_patients
                + self.n_stays + self.n_diag + self.n_act)


def sizes(spec: StarSpec) -> Sizes:
    rng = np.random.default_rng(SIZES_SEED)
    n = spec.n_patients
    sigma = spec.flows_lognormal_sigma
    mu = np.log(spec.flows_per_patient) - sigma ** 2 / 2
    counts = np.clip(np.rint(rng.lognormal(mu, sigma, size=n)), 1,
                     spec.max_flows_per_patient).astype(np.int64)
    # scale the draw so the total is exactly flows_per_patient * n
    target = int(round(spec.flows_per_patient * n))
    while counts.sum() != target:
        diff = target - int(counts.sum())
        idx = rng.integers(0, n, size=min(abs(diff), n))
        step = np.sign(diff)
        counts[idx] = np.clip(counts[idx] + step, 1,
                              spec.max_flows_per_patient)
    n_stays = max(1, int(round(spec.stays_per_patient * n)))
    n_diag = np.maximum(1, rng.poisson(spec.diags_per_stay, size=n_stays))
    n_act = rng.poisson(spec.acts_per_stay, size=n_stays)
    pairs = np.stack([n_diag, n_act], axis=1).astype(np.int32)
    n_flows = int(counts.sum())
    return Sizes(flow_counts=np.sort(counts).astype(np.int32),
                 stay_pairs=pairs, n_flows=n_flows,
                 n_pha=int(round(spec.p_flow_is_drug * n_flows)),
                 n_cam=int(round(spec.p_flow_is_act * n_flows)),
                 n_stays=n_stays, n_diag=int(n_diag.sum()),
                 n_act=int(n_act.sum()))


def zipf_cdf(n_codes: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_codes + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf.astype(np.float32)


def seed_words(seed: int) -> np.ndarray:
    """``--seed`` (any non-negative integer below 2**64) as two uint32."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _zipf(key, n: int, cdf: jax.Array) -> jax.Array:
    u = jax.random.uniform(key, (n,), jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       cdf.shape[0] - 1).astype(jnp.int32)


def _with_nulls(key, codes: jax.Array, p: float) -> jax.Array:
    return jnp.where(jax.random.bernoulli(key, p, codes.shape),
                     jnp.int32(NULL), codes)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _generate(spec: StarSpec, totals: tuple, words, flow_counts, stay_pairs,
              drug_cdf, act_cdf, diag_cdf):
    n = spec.n_patients
    n_flows, n_pha, n_cam, n_stays, n_diag, n_act = totals
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    k = iter(jax.random.split(key, 32))
    t0, days = spec.study_start, spec.follow_up_days
    i32 = jnp.int32

    # --- DCIR: ER_PRS, one row per cash flow, in random row order --------
    counts = flow_counts[jax.random.permutation(next(k), n)]
    by_patient = jnp.repeat(jnp.arange(n, dtype=i32), counts,
                            total_repeat_length=n_flows)
    patient_id = by_patient[jax.random.permutation(next(k), n_flows)]
    per_day = -(-n_flows // days)
    grid = t0 + jnp.arange(n_flows, dtype=i32) // i32(per_day)
    execution_date = grid[jax.random.permutation(next(k), n_flows)]
    kind = jax.random.permutation(next(k), n_flows)
    is_drug = kind < n_pha
    is_act = (kind >= n_pha) & (kind < n_pha + n_cam)
    flow_id = jnp.arange(n_flows, dtype=i32)
    prestation = jax.random.randint(next(k), (n_flows,), 1000, 1100, i32)
    amount = jnp.round(18.0 * (jax.random.exponential(next(k), (n_flows,))
                               + jax.random.exponential(next(k), (n_flows,))),
                       2).astype(jnp.float32)

    pha_flow = jnp.nonzero(is_drug, size=n_pha)[0].astype(i32)
    cip13 = _with_nulls(next(k), _zipf(next(k), n_pha, drug_cdf),
                        spec.p_null_code)
    atc = jnp.where(cip13 == NULL, i32(NULL),
                    cip13 % i32(spec.n_atc_classes))
    quantity = jax.random.randint(next(k), (n_pha,), 1, 4, i32)
    cam_flow = jnp.nonzero(is_act, size=n_cam)[0].astype(i32)
    ccam = _with_nulls(next(k), _zipf(next(k), n_cam, act_cdf),
                       spec.p_null_code)

    # --- IR_BEN: demographics; a death follows the patient's last flow ----
    gender = jax.random.randint(next(k), (n,), 1, 3, i32)
    age = (18 + 77 * jax.random.beta(next(k), 2.0, 1.6, (n,))).astype(i32)
    birth = (t0 - age * 365).astype(i32)
    last = jax.ops.segment_max(execution_date, patient_id, num_segments=n)
    dead = jax.random.bernoulli(next(k), spec.p_dead, (n,))
    death = jnp.where(dead, last + jax.random.randint(next(k), (n,), 1, 60,
                                                      i32), i32(NULL))

    # --- PMSI-MCO: stays with 1:N diagnoses and acts ---------------------
    stay_id = jnp.arange(n_stays, dtype=i32)
    stay_patient = jax.random.randint(next(k), (n_stays,), 0, n, i32)
    start = t0 + jax.random.randint(next(k), (n_stays,), 0, days - 30, i32)
    length = jnp.clip(jax.random.geometric(next(k), 0.25, (n_stays,)),
                      1, 60).astype(i32)
    ghm = jax.random.randint(next(k), (n_stays,), 0, 2000, i32)
    pairs = stay_pairs[jax.random.permutation(next(k), n_stays)]
    d_stay = jnp.repeat(stay_id, pairs[:, 0], total_repeat_length=n_diag)
    first = jnp.concatenate([jnp.ones((1,), bool), d_stay[1:] != d_stay[:-1]])
    diag_kind = jnp.where(first, i32(1),
                          jax.random.randint(next(k), (n_diag,), 2, 4, i32))
    icd = _zipf(next(k), n_diag, diag_cdf)
    a_stay = jnp.repeat(stay_id, pairs[:, 1], total_repeat_length=n_act)
    a_ccam = _zipf(next(k), n_act, act_cdf)
    act_date = start[a_stay] + jax.random.randint(next(k), (n_act,), 0, 5, i32)

    t = ColumnarTable.from_columns
    return {
        "ER_PRS": t({"flow_id": flow_id, "patient_id": patient_id,
                     "prestation_code": prestation,
                     "execution_date": execution_date, "amount": amount}),
        "ER_PHA": t({"flow_id": pha_flow, "cip13": cip13, "atc_class": atc,
                     "quantity": quantity}),
        "ER_CAM": t({"flow_id": cam_flow, "ccam_code": ccam}),
        "IR_BEN": t({"patient_id": jnp.arange(n, dtype=i32), "gender": gender,
                     "birth_date": birth, "death_date": death}),
        "MCO_B": t({"stay_id": stay_id, "patient_id": stay_patient,
                    "stay_start": start, "stay_end": start + length,
                    "ghm_code": ghm}),
        "MCO_D": t({"stay_id": d_stay, "icd_code": icd,
                    "diag_kind": diag_kind}),
        "MCO_A": t({"stay_id": a_stay, "ccam_code": a_ccam,
                    "act_date": act_date}),
    }


def generate(spec: StarSpec, seed: int) -> Dict[str, ColumnarTable]:
    """The star for ``seed``, built on the default device in one jitted
    call.  Table sizes depend on ``spec`` only."""
    sz = sizes(spec)
    totals = (sz.n_flows, sz.n_pha, sz.n_cam, sz.n_stays, sz.n_diag, sz.n_act)
    return _generate(spec, totals, jnp.asarray(seed_words(seed)),
                     jnp.asarray(sz.flow_counts), jnp.asarray(sz.stay_pairs),
                     jnp.asarray(zipf_cdf(spec.n_drug_codes,
                                          spec.zipf_exponent)),
                     jnp.asarray(zipf_cdf(spec.n_act_codes,
                                          spec.zipf_exponent)),
                     jnp.asarray(zipf_cdf(spec.n_diag_codes,
                                          spec.zipf_exponent)))


def host_copy(star: Mapping[str, ColumnarTable]) -> Dict[str, Dict[str, np.ndarray]]:
    """Host numpy columns of every table (all rows are valid at generation),
    for the reference."""
    return {name: {c: np.asarray(v) for c, v in t.columns.items()}
            for name, t in star.items()}
