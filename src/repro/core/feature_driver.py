"""FeatureDriver: cohorts -> ML tensor formats (paper §3.5).

The paper exports Spark dataframes to numpy / tf / torch tensors with sanity
checks.  Here the targets are JAX arrays feeding the in-repo LM stack:

  * ``dense_features``   — (patients × time-buckets × features) scatter-add
                           tensor (the ConvSCCS-style longitudinal design
                           matrix of paper ref. [27]);
  * ``token_sequences``  — per-patient event-code token streams for language
                           models (the hand-off to the assigned architectures:
                           the claims history *is* the training corpus);
  * ``to_numpy``         — host export for external libraries.

Sanity checks mirror the paper: events outside the cohort window or with
inconsistent dates are counted and excluded, never silently kept.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cohort import Cohort
from repro.core.columnar import ColumnarTable, cumsum, is_null
from repro.core.events import Category

__all__ = ["FeatureDriver", "TokenizerSpec"]

# LM special tokens for event streams
PAD, BOS, EOS = 0, 1, 2
N_SPECIAL = 8  # room for time-gap buckets etc.


@dataclasses.dataclass(frozen=True)
class TokenizerSpec:
    """Event -> token mapping: token = offset[category] + value (clipped)."""

    category_offsets: Dict[int, int]
    category_sizes: Dict[int, int]

    @classmethod
    def default(cls, n_drug: int = 512, n_act: int = 512, n_diag: int = 512) -> "TokenizerSpec":
        offs, sizes, cur = {}, {}, N_SPECIAL
        for cat, n in ((Category.DRUG_DISPENSE, n_drug), (Category.MEDICAL_ACT, n_act),
                       (Category.DIAGNOSIS, n_diag), (Category.HOSPITAL_STAY, 256),
                       (Category.EXPOSURE, n_drug), (Category.OUTCOME_FRACTURE, 64)):
            offs[cat], sizes[cat] = cur, n
            cur += n
        return cls(offs, sizes)

    @property
    def vocab_size(self) -> int:
        return N_SPECIAL + sum(self.category_sizes.values())


class FeatureDriver:
    def __init__(self, cohort: Cohort, patients: Optional[ColumnarTable] = None):
        if cohort.events is None:
            raise ValueError("FeatureDriver needs a cohort with events")
        self.cohort = cohort
        self.patients = patients
        self.checks: Dict[str, int] = {}

    # -- sanity checks ---------------------------------------------------------
    def _checked_events(self) -> ColumnarTable:
        ev = self.cohort.events
        t0, t1 = self.cohort.window
        start = ev.columns["start"]
        end = ev.columns["end"]
        in_window = (start >= t0) & (start < t1)
        dates_ok = is_null(end) | (end >= start)
        keep = in_window & dates_ok
        evv = ev.valid_bool()
        self.checks = {
            "events_total": int(ev.count),
            "events_out_of_window": int((evv & ~in_window).sum()),
            "events_bad_dates": int((evv & ~dates_ok).sum()),
        }
        return ev.filter(keep)

    # -- dense longitudinal tensor ----------------------------------------------
    def dense_features(self, n_buckets: int, bucket_days: int, n_features: int,
                       feature_of_value: Optional[jax.Array] = None) -> jax.Array:
        """(n_patients, n_buckets, n_features) scatter-add design matrix."""
        ev = self._checked_events()
        P = self.cohort.n_patients
        t0 = self.cohort.window[0]
        b = jnp.clip((ev.columns["start"] - t0) // bucket_days, 0, n_buckets - 1)
        v = ev.columns["value"]
        f = feature_of_value[jnp.clip(v, 0, feature_of_value.shape[0] - 1)] \
            if feature_of_value is not None else jnp.clip(v, 0, n_features - 1)
        pid = jnp.clip(ev.columns["patient_id"], 0, P - 1)
        flat_idx = (pid * n_buckets + b) * n_features + f
        flat_idx = jnp.where(ev.valid_bool(), flat_idx, P * n_buckets * n_features)
        out = jnp.zeros((P * n_buckets * n_features,), jnp.float32)
        out = out.at[flat_idx].add(ev.columns["weight"], mode="drop")
        return out.reshape(P, n_buckets, n_features)

    # -- LM token streams --------------------------------------------------------
    def token_sequences(self, seq_len: int, spec: Optional[TokenizerSpec] = None
                        ) -> Tuple[jax.Array, jax.Array]:
        """(n_patients, seq_len) int32 tokens + bool mask, time-ordered.

        Each patient's claims history becomes a token stream
        ``BOS e1 e2 ... EOS PAD...``; overflowing events are truncated (kept
        count is in ``self.checks``).  This is the corpus the assigned LM
        architectures train on in ``examples/train_lm.py``.
        """
        spec = spec or TokenizerSpec.default()
        ev = self._checked_events().sort_by(["patient_id", "start", "category", "value"])
        P = self.cohort.n_patients

        cat = ev.columns["category"]
        val = ev.columns["value"]
        tok = jnp.full((ev.capacity,), PAD, jnp.int32)
        for c, off in spec.category_offsets.items():
            n = spec.category_sizes[c]
            tok = jnp.where(cat == c, off + jnp.clip(val, 0, n - 1), tok)
        known = tok != PAD

        pid = ev.columns["patient_id"]
        evv = ev.valid_bool()
        ok = evv & known
        # position within patient = rank among valid rows of the same patient
        seg = jnp.where(ok, pid, P)
        one = ok.astype(jnp.int32)
        cum = cumsum(one) - one  # exclusive prefix count of valid rows
        # min of exclusive-cumsum within a segment = count before segment start
        big = jnp.int32(1 << 30)
        seg_start_count = jnp.full((P + 1,), big, jnp.int32).at[seg].min(cum, mode="drop")
        pos = cum - seg_start_count[jnp.clip(seg, 0, P)]
        slot = jnp.where(ok & (pos < seq_len - 2), pid * seq_len + 1 + pos, P * seq_len)

        toks = jnp.full((P * seq_len,), PAD, jnp.int32).at[slot].set(tok, mode="drop")
        toks = toks.reshape(P, seq_len).at[:, 0].set(BOS)
        n_per = jax.ops.segment_sum(one, jnp.clip(seg, 0, P), num_segments=P + 1)[:P]
        eos_pos = jnp.clip(n_per + 1, 1, seq_len - 1)
        toks = toks.at[jnp.arange(P), eos_pos].set(EOS)
        mask = jnp.arange(seq_len)[None, :] <= eos_pos[:, None]
        self.checks["events_truncated"] = int((evv & known & (pos >= seq_len - 2)).sum())
        return toks, mask

    # -- host export --------------------------------------------------------------
    def to_numpy(self, **kw) -> Dict[str, np.ndarray]:
        X = self.dense_features(**kw)
        return {"features": np.asarray(X), "subjects": np.asarray(self.cohort.subjects_mask())}
