"""SCALPEL-Extraction Transformers: ``List[Event] -> List[Event]`` per patient.

The paper's Transformer abstraction folds a patient's event list into complex
events (drug exposures, outcomes, follow-up...).  With events kept sorted by
``(patient, ...)`` (one sort at flatten time), every per-patient fold becomes a
*segment operation* — TPU-native, collective-free, and identical across shards
of a patient-partitioned table (DESIGN.md §2).

Implemented (paper Table 4): observation period, follow-up, trackloss,
exposures (limited/unlimited), fractures-per-body-site outcome.  The
fractures washout chain is the one sequential fold: a loop over the sorted
candidates only, never over the table's capacity.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.columnar import ColumnarTable, NULL_INT, cumsum, is_null
from repro.core.events import Category, make_events, sort_events
from repro.core.metadata import OperationLog

__all__ = [
    "observation_period",
    "follow_up",
    "trackloss",
    "exposures",
    "fractures",
]

_BIG = jnp.int32(2_000_000_000)


def _seg_min(x, seg, num, valid):
    return jax.ops.segment_min(jnp.where(valid, x, _BIG), seg, num_segments=num)


def _seg_max(x, seg, num, valid):
    return jax.ops.segment_max(jnp.where(valid, x, -_BIG), seg, num_segments=num)


def _seg_sum(x, seg, num, valid):
    return jax.ops.segment_sum(jnp.where(valid, x, 0), seg, num_segments=num)


def _clip_seg(events: ColumnarTable, n_patients: int):
    seg = jnp.clip(events.columns["patient_id"], 0, n_patients - 1)
    return jnp.where(events.valid_bool(), seg, n_patients - 1)


# ---------------------------------------------------------------------------
def observation_period(events: ColumnarTable, n_patients: int) -> ColumnarTable:
    """Per-patient [first event, last event] continuous event (Table 4)."""
    seg = _clip_seg(events, n_patients)
    ev_valid = events.valid_bool()
    first = _seg_min(events.columns["start"], seg, n_patients, ev_valid)
    last_s = _seg_max(events.columns["start"], seg, n_patients, ev_valid)
    last_e = _seg_max(
        jnp.where(is_null(events.columns["end"]), events.columns["start"], events.columns["end"]),
        seg, n_patients, ev_valid,
    )
    cnt = _seg_sum(jnp.ones_like(seg), seg, n_patients, ev_valid)
    pid = jnp.arange(n_patients, dtype=jnp.int32)
    return make_events(
        patient_id=pid, category=Category.OBSERVATION, value=jnp.zeros_like(pid),
        start=first, end=jnp.maximum(last_s, last_e), weight=cnt.astype(jnp.float32),
        valid=cnt > 0,
    )


def follow_up(
    patients: ColumnarTable,
    events: ColumnarTable,
    n_patients: int,
    study_end: int,
    delay_days: int = 0,
) -> ColumnarTable:
    """Follow-up window per patient: [first event + delay, min(death, end)].

    Mirrors the paper's Follow-up transformer (sources: patients, observation
    period, optionally trackloss/deaths).
    """
    obs = observation_period(events, n_patients)
    start = obs.columns["start"] + jnp.int32(delay_days)
    # death date scattered into a dense patient-indexed array (robust to gaps
    # in the id space and to table padding)
    pidx = jnp.where(patients.valid_bool(), patients.columns["patient_id"], n_patients)
    death = (
        jnp.full((n_patients,), NULL_INT, jnp.int32)
        .at[pidx]
        .set(patients.columns["death_date"], mode="drop")
    )
    end = jnp.where(is_null(death), jnp.int32(study_end), jnp.minimum(death, study_end))
    valid = obs.valid_bool() & (start < end)
    pid = jnp.arange(n_patients, dtype=jnp.int32)
    return make_events(
        patient_id=pid, category=Category.FOLLOW_UP, value=jnp.zeros_like(pid),
        start=start, end=end, valid=valid,
    )


def trackloss(dispenses: ColumnarTable, n_patients: int, gap_days: int) -> ColumnarTable:
    """Trackloss: a gap > ``gap_days`` between consecutive dispenses of the
    same patient marks loss of follow-up at ``last_seen + gap_days``."""
    ev = sort_events(dispenses)
    pid = ev.columns["patient_id"]
    start = ev.columns["start"]
    evv = ev.valid_bool()
    same = jnp.concatenate([jnp.zeros((1,), bool), (pid[1:] == pid[:-1]) & evv[:-1]])
    prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), start[:-1]])
    gap = jnp.where(same & evv, start - prev, 0)
    hit = gap > gap_days
    out = make_events(
        patient_id=pid, category=Category.TRACKLOSS, value=jnp.zeros_like(pid),
        start=prev + jnp.int32(gap_days), valid=hit,
    )
    # one trackloss per patient: keep the earliest
    seg = _clip_seg(out, n_patients)
    outv = out.valid_bool()
    first = _seg_min(out.columns["start"], seg, n_patients, outv)
    keep = outv & (out.columns["start"] == first[seg])
    dup = jnp.concatenate([jnp.zeros((1,), bool), (seg[1:] == seg[:-1]) & keep[:-1]])
    return out.filter(keep & ~dup)


def exposures(
    dispenses: ColumnarTable,
    n_patients: int,
    purview_days: int = 60,
    limited: bool = True,
    follow_up_events: Optional[ColumnarTable] = None,
    min_dispenses: int = 1,
) -> ColumnarTable:
    """Drug-exposure transformer (paper Table 4, 'Limited in time'/'Unlimited').

    Consecutive dispenses of the same (patient, drug) closer than
    ``purview_days`` merge into one exposure interval.  Vectorized as: sort by
    (patient, drug, date) -> boundary flags -> exposure ids by prefix sum ->
    per-exposure segment min/max/count.  The segmented-scan hot path has a
    Pallas kernel (``kernels/segment_scan``); this is the jnp oracle the
    kernel is validated against.
    """
    ev = dispenses.sort_by(["patient_id", "value", "start"])
    cap = ev.capacity
    pid, val, start = ev.columns["patient_id"], ev.columns["value"], ev.columns["start"]

    evv = ev.valid_bool()
    same_group = jnp.concatenate(
        [jnp.zeros((1,), bool), (pid[1:] == pid[:-1]) & (val[1:] == val[:-1]) & evv[:-1]]
    )
    prev_start = jnp.concatenate([jnp.zeros((1,), jnp.int32), start[:-1]])
    chained = same_group & (start - prev_start <= purview_days)
    new_exposure = evv & ~chained
    # exposure id per row (0-based); invalid rows ride along harmlessly
    eid = cumsum(new_exposure.astype(jnp.int32)) - 1
    eid = jnp.clip(eid, 0, cap - 1)

    first = _seg_min(start, eid, cap, evv)
    last = _seg_max(start, eid, cap, evv)
    n_disp = _seg_sum(jnp.ones_like(eid), eid, cap, evv)
    e_pid = _seg_max(pid, eid, cap, evv)
    e_val = _seg_max(val, eid, cap, evv)

    end = last + jnp.int32(purview_days)
    if not limited:
        if follow_up_events is None:
            raise ValueError("unlimited exposures require follow_up_events")
        fu_end = follow_up_events.sort_by(["patient_id"]).columns["end"][:n_patients]
        end = jnp.maximum(end, fu_end[jnp.clip(e_pid, 0, n_patients - 1)])

    valid = n_disp >= min_dispenses
    return make_events(
        patient_id=e_pid, category=Category.EXPOSURE, value=e_val,
        start=first, end=end, weight=n_disp.astype(jnp.float32), valid=valid,
    ).compact()


def exposures_sharded(
    dispenses: ColumnarTable,
    n_patients: int,
    mesh,
    axis_name: str = "data",
    **kw,
) -> ColumnarTable:
    """Shard-local exposures over a *patient-partitioned* event table.

    ``distributed_flatten`` keys its output on ``patient_id`` — every patient's
    events live on one shard, so the per-patient fold needs NO collectives:
    each shard runs the plain ``exposures`` transformer on its rows.  This is
    the missing scaling piece for the paper's task (d) (global sort/segment
    ops do not shard; the patient-partitioned layout makes them local).
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    # word-aligned shard blocks: the packed validity words split across the
    # mesh axis only when every shard's row block is a multiple of 32
    quantum = 32 * n
    cap = -(-dispenses.capacity // quantum) * quantum
    t = dispenses.pad_to(cap) if cap != dispenses.capacity else dispenses

    def body(cols, valid):
        local = ColumnarTable.from_columns(cols, valid=valid)
        out = exposures(local, n_patients, **kw)
        return dict(out.columns), out.valid

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)), check_vma=False,
    )
    cols, valid = fn(dict(t.columns), t.valid)
    return ColumnarTable.from_columns(cols, valid=valid)


def fractures(
    acts: ColumnarTable,
    diags: ColumnarTable,
    fracture_act_codes: Sequence[int],
    fracture_diag_codes: Sequence[int],
    n_sites: int = 8,
    washout_days: int = 90,
) -> ColumnarTable:
    """Fracture outcome (paper task (g), algorithm of ref. [9]): fracture
    candidates from medical acts + diagnoses, one outcome per body site per
    washout window.

    The greedy per-(patient, site) washout chain is order-dependent, so it is
    a genuine sequential loop (the only sequential transformer); everything
    before it is columnar.  ``sort_by`` sinks invalid rows, so the candidates
    are the sorted table's first ``count`` rows: the loop walks only those,
    its trip count the traced ``count`` (one program for every count), and
    every row past them stays dropped.
    """
    a_codes = jnp.asarray(np.asarray(fracture_act_codes, np.int32))
    d_codes = jnp.asarray(np.asarray(fracture_diag_codes, np.int32))
    a = acts.filter(jnp.isin(acts.columns["value"], a_codes))
    d = diags.filter(jnp.isin(diags.columns["value"], d_codes))
    cand = ColumnarTable.concat([a.select(["patient_id", "value", "start"]),
                                 d.select(["patient_id", "value", "start"])])
    # body-site mapping: configurable hash of the code space (stand-in for the
    # ref-[9] site tables; real deployments load a code->site mapping array).
    site = (cand.columns["value"] % jnp.int32(n_sites)).astype(jnp.int32)
    cand = cand.with_columns({"site": site})
    cand = cand.sort_by(["patient_id", "site", "start"])

    pid = cand.columns["patient_id"]
    sit = cand.columns["site"]
    dat = cand.columns["start"]

    def body(i, carry):
        prev_p, prev_s, prev_d, keep = carry
        p, s, t = pid[i], sit[i], dat[i]
        fresh = (p != prev_p) | (s != prev_s) | (t - prev_d >= washout_days)
        return (jnp.where(fresh, p, prev_p), jnp.where(fresh, s, prev_s),
                jnp.where(fresh, t, prev_d), keep.at[i].set(fresh))

    keep = jnp.zeros((cand.capacity,), bool)
    if cand.capacity:
        init = (jnp.int32(-1), jnp.int32(-1), jnp.int32(-2_000_000_000), keep)
        *_, keep = jax.lax.fori_loop(0, cand.count, body, init)

    kept = cand.filter(keep)
    return make_events(
        patient_id=kept.columns["patient_id"], category=Category.OUTCOME_FRACTURE,
        value=kept.columns["value"], start=kept.columns["start"],
        group_id=kept.columns["site"], valid=kept.valid,
    ).compact()


# --- additional transformers (paper Table 4) ---------------------------------
def drug_prescriptions(dispenses: ColumnarTable, n_patients: int,
                       refill_days: int = 30) -> ColumnarTable:
    """Drug-prescription proxy (Table 4): consecutive dispenses of the same
    drug within ``refill_days`` belong to one prescription; the event spans
    first..last dispense (weight = refill count)."""
    ex = exposures(dispenses, n_patients, purview_days=refill_days,
                   limited=True)
    # re-tag: a prescription ends at its last dispense, not +purview
    end = jnp.maximum(ex.columns["end"] - jnp.int32(refill_days),
                      ex.columns["start"])
    return ColumnarTable(
        {**ex.columns, "end": end,
         "category": jnp.full_like(ex.columns["category"], Category.DRUG_DISPENSE)},
        ex.valid, ex.count, ex.capacity,
    )


def drug_interactions(dispenses: ColumnarTable, n_patients: int,
                      window_days: int = 30) -> ColumnarTable:
    """Drug-interaction events (Table 4): two *different* drugs dispensed to
    the same patient within ``window_days``.  Columnar: sort by (patient,
    date); an interaction fires when the previous dispense is a different
    drug within the window.  value = pair hash, group = other drug."""
    ev = dispenses.sort_by(["patient_id", "start"])
    pid = ev.columns["patient_id"]
    val = ev.columns["value"]
    start = ev.columns["start"]
    evv = ev.valid_bool()
    prev_ok = jnp.concatenate([jnp.zeros((1,), bool), evv[:-1]])
    same_p = jnp.concatenate([jnp.zeros((1,), bool), pid[1:] == pid[:-1]]) & prev_ok
    prev_val = jnp.concatenate([jnp.zeros((1,), jnp.int32), val[:-1]])
    prev_start = jnp.concatenate([jnp.zeros((1,), jnp.int32), start[:-1]])
    hit = evv & same_p & (val != prev_val) & (start - prev_start <= window_days)
    pair = jnp.minimum(val, prev_val) * jnp.int32(100_003) + jnp.maximum(val, prev_val)
    out = make_events(
        patient_id=pid, category=Category.EXPOSURE, value=pair,
        start=start, group_id=prev_val, valid=hit,
    )
    return out.compact()


def _code_outcome(name_cat: int, acts: ColumnarTable, diags: ColumnarTable,
                  act_codes, diag_codes, washout_days: int) -> ColumnarTable:
    return fractures(acts, diags, act_codes, diag_codes, n_sites=1,
                     washout_days=washout_days)


def bladder_cancer(acts: ColumnarTable, diags: ColumnarTable,
                   act_codes=(101, 102), diag_codes=(188, 189),
                   washout_days: int = 365) -> ColumnarTable:
    """Bladder-cancer outcome (paper Table 4; the Neumann pioglitazone study
    [29] algorithm shape: act+diagnosis conjunction, yearly washout)."""
    return _code_outcome(Category.OUTCOME_FRACTURE, acts, diags,
                         list(act_codes), list(diag_codes), washout_days)


def infarctus(diags: ColumnarTable, diag_codes=(210, 211, 212),
              washout_days: int = 180) -> ColumnarTable:
    """Myocardial-infarction outcome (Table 4: diagnoses only)."""
    empty = diags.filter(jnp.zeros((diags.capacity,), bool))
    return _code_outcome(Category.OUTCOME_FRACTURE, empty, diags,
                         [], list(diag_codes), washout_days)


def heart_failure(diags: ColumnarTable, diag_codes=(220, 221),
                  washout_days: int = 180) -> ColumnarTable:
    """Heart-failure outcome (Table 4: diagnoses only)."""
    empty = diags.filter(jnp.zeros((diags.capacity,), bool))
    return _code_outcome(Category.OUTCOME_FRACTURE, empty, diags,
                         [], list(diag_codes), washout_days)
