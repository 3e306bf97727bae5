"""Plain numpy reference of the benchmark's SNDS study and cohort queries.

Written from the semantics the paper and the schema state (left joins of a
star with SQL NULL handling, extract = filter + conform, the §4 transforms,
cohort set algebra, flowcharts, the two feature exports), in straightforward
numpy over host copies of the generated tables.  It imports nothing of the
program under test and takes nothing it made.

Tables here are dicts of equal-length numpy columns holding valid rows only.
Event tables carry the seven event columns; their row order is not part of a
comparison (see ``row_digest``), except where a tie decides an answer (the
fracture washout), which is spelled out there.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NULL = -2_147_483_647           # int32 NULL sentinel of the stored format
EVENT_COLUMNS = ("patient_id", "category", "group_id", "value", "weight",
                 "start", "end")
CAT = {"drug": 1, "act": 2, "diag": 3, "stay": 4, "follow_up": 10,
       "exposure": 11, "fracture": 12}

Table = Dict[str, np.ndarray]


def _u32_sum(a: np.ndarray) -> int:
    return int(a.astype(np.int64).astype(np.uint32).astype(np.uint64).sum()
               % (1 << 32))


def _take(t: Table, idx) -> Table:
    return {c: v[idx] for c, v in t.items()}


def _null_of(a: np.ndarray):
    return np.float32(np.nan) if a.dtype.kind == "f" else np.int32(NULL)


def _is_null(a: np.ndarray) -> np.ndarray:
    return np.isnan(a) if a.dtype.kind == "f" else a == NULL


# ---------------------------------------------------------------------------
# joins (SQL left joins; a NULL key matches nothing) and their statistics
# ---------------------------------------------------------------------------
def _stats(rows_in, rows_out, matched, null_keys, ksum_in, ksum_out):
    return (int(rows_in), int(rows_out), int(matched), 0, int(null_keys),
            int(ksum_in), int(ksum_out))


def key_order(right: Table, rkey: str) -> np.ndarray:
    """Rows of ``right`` with a non-NULL key, in (key, row) order."""
    rk = right[rkey]
    r_ok = ~_is_null(rk)
    return np.flatnonzero(r_ok)[np.argsort(rk[r_ok], kind="stable")]


def lookup_join(left: Table, right: Table, lkey: str, rkey: str,
                keep_right: bool = True,
                order: Optional[np.ndarray] = None) -> Tuple[Table, tuple]:
    """N:1 left join; ``right`` holds at most one row per non-NULL key (the
    first in row order wins).  ``keep_right=False`` is the same join with
    no right column read (the statistics only); ``order`` is
    ``key_order(right, rkey)`` when the caller has it."""
    lk, rk = left[lkey], right[rkey]
    r_ok = ~_is_null(rk)
    order = key_order(right, rkey) if order is None else order
    rs = rk[order]
    pos = np.searchsorted(rs, lk, side="left")
    posc = np.minimum(pos, max(rs.size - 1, 0))
    found = (pos < rs.size) & ~_is_null(lk)
    if rs.size:
        found &= rs[posc] == lk
    out = dict(left)
    if keep_right:
        src = order[posc] if rs.size else np.zeros(lk.size, np.int64)
        for c, v in right.items():
            if c == rkey:
                continue
            col = v[src] if v.size else np.zeros(lk.size, v.dtype)
            out[c] = np.where(found, col, _null_of(v)).astype(v.dtype)
    ks = _u32_sum(lk)
    return out, _stats(lk.size, lk.size, found.sum(),
                       _is_null(lk).sum() + (~r_ok).sum(), ks, ks)


def expand_join(left: Table, right: Table, lkey: str,
                rkey: str) -> Tuple[Table, tuple]:
    """1:N left join: one output row per (left row, matching right row) in
    left order, the matches in right row order; a left row without a match
    keeps one row with NULL right columns."""
    lk, rk = left[lkey], right[rkey]
    r_ok = ~_is_null(rk)
    order = np.flatnonzero(r_ok)[np.argsort(rk[r_ok], kind="stable")]
    rs = rk[order]
    lo = np.searchsorted(rs, lk, side="left")
    hi = np.searchsorted(rs, lk, side="right")
    cnt = np.where(_is_null(lk), 0, hi - lo)
    reps = np.maximum(cnt, 1)
    src = np.repeat(np.arange(lk.size), reps)
    first = np.repeat(np.cumsum(reps) - reps, reps)
    rel = np.arange(src.size) - first
    has = cnt[src] > 0
    ridx = order[np.where(has, lo[src] + rel, 0)] if rs.size else \
        np.zeros(src.size, np.int64)
    out = {c: v[src] for c, v in left.items()}
    for c, v in right.items():
        if c == rkey:
            continue
        out[c] = np.where(has, v[ridx], _null_of(v)).astype(v.dtype)
    return out, _stats(lk.size, src.size, (cnt > 0).sum(),
                       _is_null(lk).sum() + (~r_ok).sum(), _u32_sum(lk),
                       _u32_sum(out[lkey]))


DCIR_JOINS = (("ER_PHA", "flow_id", "flow_id"), ("ER_CAM", "flow_id",
                                                  "flow_id"),
              ("IR_BEN", "patient_id", "patient_id"))
PMSI_JOINS = (("MCO_D", "stay_id", "stay_id"), ("MCO_A", "stay_id",
                                                 "stay_id"))


def flatten_dcir(star: Dict[str, Table], central: Optional[Table] = None,
                 with_patients: bool = True,
                 orders: Optional[Dict[str, np.ndarray]] = None
                 ) -> Tuple[Table, List[tuple]]:
    t = star["ER_PRS"] if central is None else central
    stats = []
    for right, lk, rk in DCIR_JOINS:
        keep = with_patients or right != "IR_BEN"
        t, s = lookup_join(t, star[right], lk, rk, keep_right=keep,
                           order=(orders or {}).get(right))
        stats.append(s)
    return t, stats


def flatten_pmsi(star: Dict[str, Table]) -> Tuple[Table, List[tuple]]:
    t, stats = star["MCO_B"], []
    for right, lk, rk in PMSI_JOINS:
        t, s = expand_join(t, star[right], lk, rk)
        stats.append(s)
    return t, stats


def slice_edges(t0: int, t1: int, n: int) -> np.ndarray:
    return np.linspace(int(t0), int(t1) + 1, int(n) + 1).astype(np.int32)


def flatten_dcir_sliced(star: Dict[str, Table], col: str, n: int, t0: int,
                        t1: int) -> Tuple[Table, List[tuple]]:
    """Temporal slicing: each slice of the central table (``lo <= col <
    hi``, in row order) is flattened, the slices appended in time order."""
    prs = star["ER_PRS"]
    edges = slice_edges(t0, t1, n)
    parts, stats = [], []
    orders = {r: key_order(star[r], rk) for r, _, rk in DCIR_JOINS}
    for lo, hi in zip(edges[:-1], edges[1:]):
        m = (prs[col] >= lo) & (prs[col] < hi)
        sl = _take(prs, m)
        ks = _u32_sum(sl[col])
        stats.append(_stats(prs[col].size, m.sum(), m.sum(), 0, ks, ks))
        flat, s = flatten_dcir(star, central=sl, with_patients=False,
                               orders=orders)
        parts.append(flat)
        stats += s
    return {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}, stats


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------
def events(patient_id, category, value, start, end=None, group=None,
           weight=None) -> Table:
    n = patient_id.size
    return {
        "patient_id": patient_id.astype(np.int32),
        "category": np.full(n, category, np.int32),
        "group_id": (np.zeros(n, np.int32) if group is None
                     else group.astype(np.int32)),
        "value": value.astype(np.int32),
        "weight": (np.ones(n, np.float32) if weight is None
                   else weight.astype(np.float32)),
        "start": start.astype(np.int32),
        "end": (np.full(n, NULL, np.int32) if end is None
                else end.astype(np.int32)),
    }


def first_of_distinct(t: Table, keys: Sequence[str]) -> Table:
    """DISTINCT over ``keys``: rows in key order, the first of each run."""
    order = np.lexsort([t[k] for k in reversed(keys)])
    s = _take(t, order)
    head = np.ones(order.size, bool)
    if order.size:
        head[1:] = np.any(np.stack([s[k][1:] != s[k][:-1] for k in keys]),
                          axis=0)
    return _take(s, head)


def drug_purchases(flat: Table, codes=None, window=None) -> Table:
    m = ~_is_null(flat["cip13"])
    if codes is not None:
        m &= np.isin(flat["cip13"], codes)
    if window is not None:
        m &= (flat["execution_date"] >= window[0]) & (
            flat["execution_date"] < window[1])
    return events(flat["patient_id"][m], CAT["drug"], flat["cip13"][m],
                  flat["execution_date"][m])


def dcir_acts(flat: Table) -> Table:
    m = ~_is_null(flat["ccam_code"])
    return events(flat["patient_id"][m], CAT["act"], flat["ccam_code"][m],
                  flat["execution_date"][m])


def hospital_acts(flat: Table) -> Table:
    t = first_of_distinct(_take(flat, ~_is_null(flat["ccam_code"])),
                          ("stay_id", "ccam_code", "act_date"))
    return events(t["patient_id"], CAT["act"], t["ccam_code"], t["act_date"])


def diagnoses(flat: Table, codes=None) -> Table:
    m = ~_is_null(flat["icd_code"])
    if codes is not None:
        m &= np.isin(flat["icd_code"], codes)
    t = first_of_distinct(_take(flat, m), ("stay_id", "icd_code",
                                           "diag_kind"))
    return events(t["patient_id"], CAT["diag"], t["icd_code"],
                  t["stay_start"], group=t["diag_kind"])


def stays(flat: Table) -> Table:
    t = first_of_distinct(_take(flat, ~_is_null(flat["ghm_code"])),
                          ("stay_id",))
    return events(t["patient_id"], CAT["stay"], t["ghm_code"],
                  t["stay_start"], end=t["stay_end"])


def patients(ir_ben: Table) -> Table:
    cols = ("patient_id", "gender", "birth_date", "death_date")
    return first_of_distinct({c: ir_ben[c] for c in cols}, ("patient_id",))


# ---------------------------------------------------------------------------
# transforms (paper Table 4)
# ---------------------------------------------------------------------------
def exposures(drugs: Table, purview: int) -> Table:
    """Dispenses of one (patient, drug) chained while consecutive dispenses
    are at most ``purview`` days apart; each chain is one exposure from its
    first dispense to its last plus ``purview``, weighted by its size."""
    o = np.lexsort([drugs["start"], drugs["value"], drugs["patient_id"]])
    p, v, s = drugs["patient_id"][o], drugs["value"][o], drugs["start"][o]
    new = np.ones(o.size, bool)
    if o.size:
        new[1:] = ~((p[1:] == p[:-1]) & (v[1:] == v[:-1])
                    & (s[1:] - s[:-1] <= purview))
    heads = np.flatnonzero(new)
    n = np.diff(np.append(heads, o.size))
    last = s[np.append(heads[1:], o.size) - 1] if o.size else s[:0]
    return events(p[heads], CAT["exposure"], v[heads], s[heads],
                  end=last + purview, weight=n.astype(np.float32))


def fractures(acts: Table, diags: Table, act_codes, diag_codes,
              n_sites: int = 8, washout: int = 90) -> Table:
    """Fracture outcomes: candidate acts then diagnoses (in that row order)
    with a fracture code, site = code mod ``n_sites``, ordered by (patient,
    site, date) with ties in candidate order; a candidate is kept when it
    opens a new (patient, site) or comes ``washout`` days or more after the
    last kept one."""
    ma = np.isin(acts["value"], act_codes)
    md = np.isin(diags["value"], diag_codes)
    p = np.concatenate([acts["patient_id"][ma], diags["patient_id"][md]])
    v = np.concatenate([acts["value"][ma], diags["value"][md]])
    s = np.concatenate([acts["start"][ma], diags["start"][md]])
    site = v % n_sites
    o = np.lexsort([s, site, p])
    p, v, s, site = p[o], v[o], s[o], site[o]
    keep = np.zeros(o.size, bool)
    # the chain restarts at every new (patient, site); within one, a greedy
    # walk over dates
    grp = np.ones(o.size, bool)
    if o.size:
        grp[1:] = (p[1:] != p[:-1]) | (site[1:] != site[:-1])
    starts = np.flatnonzero(grp)
    ends = np.append(starts[1:], o.size)
    for a, b in zip(starts, ends):
        last = None
        for i in range(a, b):
            if last is None or s[i] - last >= washout:
                keep[i] = True
                last = s[i]
    return events(p[keep], CAT["fracture"], v[keep], s[keep],
                  group=site[keep])


def follow_up(pats: Table, drugs: Table, n_patients: int,
              study_end: int) -> Table:
    """Per patient with a dispense: from the first dispense to the study end
    or the death, whichever comes first; kept when it starts before it
    ends."""
    pid = drugs["patient_id"]
    has = np.bincount(pid, minlength=n_patients) > 0
    first = np.full(n_patients, np.iinfo(np.int32).max, np.int64)
    np.minimum.at(first, pid, drugs["start"])
    death = np.full(n_patients, NULL, np.int64)
    death[pats["patient_id"]] = pats["death_date"]
    end = np.where(death == NULL, study_end, np.minimum(death, study_end))
    ok = has & (first < end)
    ids = np.arange(n_patients)[ok]
    return events(ids, CAT["follow_up"], np.zeros(ids.size, np.int32),
                  first[ok], end=end[ok])


# ---------------------------------------------------------------------------
# cohorts, flowchart and feature exports
# ---------------------------------------------------------------------------
def subjects(t: Table, n_patients: int) -> np.ndarray:
    """Sorted distinct patient ids of a table (within the universe)."""
    p = np.unique(t["patient_id"])
    return p[(p >= 0) & (p < n_patients)]


def dense_features(ev: Table, t0: int, n_buckets: int, bucket_days: int,
                   n_features: int, n_patients: int) -> np.ndarray:
    b = np.clip((ev["start"] - t0) // bucket_days, 0, n_buckets - 1)
    f = np.clip(ev["value"], 0, n_features - 1)
    p = np.clip(ev["patient_id"], 0, n_patients - 1)
    idx = (p.astype(np.int64) * n_buckets + b) * n_features + f
    x = np.bincount(idx, weights=ev["weight"].astype(np.float64),
                    minlength=n_patients * n_buckets * n_features)
    return x.astype(np.float32).reshape(n_patients, n_buckets, n_features)


# token layout: category -> (offset, size); specials PAD 0, BOS 1, EOS 2
TOKENS = {1: (8, 512), 2: (520, 512), 3: (1032, 512), 4: (1544, 256),
          11: (1800, 512), 12: (2312, 64)}


def token_sequences(ev: Table, seq_len: int,
                    n_patients: int) -> Tuple[np.ndarray, np.ndarray]:
    o = np.lexsort([ev["value"], ev["category"], ev["start"],
                    ev["patient_id"]])
    pid, cat, val = ev["patient_id"][o], ev["category"][o], ev["value"][o]
    tok = np.zeros(o.size, np.int64)
    for c, (off, size) in TOKENS.items():
        m = cat == c
        tok[m] = off + np.clip(val[m], 0, size - 1)
    known = tok != 0
    pid, tok = pid[known], tok[known]
    first = np.searchsorted(pid, pid, side="left")
    pos = np.arange(pid.size) - first
    toks = np.zeros((n_patients, seq_len), np.int32)
    fit = pos < seq_len - 2
    toks[pid[fit], 1 + pos[fit]] = tok[fit]
    toks[:, 0] = 1
    n_per = np.bincount(pid, minlength=n_patients)
    eos = np.clip(n_per + 1, 1, seq_len - 1)
    toks[np.arange(n_patients), eos] = 2
    mask = np.arange(seq_len)[None, :] <= eos[:, None]
    return toks, mask


def checked(ev: Table, window: Tuple[int, int]) -> Table:
    ok = (ev["start"] >= window[0]) & (ev["start"] < window[1]) & (
        (ev["end"] == NULL) | (ev["end"] >= ev["start"]))
    return _take(ev, ok)


def keep_subjects(ev: Table, subj: np.ndarray) -> Table:
    return _take(ev, np.isin(ev["patient_id"], subj))


# ---------------------------------------------------------------------------
# comparison helpers
# ---------------------------------------------------------------------------
def row_digest(t: Table, columns: Sequence[str]) -> Tuple[int, np.ndarray]:
    """Order-free fingerprint of a table's rows: the row count and the
    sorted 64-bit mixes of each row's columns (equal multisets of rows give
    equal digests)."""
    n = next(iter(t.values())).size if t else 0
    h = np.full(n, 0x9E3779B97F4A7C15, np.uint64)
    for c in columns:
        v = np.ascontiguousarray(t[c])
        bits = v.view(np.uint32).astype(np.uint64)
        h = (h ^ bits) * np.uint64(0x100000001B3)
        h ^= h >> np.uint64(29)
    return n, np.sort(h)
