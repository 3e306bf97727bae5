"""The closed study loop: one client runs the configuration's study again
as soon as the previous one is on the host, for the whole window, over one
resident star, checked against the study's numpy reference once the window
has closed.

The study is a design, ``chipbench/designs/<design>.py``, named by the
configuration's ``study.design`` (the paper's §4 study by default).  A
design gives ``EVENT_OUTPUTS``, ``codes(study, zipf)``, ``build(spec,
study, codes) -> Study``, ``reference(host, spec, study, codes,
lossy_capacity=None)`` and ``compare(prog, want, n_patients) -> [names of
the outputs that differ]``; its reference returns at least ``events`` (each
of ``EVENT_OUTPUTS`` and ``extract_patients``), ``stats`` (the flatten
statistics as ``STAT_KEYS`` tuples), ``cohorts``, ``flow`` and ``counts``.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import harness
from chipbench.data import snds

#: a ``--trace 1`` run traces one study: the per-layer readers average
#: over whole studies, and one holds about 16 k op events
TRACE_SECONDS = 0.0
DEFAULT_DESIGN = "snds_s4_fractures"
#: the fields of the program's flatten statistics, in the reference's order
STAT_KEYS = ("rows_in", "rows_out", "matched", "overflow", "null_keys",
             "key_sum_in", "key_sum_out")


def design(study: Dict):
    """The design module a configuration's ``study`` section names."""
    return harness._load_path("designs", study.get("design", DEFAULT_DESIGN))


def words_to_ids(words: np.ndarray, n: int) -> np.ndarray:
    bits = np.unpackbits(words.astype("<u4").view(np.uint8),
                         bitorder="little")[:n]
    return np.flatnonzero(bits).astype(np.int32)


def host_answer(res) -> Dict:
    """What a user holds once a study is done: cohorts, flowchart counts and
    features on the host (plus the event counts)."""
    return {
        "cohorts": {k: np.asarray(c.subjects) for k, c in res.cohorts.items()},
        "flow": [int(c.subject_count()) for c in res.flow.steps],
        "features": jax.tree.map(np.asarray, res.features),
        "counts": {k: int(t.count) for k, t in res.events.items()},
    }


class Cell:
    """Set-up, window and check of a ``closed_study`` cell, on one chip."""

    #: the devices a cell hands the loop; a loop that shards the study
    #: subclasses this one, sets its own, and passes a mesh in ``_run``
    CHIPS = 1

    def __init__(self, cfg: Dict, mix: Dict, seed: int, devices):
        if len(devices) != self.CHIPS:
            raise ValueError(
                f"the closed study loop runs on {self.CHIPS} chip(s) and was "
                f"given {len(devices)} devices; a cell on more chips needs a "
                f"loop that shards the study (a subclass whose _run passes a "
                f"mesh to Study.run)")
        self.devices = list(devices)
        self.spec = snds.StarSpec.from_config(cfg)
        self.study_cfg = cfg["study"]
        self.design = design(self.study_cfg)
        self.seed = seed
        self.codes = self.design.codes(self.study_cfg, self.spec.zipf_exponent)
        self.rows = snds.sizes(self.spec).rows(self.spec.n_patients)
        self.durations: List[float] = []
        self.answers_ok: List[Dict] = []     # per-study counts and flow
        self.last = None
        self.last_answer = None
        self.window_s = 0.0

    # -- set-up: data on the device, programs loaded, one warm study --------
    def setup(self, seconds: float) -> None:
        self.star = snds.generate(self.spec, self.seed)
        jax.block_until_ready(self.star)
        self.study = self.design.build(self.spec, self.study_cfg, self.codes)
        res = self._run()
        host_answer(res)
        del res

    def window(self, seconds: float) -> None:
        """Studies back to back until ``seconds`` have passed; at least
        one."""
        t_start = time.perf_counter()
        while not self.durations or time.perf_counter() - t_start < seconds:
            t0 = time.perf_counter()
            self.last = self.last_answer = None   # free the previous study
            with TraceAnnotation("bench.study"):
                self.last = self._run()
                self.last_answer = host_answer(self.last)
            self.durations.append(time.perf_counter() - t0)
            self.answers_ok.append({"counts": self.last_answer["counts"],
                                    "flow": self.last_answer["flow"]})
        self.window_s = time.perf_counter() - t_start

    def _run(self):
        """One study over the resident star: the loop's one call of
        ``Study.run``."""
        return self.study.run(dict(self.star))

    # -- end-to-end metrics --------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        return {"study_events_per_s":
                len(self.durations) * self.rows / self.window_s}

    def counts(self):
        return len(self.durations), 0

    # -- the check -----------------------------------------------------------
    def take_outputs(self) -> Dict:
        """The last window study's outputs on the host; frees the device."""
        res = self.last
        out = {"events": {k: res.events[k].to_numpy()
                          for k in self.design.EVENT_OUTPUTS
                          + ("extract_patients",)},
               "stats": sorted(tuple(int(d[k]) for k in STAT_KEYS)
                               for d in res.flatten_stats.values()),
               **self.last_answer}
        out["cohorts"] = {k: words_to_ids(v, self.spec.n_patients)
                          for k, v in out["cohorts"].items()}
        self.host = snds.host_copy(self.star)
        self.last = self.star = self.study = None
        return out

    def reference(self) -> Dict:
        return self.design.reference(self.host, self.spec, self.study_cfg,
                                     self.codes)

    def check(self, prog: Dict, want: Dict) -> Dict[str, Dict]:
        n = self.spec.n_patients
        differing = self.design.compare(prog, want, n)
        off = sum(1 for a in self.answers_ok
                  if a["counts"] != want["counts"] or a["flow"] != want["flow"])
        self.differing = differing
        return {"differing_outputs": {"value": len(differing), "limit": 0},
                "studies_off_reference": {"value": off, "limit": 0}}
