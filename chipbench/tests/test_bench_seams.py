"""The harness's seams: a loop kind and a study design are files found by
name, so a new loop, design, configuration and cell are new files and
``BENCHMARK.json`` entries.  Each test adds its files to a copy of
``chipbench/`` and writes nothing into the repository."""
import json
import os
import shutil

import jax
import pytest

from chipbench import harness, traffic

ROOT = os.path.dirname(harness.HERE)
SEED = 2 ** 33 + 5
N = 128

#: a loop kind of its own: it sums a device array again and again for the
#: window, and records the devices it was handed
TOY_LOOP = '''
import json
import time

import jax.numpy as jnp

TRACE_SECONDS = 0.0


class Cell:
    def __init__(self, cfg, mix, seed, devices):
        with open(mix["record"], "w") as f:
            json.dump([str(d) for d in devices], f)
        self.size = seed % 97 + 8
        self.n = self.total = 0

    def setup(self, seconds):
        self.x = jnp.arange(self.size)
        int(self.x.sum())

    def window(self, seconds):
        t0 = time.perf_counter()
        while not self.n or time.perf_counter() - t0 < seconds:
            self.total += int(self.x.sum())
            self.n += 1
        self.window_s = time.perf_counter() - t0

    def end_to_end(self):
        return {"sums_per_s": self.n / self.window_s}

    def counts(self):
        return self.n, 0

    def take_outputs(self):
        return self.total

    def reference(self):
        return self.n * self.size * (self.size - 1) // 2

    def check(self, prog, want):
        return {"sum_off": {"value": abs(prog - want), "limit": 0}}
'''

#: a study design of its own: the women among the patients, their cohort
#: and a two-step flowchart
TOY_DESIGN = '''
import numpy as np

from chipbench.reference import snds_ref as ref
from repro.study import Study, col

EVENT_OUTPUTS = ("women",)


def codes(study, zipf):
    return {}


def build(spec, study, codes):
    return (Study(n_patients=spec.n_patients,
                  window=(spec.study_start, spec.study_end))
            .patients("IR_BEN")
            .filter("extract_patients", col("gender") == 2, name="women")
            .cohort("base", "extract_patients")
            .cohort("female", "women")
            .flow("base", "female"))


def reference(host, spec, study, codes, lossy_capacity=None):
    patients = ref.patients(host["IR_BEN"])
    women = {c: v[patients["gender"] == 2] for c, v in patients.items()}
    ev = {"extract_patients": patients, "women": women}
    base = ref.subjects(patients, spec.n_patients)
    chosen = ref.subjects(women, spec.n_patients)
    return {"events": ev, "stats": [],
            "cohorts": {"base": base, "female": chosen},
            "flow": [base.size, np.intersect1d(base, chosen).size],
            "counts": {k: int(t["patient_id"].size) for k, t in ev.items()}}


def compare(prog, want, n_patients):
    bad = [f"events/{k}" for k, t in want["events"].items()
           if not np.array_equal(ref.row_digest(prog["events"][k], sorted(t))[1],
                                 ref.row_digest(t, sorted(t))[1])]
    bad += [f"cohorts/{k}" for k, ids in want["cohorts"].items()
            if not np.array_equal(prog["cohorts"][k], ids)]
    return bad + [k for k in ("stats", "flow", "counts")
                  if prog[k] != want[k]]
'''


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """A copy of ``chipbench/`` holding the toy loop and design, with the
    harness and the mixes read from it; returns the copy's root."""
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = str(tmp_path / "chipbench")
    (tmp_path / "chipbench" / "loops" / "toy_count.py").write_text(TOY_LOOP)
    (tmp_path / "chipbench" / "designs" / "toy_women.py").write_text(
        TOY_DESIGN)
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(traffic, "HERE", here)
    return tmp_path


def _add(root, *, configs=(), workloads=(), end_to_end=(), reports=()):
    """``BENCHMARK.json`` at ``root``: the repository's, with entries added
    as a later change adds them; ``reports`` joins cells to an end-to-end
    metric's ``workloads``."""
    bench = harness.load_benchmark(ROOT)
    bench["configs"] += list(configs)
    bench["workloads"] += list(workloads)
    bench["end_to_end"] += list(end_to_end)
    for metric, cells in reports:
        m = next(m for m in bench["end_to_end"] if m["name"] == metric)
        m["workloads"] += list(cells)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return harness.load_benchmark(root)


def _write(root, path, obj):
    with open(os.path.join(root, "chipbench", path), "w") as f:
        json.dump(obj, f)


@pytest.mark.parametrize("n_devices", [1, 2])
def test_new_loop_kind_runs_to_a_result_line(copy, n_devices):
    """A loop kind added as a file, with its mix and cell, runs through
    ``run_cell``; its ``Cell`` gets the devices the run was given."""
    record = str(copy / "devices.json")
    _write(copy, "traffic/toy_sums.json", {"kind": "toy_count",
                                           "record": record})
    bench = _add(copy, workloads=[{
        "name": "toy.sums", "config": "snds_paper_study_1chip",
        "traffic": "toy_sums", "chips": 1, "why": "a loop kind as a file"}],
        end_to_end=[{"name": "sums_per_s", "unit": "1/s", "better": "higher",
                     "bound": 0.03, "source": "host_clock",
                     "workloads": ["toy.sums"]}])
    devices = jax.devices()[:1] * n_devices
    r = harness.run_cell(bench, harness.find_cell(bench, "toy.sums"), SEED,
                         0.05, False, devices)
    assert r["correct"], r["checks"]
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert set(r["metrics"]) == {"sums_per_s", "setup_s"}
    assert r["attempted"] >= 1 and r["device"]["count"] == n_devices
    with open(record) as f:
        assert json.load(f) == [str(d) for d in devices]


@pytest.mark.parametrize("fault", [False, True])
def test_new_design_runs_under_the_closed_loop(copy, fault):
    """A design added as a file, with a configuration that names it and a
    cell, runs under ``closed_study`` and reports the loop's metric; an
    answer altered where the design's study produces it is not correct."""
    if fault:
        path = copy / "chipbench" / "designs" / "toy_women.py"
        path.write_text(TOY_DESIGN.replace('col("gender") == 2',
                                           'col("gender") == 1'))
    cfg = harness.load_config("snds_paper_study_1chip")
    cfg.update(name="snds_toy_women", n_patients=N)
    cfg["study"] = {"design": "toy_women"}
    _write(copy, "configs/snds_toy_women.json", cfg)
    bench = _add(copy, configs=[{
        "name": "snds_toy_women", "source": "a test",
        "file": "chipbench/configs/snds_toy_women.json", "reduced": [],
        "why": "a design as a file"}], workloads=[{
            "name": "toy.women", "config": "snds_toy_women",
            "traffic": "study_closed", "chips": 1,
            "why": "a design under the closed loop"}],
        reports=[("study_events_per_s", ["toy.women"])])
    r = harness.run_cell(bench, harness.find_cell(bench, "toy.women"), SEED,
                         0.05, False, jax.devices()[:1])
    assert set(r["metrics"]) == {"study_events_per_s", "setup_s"}
    assert r["correct"] is not fault, r["checks"]
    if fault:
        assert r["checks"]["differing_outputs"]["value"] > 0


@pytest.mark.parametrize("unknown", ["loop", "design"])
def test_unknown_names_fail_with_the_names_found(copy, unknown):
    cfg = harness.load_config("snds_paper_study_1chip")
    mix = traffic.load("study_closed")
    if unknown == "loop":
        mix["kind"], found = "no_such_loop", ["closed_study", "toy_count"]
    else:
        cfg["study"]["design"] = "no_such_design"
        found = ["snds_s4_fractures", "toy_women"]
    bench = harness.load_benchmark(ROOT)
    with pytest.raises(ValueError, match=f"no_such_{unknown}") as e:
        harness.run_cell(bench, harness.find_cell(bench, "study.batch"),
                         SEED, 0.05, False, jax.devices()[:1], cfg=cfg,
                         mix=mix)
    assert str(found) in str(e.value)


def test_closed_loop_refuses_two_devices(copy):
    bench = harness.load_benchmark(ROOT)
    with pytest.raises(ValueError, match=r"1 chip\(s\) and was given 2"):
        harness.run_cell(bench, harness.find_cell(bench, "study.batch"),
                         SEED, 0.05, False, jax.devices()[:1] * 2)


#: a loop kind that subclasses the closed study loop for two chips, as a
#: sharded loop would, and records the devices its one Study.run saw
WIDE_LOOP = '''
import json

from chipbench import harness

closed = harness._load_path("loops", "closed_study")


class Cell(closed.Cell):
    CHIPS = 2

    def __init__(self, cfg, mix, seed, devices):
        super().__init__(cfg, mix, seed, devices)
        self.record = mix["record"]

    def _run(self):
        with open(self.record, "a") as f:
            f.write(json.dumps([str(d) for d in self.devices]) + "\\n")
        return super()._run()
'''


def test_closed_loop_subclass_gets_the_devices(copy):
    """A loop file that subclasses the closed loop for more chips, and
    overrides its one call of ``Study.run``, runs a design to a correct
    result line with every device the cell was given."""
    (copy / "chipbench" / "loops" / "toy_wide.py").write_text(WIDE_LOOP)
    record = str(copy / "runs.jsonl")
    cfg = dict(harness.load_config("snds_paper_study_1chip"), n_patients=N,
               study={"design": "toy_women"})
    bench = harness.load_benchmark(ROOT)
    devices = jax.devices()[:1] * 2
    r = harness.run_cell(bench, harness.find_cell(bench, "study.batch"),
                         SEED, 0.05, False, devices, cfg=cfg,
                         mix={"kind": "toy_wide", "record": record})
    assert r["correct"], r["checks"]
    with open(record) as f:
        seen = [json.loads(line) for line in f]
    assert len(seen) == 1 + r["attempted"]         # set-up's and the window's
    assert all(s == [str(d) for d in devices] for s in seen)


def test_every_cell_finds_its_loop_and_design():
    """Every cell's mix names a loop file with a ``Cell``; where the loop
    runs a design, the cell's configuration names a design file with all
    its parts."""
    bench = harness.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        loop = harness._load_path("loops",
                                  traffic.load(cell["traffic"])["kind"])
        assert callable(loop.Cell), cell["name"]
        if hasattr(loop, "design"):
            design = loop.design(harness.load_config(cell["config"])["study"])
            for part in ("EVENT_OUTPUTS", "codes", "build", "reference",
                         "compare"):
                assert hasattr(design, part), (cell["name"], part)
