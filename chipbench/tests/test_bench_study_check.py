"""The study cell's check: the numpy reference agrees with ``Study.run`` on
the §4 study, and ``correct`` comes out false for the control and for
faults planted in the program's timed path."""
from chipbench import harness
from chipbench.tests import _cells
from chipbench.tools import control
from repro.study import executor

N, SEED = 128, 2 ** 31 + 99


def _run():
    executor.clear_jit_cache()
    return _cells.run("study.batch", N, 0.5, SEED)


def test_reference_agrees_with_study_run():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1
    assert set(r["checks"]) == {"differing_outputs", "studies_off_reference"}


def test_control_lossy_joins_is_not_correct():
    """The control: the reference itself with the PMSI 1:N joins capped by a
    1.5x slack capacity (a guessed capacity in place of the planned one)."""
    cfg = harness.load_config("snds_paper_study_1chip")
    cfg["n_patients"] = N
    got = control.study_control(cfg, SEED)
    assert got["differing_outputs"] > 0
    assert "events/diagnoses/rows" in got["which"]


def test_fault_answer_altered(monkeypatch):
    """An exposure's start moved by a day where the transform makes it."""
    fn, wants = executor.TRANSFORMS["exposures"]

    def shifted(*a, **kw):
        out = fn(*a, **kw)
        cols = dict(out.columns, start=out.columns["start"] + 1)
        return type(out)(cols, out.valid, out.count, out.capacity)

    monkeypatch.setitem(executor.TRANSFORMS, "exposures", (shifted, wants))
    r = _run()
    assert not r["correct"]
    assert r["checks"]["differing_outputs"]["value"] > 0


def test_fault_half_the_rows_left_out(monkeypatch):
    """The flatten's detail joins see only every other central row."""
    from repro.core import flattening

    real = flattening.lookup_join

    def half(left, right, *a, **kw):
        import jax.numpy as jnp
        from repro.core import bitset

        rows = jnp.arange(left.capacity)
        keep = bitset.bit_at(left.valid, rows) & (rows % 2 == 0)
        left = type(left).from_columns(left.columns, valid=keep)
        return real(left, right, *a, **kw)

    monkeypatch.setattr(flattening, "lookup_join", half)
    r = _run()
    assert not r["correct"]
