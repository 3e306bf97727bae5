"""``chip_smoke.py``'s phases and checks on the CPU at a tiny size.

The script itself refuses any backend but a TPU; here its phase functions
run directly (Pallas kernels in interpret mode) so the parity, engine-log,
recount, ticket-status and chunked checks are exercised on every run, and
each check is shown to fire on a result that breaks it.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

N = 200


@pytest.fixture(scope="module")
def data():
    star, host = cs.make_star(N, seed=3)
    with open(cs.SPEC_PATH) as f:
        spec = json.load(f)
    return star, host, spec


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform=cpu" in out


def test_batch_phase_parity_engines_and_recount(data):
    star, host, _ = data
    fp = cs.batch_phase(cs.Clock(), star, N)
    assert "cohorts/exposed" in fp and "cohorts/final" in fp
    cs.check_recount(fp, cs.numpy_recount(host, N))


def test_engine_log_check_fires_on_jnp_fallback(data, monkeypatch):
    # an expression the kernel cannot take is stamped jnp by the optimizer;
    # the smoke run must refuse that instead of passing on the jnp engine
    from repro.kernels import predicate

    monkeypatch.setattr(predicate, "compilable", lambda p: False)
    star, _, _ = data
    with pytest.raises(cs.SmokeFailure, match="fell back"):
        cs.batch_phase(cs.Clock(), star, N)


def test_recount_and_parity_checks_fire():
    fp = {"events/acts/count": np.asarray(5), "cohorts/base": np.asarray(
        [0b1011], np.uint32), "x": np.zeros(3, np.float32)}
    with pytest.raises(cs.SmokeFailure, match="recount"):
        cs.check_recount(fp, {"acts": 6, "base": 3})
    cs.check_recount(fp, {"acts": 5, "base": 3})
    other = dict(fp, x=np.asarray([0, 0, 1], np.float32))
    with pytest.raises(cs.SmokeFailure, match="x differs"):
        cs.assert_identical(fp, other, "probe")


def test_service_phase_tickets_done_and_solo_identical(data):
    star, _, spec = data
    snap = cs.service_phase(cs.Clock(), star, N, spec)
    assert snap["queries"] == 8 and snap["demotions"] == 0
    assert set(snap["tenants"]) == {"epi-a", "epi-b"}


def test_service_phase_fails_on_a_failed_ticket(data, monkeypatch):
    from repro.core.extraction import Extractor

    star, _, spec = data
    real = cs.drug_query

    def broken(n, codes, date_from):    # scans a table the service lacks
        return real(n, codes, date_from).extract(
            Extractor(name="ghost", source="NO_SUCH_TABLE", category=2,
                      value_col="v", start_col="d"), name="ghost")

    monkeypatch.setattr(cs, "drug_query", broken)
    with pytest.raises(cs.SmokeFailure, match="not done"):
        cs.service_phase(cs.Clock(), star, N, spec)


def test_chunked_phase_one_compile(data, tmp_path):
    star, _, _ = data
    report = cs.chunked_phase(cs.Clock(), star, N, str(tmp_path))
    assert report["compiles"] == 1 and report["n_chunks"] >= 4
    assert not os.listdir(tmp_path)         # scratch store removed


def test_sharded_phase_on_a_one_device_mesh(data):
    star, _, _ = data
    cs.sharded_phase(cs.Clock(), star, N, 1)
