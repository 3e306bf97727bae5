"""The study's code lists: fixed per seed, distinct, drawn from the stated
ranks."""
import numpy as np

from chipbench import traffic


def test_study_codes_per_seed():
    study = {"code_pool": 1000, "prevalent_drugs": 65,
             "fracture_act_codes": 30, "fracture_diag_codes": 40,
             "fracture_code_ranks": [100, 2000]}
    a = traffic.study_codes(5, study, 1.0)
    b = traffic.study_codes(5, study, 1.0)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert [a[k].size for k in ("prevalent", "fracture_acts",
                                "fracture_diags")] == [65, 30, 40]
    assert all(np.unique(v).size == v.size for v in a.values())
    assert a["prevalent"].max() < 1000
    assert a["fracture_acts"].min() >= 100 and a["fracture_diags"].max() < 2000
