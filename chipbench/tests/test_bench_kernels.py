"""The kernel byte models against a hand count at one shape, on the HLO
text of each kernel as compiled for a v5e (1,048,576 rows)."""
from chipbench import harness
from chipbench.trace import hlo_arrays, is_pallas

MODELS = harness.kernel_models()
ROWS = 1 << 20
PRED = ('%_predicate_bitset_jit.1 = s32[256,128]{1,0:T(8,128)S(1)} '
        'custom-call(%bitcast.6, %bitcast.7, %iota, %bitcast-convert), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[8192,128]{1,0}, s32[8192,128]{1,0}, s32[16]{0}, '
        's32[256,128]{1,0}}, frontend_attributes={kernel_metadata={}}')
COMPACT = ('%g.1 = s32[8192,128]{1,0:T(8,128)} custom-call(%bitcast.5, '
           '%bitcast-convert), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={s32[8192,128]{1,0}, '
           's32[256,128]{1,0}}, frontend_attributes={kernel_metadata={}}')
ALGEBRA = ('%h.1 = (u32[256,128]{1,0:T(8,128)}, s32[32,128]{1,0:T(8,128)}) '
           'custom-call(%bitcast.6, %bitcast.7), custom_call_target='
           '"tpu_custom_call", operand_layout_constraints={u32[256,128]{1,0},'
           ' u32[256,128]{1,0}}, frontend_attributes={kernel_metadata={}}')


def _pick(name, text):
    outs, ins = hlo_arrays(text)
    hits = [k for k, m in MODELS.items()
            if m.matches(name) and (not hasattr(m, "fits")
                                        or m.fits(outs, ins))]
    return hits, MODELS[hits[0]].cost(outs, ins) if hits else None


def test_predicate_bytes():
    assert is_pallas(PRED)
    hits, (ops, nbytes) = _pick("_predicate_bitset_jit.1", PRED)
    assert hits == ["predicate"]
    # two int32 columns, a 16-code whitelist, validity in, words out
    assert nbytes == 2 * 4 * ROWS + 16 * 4 + ROWS // 8 + ROWS // 8
    assert ops == 0


def test_compaction_bytes():
    hits, (ops, nbytes) = _pick("g.1", COMPACT)
    assert hits == ["filter_compact"]
    # values in, keep mask in, compacted values out
    assert nbytes == 4 * ROWS + ROWS // 8 + 4 * ROWS


def test_cohort_algebra_bytes():
    hits, (ops, nbytes) = _pick("h.1", ALGEBRA)
    assert hits == ["bitset_op"]
    words = ROWS // 32
    # two word arrays in, words out, 32 (8, 128) popcount tiles out
    assert nbytes == 3 * 4 * words + 32 * 128 * 4


def test_peaks_table_refuses_unknown_devices():
    import pytest
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")
