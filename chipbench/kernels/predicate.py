"""Op and byte model of the Pallas predicate kernel
(``repro/kernels/predicate.py``: an Expr evaluated over projected columns
and ANDed with the packed validity, emitting packed words).

In the trace it is a ``tpu_custom_call`` whose HLO instruction is named
after its wrapper, ``_predicate_bitset_jit`` (``_predicate_bitset_jit.6``
in a v5e trace).  Per call it streams each
projected 32-bit column once (``4 * rows`` bytes each), reads the packed
validity (``rows / 8`` bytes) and the SMEM whitelists and scalars, and
writes the packed result (``rows / 8`` bytes).  The element-wise compares
run on the vector unit, for which the peak table holds no rate, so the
model counts bytes only and the roofline is the bandwidth bound.
"""
TRACE_NAME = "_predicate_bitset_jit"


def matches(name: str) -> bool:
    return name.startswith(TRACE_NAME)


def cost(outs, ins):
    """``(ops, bytes)`` of one call from its result and operand arrays."""
    return 0, sum(b for _, b in ins) + sum(b for _, b in outs)
