"""Op and byte model of the Pallas cohort-algebra kernel
(``repro/kernels/bitset_ops.py``: a word-wise AND / OR / ANDNOT of two
packed subject sets fused with its popcount).

In the trace it is a ``tpu_custom_call`` named after the jitted function
that calls it, told apart by its two results: the words, shaped as its two
operands, and an ``(8 * blocks, 128)`` int32 tile of partial popcounts.
Per call it reads both word arrays and writes the result words and the
partial counts; one bitwise op and one popcount per word run on the vector
unit (no peak rate in the table), so bytes only.
"""


def matches(name: str) -> bool:
    return not name.startswith("_predicate_bitset_jit")


def fits(outs, ins) -> bool:
    return (len(outs) == 2 and len(ins) == 2 and outs[0][0] == ins[0][0]
            and ins[0][0] == ins[1][0])


def cost(outs, ins):
    return 0, sum(b for _, b in ins) + sum(b for _, b in outs)
