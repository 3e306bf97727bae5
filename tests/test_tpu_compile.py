"""Real-width compiles of the main-path Pallas kernels for a TPU v5e chip.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one chip
of a *described* ``v5e:2x2`` topology and asks the TPU compiler for the
executable, so a block layout or in-kernel op the chip refuses fails here,
on any host, instead of on the chip.  The topology is described inside a
fixture (never at import, in ``skipif`` or in ``parametrize``), and the
persistent compilation cache is off around the compiles: an executable for a
described chip can be written to it but never read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitset_ops, filter_compact, predicate

ROWS = 1 << 22                     # 4M rows: one chip's share of a flat table


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (expr param, hoisted lit values, hoisted whitelist lengths): a plain
# conjunction with null tests and arithmetic, static whitelists of 8 and
# 1,024 (the MAX_ISIN_VALUES budget), and the normalized-plan form whose
# literals are SMEM operands
_EXPRS = {
    "cmp_arith_null": (
        ("bool", "and",
         ("cmp", ">=", ("arith", "%", ("col", "a"), ("lit", 7)), ("lit", 2)),
         ("bool", "or", ("notnull", ("col", "b")),
          ("not", ("cmp", "<", ("col", "b"), ("lit", 1.5))))),
        {}, {}),
    "isin_8": (("isin", ("col", "a"), tuple(range(0, 80, 10))), {}, {}),
    "isin_1024": (("isin", ("col", "a"),
                   tuple(range(0, 3 * predicate.MAX_ISIN_VALUES, 3))),
                  {}, {}),
    "hoisted_lit_and_isin": (
        ("bool", "and", ("cmp", ">", ("col", "a"), ("hlit", 0)),
         ("hisin", ("col", "a"), 1, predicate.MAX_ISIN_VALUES, False)),
        {0: jnp.int32}, {1: predicate.MAX_ISIN_VALUES}),
}


@pytest.mark.parametrize("name", sorted(_EXPRS))
def test_predicate_bitset_compiles(one_chip, name):
    param, lit_types, vec_lens = _EXPRS[name]
    assert predicate.compilable(param)
    names = predicate.compile_predicate(param)[0]
    cols = {"a": _spec((ROWS,), jnp.int32, one_chip),
            "b": _spec((ROWS,), jnp.float32, one_chip)}
    cols = {nm: cols[nm] for nm in names}
    words = _spec((ROWS // 32,), jnp.uint32, one_chip)
    lits = {s: _spec((), t, one_chip) for s, t in lit_types.items()}
    vecs = {s: _spec((n,), jnp.int32, one_chip) for s, n in vec_lens.items()}

    def run(cols, words, lits, vecs):
        return predicate.predicate_bitset_blocks(
            param, cols, words, block=predicate.DEFAULT_BLOCK,
            interpret=False, params=(lits, vecs))

    assert "tpu_custom_call" in _compiled_text(run, cols, words, lits, vecs)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_filter_compact_bits_compiles(one_chip, dtype):
    def run(vals, words):
        return filter_compact.filter_compact_bits_blocks(vals, words,
                                                         interpret=False)

    text = _compiled_text(run, _spec((ROWS,), dtype, one_chip),
                          _spec((ROWS // 32,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in text


def test_filter_compact_bool_mask_compiles(one_chip):
    def run(vals, mask):
        return filter_compact.filter_compact_blocks(vals, mask,
                                                    interpret=False)

    text = _compiled_text(run, _spec((ROWS,), jnp.int32, one_chip),
                          _spec((ROWS,), jnp.bool_, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", sorted(bitset_ops.OPS))
def test_bitset_op_popcount_compiles(one_chip, op):
    def run(a, b):
        return bitset_ops.bitset_op_popcount(a, b, op, interpret=False)

    w = _spec((ROWS // 32,), jnp.uint32, one_chip)
    assert "tpu_custom_call" in _compiled_text(run, w, w)
