"""FirstFit variants + conflict heuristics: deterministic unit tests.

The hypothesis property tests (randomized oracle sweeps) live in
``test_properties.py`` behind ``pytest.importorskip("hypothesis")`` so this
module's coverage survives environments without hypothesis installed.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.firstfit import (
    FF_FUNCS,
    FF_MIN_WORDS,
    bitset_words,
    ffs_u32,
    firstfit_bitset,
    firstfit_scan,
    firstfit_sort,
)
from repro.core.heuristics import conflict_lose_flags
from repro.kernels.firstfit.ref import firstfit_ref


def _oracle_row(row):
    present = set(int(c) for c in row if c > 0)
    c = 1
    while c in present:
        c += 1
    return c


def test_firstfit_variants_match_oracle_fixed_seeds():
    for w, W, seed in [(7, 5, 0), (30, 40, 1), (1, 1, 2), (16, 33, 3)]:
        rng = np.random.default_rng(seed)
        nc = rng.integers(0, W + 3, size=(w, W)).astype(np.int32)
        want = np.array([_oracle_row(r) for r in nc], dtype=np.int32)
        for name, fn in FF_FUNCS.items():
            got = np.asarray(fn(jnp.asarray(nc)))
            np.testing.assert_array_equal(got, want, err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(firstfit_ref(jnp.asarray(nc))), want)


def test_firstfit_greedy_bound_edge():
    # W neighbors with colors exactly 1..W -> answer W+1 (bound is tight)
    W = 37
    nc = jnp.asarray(np.arange(1, W + 1)[None, :].astype(np.int32))
    for fn in (firstfit_scan, firstfit_sort, firstfit_bitset):
        assert int(fn(nc)[0]) == W + 1


def test_firstfit_ignores_uncolored_and_huge():
    nc = jnp.asarray(np.array([[0, 0, 999, 2]], dtype=np.int32))
    for fn in FF_FUNCS.values():
        assert int(fn(nc)[0]) == 1


@pytest.mark.parametrize("W", [1, 32, 100, 600, 4000])
@pytest.mark.parametrize("top", [0, 31, 300, 700])
@pytest.mark.parametrize("chunks", [1, 3])
def test_bounded_bitset_matches_the_full_bitset(W, top, chunks):
    """The bitset at ``bitset_words(top, chunks)`` words answers as the
    full-width one on tiles whose colors reach ``top + chunks - 1`` (the
    last chunk of a chunked step): random rows, and rows that hold every
    color up to their width or to that bound, whose answer is the last."""
    rng = np.random.default_rng(W * 7919 + top * 31 + chunks)
    hi = top + chunks - 1
    nc = rng.integers(0, hi + 1, size=(48, W)).astype(np.int32)
    full_rows = np.zeros((3, W), np.int32)
    for i, k in enumerate((hi, hi - 1, W)):
        k = max(0, min(k, W, hi))
        full_rows[i, :k] = rng.permutation(np.arange(1, k + 1))
    nc = jnp.asarray(np.concatenate([nc, full_rows]))
    words = bitset_words(top, chunks)
    assert words >= FF_MIN_WORDS and words & (words - 1) == 0
    assert 32 * words >= top + chunks
    want = np.asarray(firstfit_bitset(nc))
    np.testing.assert_array_equal(np.asarray(firstfit_bitset(nc, words)),
                                  want)
    assert want.max() <= hi + 1


def test_too_few_bitset_words_miss_the_answer():
    """The bound is what keeps the cap exact: a row holding colors 1..40
    needs two words, and one word cannot see its answer, 41."""
    nc = jnp.asarray(np.arange(1, 41, dtype=np.int32)[None, :])
    assert int(firstfit_bitset(nc)[0]) == 41
    assert int(firstfit_bitset(nc, 2)[0]) == 41
    assert int(firstfit_bitset(nc, 1)[0]) != 41


def test_ffs_u32():
    vals = np.array([1, 2, 3, 8, 0x80000000, 0, 0xFFFFFFFF], dtype=np.uint32)
    got = np.asarray(ffs_u32(jnp.asarray(vals)))
    want = []
    for v in vals:
        vi = int(v)
        want.append(32 if vi == 0 else (vi & -vi).bit_length() - 1)
    np.testing.assert_array_equal(got, np.array(want))


def test_conflict_exactly_one_loser_fixed_seed():
    """For every monochromatic edge, exactly one endpoint loses (both rules)."""
    rng = np.random.default_rng(1234)
    n = 10
    deg = rng.integers(0, 7, size=n + 1).astype(np.int32)
    deg[n] = 0
    colors = rng.integers(0, 3, size=n + 1).astype(np.int32)
    colors[n] = 0
    for heuristic in ("id", "degree"):
        for u in range(n):
            for v in range(n):
                if u == v or colors[u] == 0 or colors[u] != colors[v]:
                    continue
                lu = conflict_lose_flags(
                    jnp.asarray([u]), jnp.asarray([[v]]),
                    jnp.asarray([colors[u]]), jnp.asarray([[colors[v]]]),
                    jnp.asarray([deg[u]]), jnp.asarray([[deg[v]]]), heuristic)
                lv = conflict_lose_flags(
                    jnp.asarray([v]), jnp.asarray([[u]]),
                    jnp.asarray([colors[v]]), jnp.asarray([[colors[u]]]),
                    jnp.asarray([deg[v]]), jnp.asarray([[deg[u]]]), heuristic)
                assert bool(lu[0]) != bool(lv[0]), (heuristic, u, v)


def test_conflict_none_when_uncolored_or_different():
    lose = conflict_lose_flags(
        jnp.asarray([3]), jnp.asarray([[5, 7]]),
        jnp.asarray([0]), jnp.asarray([[0, 2]]),
        jnp.asarray([4]), jnp.asarray([[4, 4]]), "degree")
    assert not bool(lose[0])
