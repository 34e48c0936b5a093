"""Ragged CSR-native super-step engine (DESIGN.md §12).

Covers the fused superstep Pallas kernel against its independent pure-jnp
ref, the padded/ragged engine bit-identity contract, adaptive
tail-serialization, the CSR-native storage, and the satellite regressions
(``reuse_rows`` forwarding, ``coarsen_lanes`` chunk derivation).
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

import jax

import repro.core.coloring as C
from repro.core import (
    CSRGraph,
    DeviceCSR,
    auto_tile_thresholds,
    color_data_driven,
    csr_from_edges,
    is_valid_coloring,
    num_colors,
    next_pow2,
)
from repro.core.firstfit import FF_MIN_WORDS
from repro.core.serial import greedy_serial
from repro.graphs import build_graph, erdos_renyi, grid2d, power_law, rmat
from repro.kernels.superstep.csr_kernel import (
    serial_tail_csr_tpu,
    superstep_csr_tpu,
)
from repro.kernels.superstep.ops import superstep_tpu
from repro.kernels.superstep.ref import superstep_ref

GRAPHS = {
    "er": lambda: erdos_renyi(900, 7.0, seed=11),
    "grid": lambda: grid2d(25, 30),
    "rmat-g": lambda: rmat(1200, 9.0, seed=12),
    "powerlaw": lambda: power_law(900, 6.0, seed=13),
}


# --------------------------------------------------------------------------
# fused superstep kernel vs its independent ref (acceptance: bit-identical)
# --------------------------------------------------------------------------

SHAPES = [(7, 3), (8, 8), (64, 16), (100, 33), (256, 64), (33, 130), (512, 5)]


def _random_tile(w, W, seed):
    rng = np.random.default_rng(seed)
    ids = rng.permutation(w + 3)[:w].astype(np.int32)
    nid = rng.integers(0, w + 3, size=(w, W)).astype(np.int32)
    my_c = rng.integers(0, W + 2, size=(w,)).astype(np.int32)
    nc = rng.integers(0, W + 2, size=(w, W)).astype(np.int32)
    my_d = rng.integers(0, 9, size=(w,)).astype(np.int32)
    nd = rng.integers(0, 9, size=(w, W)).astype(np.int32)
    return tuple(map(jnp.asarray, (ids, nid, my_c, nc, my_d, nd)))


@pytest.mark.parametrize("w,W", SHAPES)
@pytest.mark.parametrize("heuristic", ["id", "degree"])
def test_superstep_kernel_matches_ref(w, W, heuristic):
    args = _random_tile(w, W, seed=w * 1000 + W)
    got_c, got_n = superstep_tpu(*args, heuristic)
    want_c, want_n = superstep_ref(*args, heuristic)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


@pytest.mark.parametrize("block_n", [8, 16, 128])
def test_superstep_kernel_block_sizes(block_n):
    args = _random_tile(200, 17, seed=5)
    got_c, got_n = superstep_tpu(*args, "degree", block_n=block_n)
    want_c, want_n = superstep_ref(*args, "degree")
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


def test_superstep_kernel_empty():
    c, n = superstep_tpu(*[jnp.zeros(s, jnp.int32) for s in
                           [(0,), (0, 4), (0,), (0, 4), (0,), (0, 4)]])
    assert c.shape == (0,) and n.shape == (0,)


def test_superstep_kernel_semantics():
    """Winner keeps; loser refits treating beaten neighbors as cleared."""
    # two adjacent vertices, both color 1; degree rule: larger degree keeps
    ids = jnp.asarray([0, 1], jnp.int32)
    nid = jnp.asarray([[1], [0]], jnp.int32)
    my_c = jnp.asarray([1, 1], jnp.int32)
    nc = jnp.asarray([[1], [1]], jnp.int32)
    my_d = jnp.asarray([5, 2], jnp.int32)
    nd = jnp.asarray([[2], [5]], jnp.int32)
    newc, need = superstep_tpu(ids, nid, my_c, nc, my_d, nd, "degree")
    np.testing.assert_array_equal(np.asarray(need), [False, True])
    # vertex 0 (winner) keeps 1; vertex 1 must avoid the winner's color
    np.testing.assert_array_equal(np.asarray(newc), [1, 2])


@pytest.mark.parametrize("W", [31, 32, 63, 64])
def test_superstep_kernel_nwords_boundary(W):
    """Every color 1..W forbidden forces FirstFit to W+1 — the bit that
    lives exactly at (or one past) a 32-bit bitset word boundary, where an
    off-by-one in ``nwords = (W + 1 + 31) // 32`` would truncate."""
    w = 4
    ids = jnp.arange(w, dtype=jnp.int32)
    nid = jnp.broadcast_to(jnp.arange(w, w + W, dtype=jnp.int32), (w, W))
    my_c = jnp.zeros(w, jnp.int32)  # uncolored: must FirstFit
    nc = jnp.broadcast_to(jnp.arange(1, W + 1, dtype=jnp.int32), (w, W))
    my_d = jnp.full(w, W, jnp.int32)
    nd = jnp.full((w, W), W, jnp.int32)
    got_c, got_n = superstep_tpu(ids, nid, my_c, nc, my_d, nd, "degree")
    want_c, want_n = superstep_ref(ids, nid, my_c, nc, my_d, nd, "degree")
    np.testing.assert_array_equal(np.asarray(got_c), np.full(w, W + 1))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


@pytest.mark.parametrize("heuristic", ["id", "degree"])
def test_superstep_kernel_all_conflict_worklist(heuristic):
    """A monochromatic clique tile: every lane conflicts with every other.
    Equal degrees leave a single total-order winner — the largest id under
    the "id" rule, the smallest under "degree"'s id tiebreak — who alone
    keeps color 1 while every loser refits around the winners it lost to."""
    k = 9
    ids = jnp.arange(k, dtype=jnp.int32)
    nid = jnp.asarray(
        [[v for v in range(k) if v != u] for u in range(k)], jnp.int32)
    my_c = jnp.ones(k, jnp.int32)
    nc = jnp.ones((k, k - 1), jnp.int32)
    my_d = jnp.full(k, k - 1, jnp.int32)
    nd = jnp.full((k, k - 1), k - 1, jnp.int32)
    got_c, got_n = superstep_tpu(ids, nid, my_c, nc, my_d, nd, heuristic)
    want_c, want_n = superstep_ref(ids, nid, my_c, nc, my_d, nd, heuristic)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    winner = k - 1 if heuristic == "id" else 0
    need = np.asarray(got_n)
    assert not need[winner] and need.sum() == k - 1
    assert int(got_c[winner]) == 1
    # losers all refit to 2: beaten neighbors' colors are not forbidden
    losers = np.asarray(got_c)[np.arange(k) != winner]
    np.testing.assert_array_equal(losers, np.full(k - 1, 2))


def test_superstep_kernel_worklist_smaller_than_block():
    """w < block_n: the grid pads the worklist axis; padding lanes must not
    corrupt the live ones nor the returned shapes."""
    args = _random_tile(3, 12, seed=21)
    for block_n in (8, 64, 256):
        got_c, got_n = superstep_tpu(*args, "degree", block_n=block_n)
        want_c, want_n = superstep_ref(*args, "degree")
        assert got_c.shape == (3,) and got_n.shape == (3,)
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
        np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


def test_kernel_backend_matches_pure_jax_engine():
    g = GRAPHS["er"]()
    for mode in ("workefficient", "fused"):
        plain = color_data_driven(g, mode=mode)
        kern = color_data_driven(g, mode=mode, backend="pallas")
        assert (plain.colors == kern.colors).all(), mode
        assert plain.iterations == kern.iterations


# --------------------------------------------------------------------------
# engine bit-identity: ragged == padded == fused, tiled == untiled
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gname", list(GRAPHS))
def test_padded_and_ragged_engines_bit_identical(gname):
    g = GRAPHS[gname]()
    base = color_data_driven(g)
    assert is_valid_coloring(g, base.colors)
    assert base.converged
    for opts in (
        dict(engine="padded"),
        dict(engine="padded", mode="fused"),
        dict(mode="fused"),
        dict(tiling=None),
        dict(buckets=(8, 32)),
        dict(engine="padded", buckets=(8, 32)),
    ):
        r = color_data_driven(g, **opts)
        assert (r.colors == base.colors).all(), (gname, opts)
        assert r.iterations == base.iterations, (gname, opts)


def test_padded_work_counts_gather_cells():
    """Satellite: padded_work is lanes × tile width, so the ragged engine's
    bandwidth saving on skewed graphs is visible in the accounting."""
    g = GRAPHS["powerlaw"]()
    ragged = color_data_driven(g, buckets=(8, 32), tail_serial=None)
    padded = color_data_driven(g, buckets=(8, 32), engine="padded",
                               tail_serial=None)
    assert (ragged.colors == padded.colors).all()
    # identical schedule, but the ragged engine touches far fewer cells
    assert ragged.padded_work < padded.padded_work / 2


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="unknown engine"):
        color_data_driven(GRAPHS["grid"](), engine="nope")


# --------------------------------------------------------------------------
# adaptive tail-serialization
# --------------------------------------------------------------------------

def test_tail_serialization_collapses_cascades():
    """Acceptance: >=3x fewer super-steps on the cascading circuit graphs."""
    for name in ("G3_circuit", "thermal2"):
        g = build_graph(name, 0.01)
        tail = color_data_driven(g)
        free = color_data_driven(g, tail_serial=None)
        assert is_valid_coloring(g, tail.colors), name
        assert tail.converged
        assert tail.iterations * 3 <= free.iterations, (
            name, tail.iterations, free.iterations)
        # quality stays within +1 of the serial greedy oracle on cascades
        assert tail.num_colors <= num_colors(greedy_serial(g)) + 1, name


def test_tail_disabled_still_converges():
    g = GRAPHS["er"]()
    r = color_data_driven(g, tail_serial=None)
    assert r.converged and is_valid_coloring(g, r.colors)


def test_explicit_tail_threshold():
    g = GRAPHS["er"]()
    r = color_data_driven(g, tail_serial=g.n + 1)  # serialize everything
    assert r.converged and is_valid_coloring(g, r.colors)
    assert r.iterations <= 2  # bootstrap + one serial pass


def test_tail_modes_and_engines_agree():
    g = build_graph("thermal2", 0.01)  # stall-triggered tail
    base = color_data_driven(g)
    for opts in (dict(mode="fused"), dict(engine="padded"),
                 dict(engine="padded", mode="fused")):
        r = color_data_driven(g, **opts)
        assert (r.colors == base.colors).all(), opts
        assert r.iterations == base.iterations, opts


# --------------------------------------------------------------------------
# CSR-native storage
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gname", list(GRAPHS))
def test_device_csr_gather_matches_padded_adjacency(gname):
    g = GRAPHS[gname]()
    dcsr = DeviceCSR.from_csr(g)
    W = max(g.max_degree, 1)
    ids = np.asarray([0, 1, g.n // 2, g.n - 1, g.n], np.int32)  # incl sentinel
    got = np.asarray(dcsr.gather_rows(jnp.asarray(ids), W))
    dense = g.padded_adjacency(W)
    want = np.concatenate([dense[ids[:-1]], np.full((1, W), g.n, np.int32)])
    np.testing.assert_array_equal(got, want)
    for v in ids:
        np.testing.assert_array_equal(
            np.asarray(dcsr.gather_row1(jnp.int32(v))),
            want[min(int(v), len(ids) - 1)] if v == g.n else dense[v],
        )


def test_auto_tile_thresholds_properties():
    deg = np.concatenate([np.full(5000, 3), np.full(400, 20), np.full(40, 200)])
    ts = auto_tile_thresholds(deg)
    assert ts and list(ts) == sorted(ts)           # ascending log-spaced
    assert all(t >= 8 for t in ts)
    # tiny graphs and flat histograms: single class
    assert auto_tile_thresholds(np.full(100, 50)) == ()
    assert auto_tile_thresholds(np.full(5000, 9)) == ()


# --------------------------------------------------------------------------
# satellite regressions
# --------------------------------------------------------------------------

def test_classic_fused_forwards_reuse_rows(monkeypatch):
    """Regression: reuse_rows was silently dropped by the classic fused driver."""
    seen = {}
    orig = C.sgr_step

    def spy(*args, **kwargs):
        seen.update(kwargs)
        return orig(*args, **kwargs)

    monkeypatch.setattr(C, "sgr_step", spy)
    g = erdos_renyi(300, 5.0, seed=3)
    r = color_data_driven(g, engine="classic", mode="fused", reuse_rows=True)
    assert seen.get("reuse_rows") is True
    base = color_data_driven(g, engine="classic", mode="fused")
    assert (r.colors == base.colors).all()  # pure perf knob: same colors


@pytest.mark.parametrize("buckets", [(), (8, 32)])
@pytest.mark.parametrize("lanes", [64, 300, 10**6])
def test_coarsen_lanes_derivation(monkeypatch, buckets, lanes):
    """Satellite: coarsen_lanes derives ceil(cap / lanes) chunks per step and
    the derived chunking is bit-identical to the explicit equivalent."""
    recorded = []
    orig = C._tiled_superstep

    def spy(provider, deg_ext, colors_ext, wls, **kw):
        recorded.append((tuple(int(w.shape[0]) for w in wls), kw["chunks"]))
        return orig(provider, deg_ext, colors_ext, wls, **kw)

    monkeypatch.setattr(C, "_tiled_superstep", spy)
    # also patch the jitted wrapper used by the workefficient driver
    monkeypatch.setattr(
        C, "provider_tiled_superstep",
        lambda provider, deg_ext, colors_ext, wls, **kw: spy(
            provider, deg_ext, colors_ext, wls, **kw),
    )
    g = erdos_renyi(700, 6.0, seed=4)
    r = color_data_driven(g, coarsen_lanes=lanes, buckets=buckets)
    assert is_valid_coloring(g, r.colors)
    assert recorded
    for caps, chunks in recorded:
        assert chunks == tuple(max(1, math.ceil(c / lanes)) for c in caps)
    # derived chunking == equivalent explicit coarsen_ff, bit for bit
    if lanes >= 10**6:
        explicit = color_data_driven(g, coarsen_ff=1, buckets=buckets)
        assert (r.colors == explicit.colors).all()
        assert r.iterations == explicit.iterations


def test_classic_engine_unchanged_contract():
    g = GRAPHS["grid"]()
    r = color_data_driven(g, engine="classic")
    assert is_valid_coloring(g, r.colors)
    assert r.converged
    assert r.num_colors <= g.max_degree + 1


# --------------------------------------------------------------------------
# CSR-resident fused kernel (DESIGN.md §18): gathers straight from R/C
# --------------------------------------------------------------------------

def _csr_inputs(g, seed, extra_sentinels=0):
    """(DeviceCSR, colors_ext, packed table, full worklist) for ``g``."""
    rng = np.random.default_rng(seed)
    dev = DeviceCSR.from_csr(g)
    W = dev.max_width
    colors = rng.integers(0, W + 2, g.n).astype(np.int32)
    colors_ext = jnp.asarray(np.concatenate([colors, [0]]).astype(np.int32))
    wl = np.arange(g.n, dtype=np.int32)
    if extra_sentinels:
        wl = np.concatenate([wl, np.full(extra_sentinels, g.n, np.int32)])
    return dev, colors_ext, colors_ext + (dev.deg_ext << 16), jnp.asarray(wl)


def _gathered_step(dev, colors_ext, wl, W, heuristic):
    rows = dev.gather_rows(wl, W)
    return superstep_tpu(wl, rows, colors_ext[wl], colors_ext[rows],
                         dev.deg_ext[wl], dev.deg_ext[rows], heuristic)


def _mask(wl, n, newc, need):
    valid = wl < n
    return jnp.where(valid, newc, 0), need & valid


@pytest.mark.parametrize("W", [31, 32, 63, 64])
@pytest.mark.parametrize("heuristic", ["id", "degree"])
def test_csr_kernel_word_boundary_widths(W, heuristic):
    """A (W+1)-clique puts every row at degree exactly W — the gather width
    sits at (or one past) a 32-bit bitset word boundary, where an off-by-one
    in the kernel's nwords or lane masking would corrupt colors."""
    k = W + 1
    src, dst = np.triu_indices(k, 1)
    g = csr_from_edges(k, src, dst)
    dev, colors_ext, packed, wl = _csr_inputs(g, seed=W)
    g_c, g_n = _gathered_step(dev, colors_ext, wl, W, heuristic)
    c_c, c_n = superstep_csr_tpu(dev.row_starts, dev.col_padded, packed,
                                 wl, W, heuristic)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(c_c))
    np.testing.assert_array_equal(np.asarray(g_n), np.asarray(c_n))


@pytest.mark.parametrize("gname", ["er", "powerlaw", "grid"])
@pytest.mark.parametrize("heuristic", ["id", "degree"])
def test_csr_kernel_ragged_rows_match_gathered(gname, heuristic):
    """Ragged degrees: lanes past a row's degree alias the NEXT row's ids in
    raw C storage — the kernel must mask them to the inert sentinel, exactly
    reproducing DeviceCSR.gather_rows + the packed pure-JAX gather."""
    g = GRAPHS[gname]()
    dev, colors_ext, packed, wl = _csr_inputs(g, seed=17)
    W = dev.max_width
    g_c, g_n = _gathered_step(dev, colors_ext, wl, W, heuristic)
    c_c, c_n = superstep_csr_tpu(dev.row_starts, dev.col_padded, packed,
                                 wl, W, heuristic)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(c_c))
    np.testing.assert_array_equal(np.asarray(g_n), np.asarray(c_n))


def test_csr_kernel_sentinel_padded_worklist():
    """Pow2-padded worklists (dynamic sessions) carry trailing sentinel ids;
    after the caller-side validity mask both kernels must agree and the
    sentinel lanes must come back inert (color 0, need False)."""
    g = GRAPHS["er"]()
    dev, colors_ext, packed, wl = _csr_inputs(g, seed=23, extra_sentinels=37)
    W = dev.max_width
    g_c, g_n = _mask(wl, g.n, *_gathered_step(dev, colors_ext, wl, W,
                                              "degree"))
    c_c, c_n = _mask(wl, g.n, *superstep_csr_tpu(
        dev.row_starts, dev.col_padded, packed, wl, W, "degree"))
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(c_c))
    np.testing.assert_array_equal(np.asarray(g_n), np.asarray(c_n))
    assert not np.asarray(c_n)[g.n:].any()
    assert (np.asarray(c_c)[g.n:] == 0).all()


@pytest.mark.parametrize("block_n", [8, 16, 128])
def test_csr_kernel_block_sizes(block_n):
    g = GRAPHS["er"]()
    dev, colors_ext, packed, wl = _csr_inputs(g, seed=29)
    W = dev.max_width
    g_c, g_n = _gathered_step(dev, colors_ext, wl, W, "degree")
    c_c, c_n = superstep_csr_tpu(dev.row_starts, dev.col_padded, packed,
                                 wl, W, "degree", block_n=block_n)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(c_c))
    np.testing.assert_array_equal(np.asarray(g_n), np.asarray(c_n))


def test_csr_kernel_empty():
    c, n = superstep_csr_tpu(jnp.zeros(3, jnp.int32), jnp.zeros(4, jnp.int32),
                             jnp.zeros(3, jnp.int32), jnp.zeros(0, jnp.int32),
                             4)
    assert c.shape == (0,) and n.shape == (0,)


@pytest.mark.parametrize("gname", ["er", "grid", "powerlaw"])
@pytest.mark.parametrize("kind", ["bitset", "scan"])
def test_csr_tail_matches_serial_tail_oracle(gname, kind):
    """The grid=1 on-device tail vs the fori_loop ``serial_tail_step``: the
    same clear-then-sequential-FirstFit over the live state, so colors must
    match bit for bit regardless of the FirstFit kind (every kind returns
    the smallest free color)."""
    g = GRAPHS[gname]()
    dev, colors_ext, _, _ = _csr_inputs(g, seed=31)
    W = dev.max_width
    rng = np.random.default_rng(37)
    wl = rng.choice(g.n, min(64, g.n), replace=False).astype(np.int32)
    wl = np.concatenate([wl, np.full(7, g.n, np.int32)])  # sentinel padding
    wl = C.order_tail(jnp.asarray(wl), dev.deg_ext)
    want = C.serial_tail_step(dev.row1, colors_ext, wl, kind)
    got = serial_tail_csr_tpu(dev.row_starts, dev.col_padded, dev.deg_ext,
                              colors_ext, wl, W)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# --------------------------------------------------------------------------
# the round tail: serial_tail_step's coloring by dependency rounds
# --------------------------------------------------------------------------

TAIL_SHAPES = ["stalled_all", "live_subset", "padded_subset"]


def _round_tail_inputs(g, shape, dense=False):
    """(graph, provider, colors_ext, ordered tail, tail ids) for one
    of the three tail shapes the engine hands over: every vertex from a
    cleared state (the cold stall), a live third of the vertices among
    settled colors (a warm start), and that subset plus degree-0 vertices
    and trailing sentinels (a pow2-padded buffer)."""
    n0 = g.n
    if shape == "padded_subset":  # five isolated vertices at the end
        g = CSRGraph(np.concatenate([g.row_offsets,
                                     np.full(5, g.row_offsets[-1])]),
                     g.col_indices)
    n = g.n
    dev = DeviceCSR.from_csr(g)
    provider = C.DenseRows(jnp.asarray(g.padded_adjacency())) if dense \
        else dev
    rng = np.random.default_rng(41)
    if shape == "stalled_all":
        colors = np.zeros(n, np.int32)
        tail = np.arange(n, dtype=np.int32)
    else:
        colors = rng.integers(1, dev.max_width + 2, n).astype(np.int32)
        tail = rng.choice(n0, n0 // 3, replace=False).astype(np.int32)
        if shape == "padded_subset":
            tail = np.concatenate([tail, np.arange(n0, n, dtype=np.int32)])
    colors_ext = jnp.asarray(np.concatenate([colors, [0]]).astype(np.int32))
    wl = tail
    if shape == "padded_subset":
        wl = np.concatenate([wl, np.full(7, n, np.int32)])
    wl = C.order_tail(jnp.asarray(wl), dev.deg_ext)
    return g, provider, colors_ext, wl, tail


def _longest_predecessor_chain(g, order):
    """Plain reference: the longest path of tail vertices, each adjacent to
    and earlier in ``order`` than the next."""
    rank = {int(v): i for i, v in enumerate(order)}
    level = {}
    for v in order:
        v = int(v)
        level[v] = 1 + max((level[int(u)] for u in g.neighbors(v)
                            if rank.get(int(u), len(rank)) < rank[v]),
                           default=0)
    return max(level.values(), default=0)


@pytest.mark.parametrize("shape", TAIL_SHAPES)
@pytest.mark.parametrize("gname", ["er", "grid", "powerlaw"])
@pytest.mark.parametrize("kind", ["bitset", "scan"])
def test_round_tail_matches_serial_tail_step(gname, kind, shape):
    """Bit for bit the fori_loop's sequential greedy: a frontier vertex
    reads its predecessors' colors and the settled ones, and its later
    neighbors still cleared, as the loop shows it at its turn."""
    g, dev, colors_ext, wl, tail = _round_tail_inputs(GRAPHS[gname](), shape)
    want = C.serial_tail_step(dev.row1, colors_ext, wl, kind)
    got, rounds = C.round_tail(dev, colors_ext, wl, tail, kind)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert 0 < rounds <= tail.size


@pytest.mark.parametrize("shape", TAIL_SHAPES)
def test_round_tail_matches_serial_tail_step_on_dense_rows(shape):
    g, dense, colors_ext, wl, tail = _round_tail_inputs(
        GRAPHS["powerlaw"](), shape, dense=True)
    want = C.serial_tail_step(dense.row1, colors_ext, wl, "bitset")
    got, _ = C.round_tail(dense, colors_ext, wl, tail)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_round_tail_stalled_all_is_largest_degree_first_greedy(gname):
    g, dev, colors_ext, wl, tail = _round_tail_inputs(GRAPHS[gname](),
                                                      "stalled_all")
    got, _ = C.round_tail(dev, colors_ext, wl, tail)
    np.testing.assert_array_equal(
        np.asarray(got)[:g.n], greedy_serial(g, "largest_degree_first"))


@pytest.mark.parametrize("shape", TAIL_SHAPES)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_round_tail_rounds_are_the_longest_predecessor_chain(gname, shape):
    g, dev, colors_ext, wl, tail = _round_tail_inputs(GRAPHS[gname](), shape)
    _, rounds = C.round_tail(dev, colors_ext, wl, tail)
    order = np.asarray(wl)
    assert rounds == _longest_predecessor_chain(g, order[order < g.n])


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_tail_classes_partition_the_tail_at_covering_widths(gname):
    g = GRAPHS[gname]()
    dev = DeviceCSR.from_csr(g)
    tail = np.random.default_rng(5).permutation(g.n)[:g.n // 2].astype(
        np.int32)
    members, widths = C._tail_classes(dev, tail)
    assert sorted(np.concatenate(members).tolist()) == sorted(tail.tolist())
    for m, w in zip(members, widths):
        assert w == dev.max_width or (w >= C.TAIL_MIN_WIDTH and w & (w - 1) == 0)
        assert g.degrees[m].max() <= w
        assert w == C.TAIL_MIN_WIDTH or g.degrees[m].max() > w // 2
    dense = C.DenseRows(jnp.asarray(g.padded_adjacency()))
    assert C._tail_classes(dense, tail)[1] == [None]


@pytest.mark.parametrize("shape", TAIL_SHAPES)
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_tail_counters_along_c_match_the_tiles(gname, shape):
    """The predecessor counters counted once along C (a large tail) equal
    those counted over the classes' padded tiles (a small one)."""
    g, dev, colors_ext, wl, tail = _round_tail_inputs(GRAPHS[gname](), shape)
    members, widths = C._tail_classes(dev, tail)
    padded = tuple(jnp.asarray(np.concatenate(
        [m, np.full(C.ROUND_CAP_FLOOR, g.n, np.int32)])) for m in members)
    tiles, along_c = (C.provider_tail_setup(
        dev, colors_ext, wl, padded, widths=tuple(widths), by_entries=b)
        for b in (False, True))
    for a, b in zip(tiles, along_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(tiles[1]).max() > 0


@pytest.mark.parametrize("case", ["distance2", "bipartite"])
def test_round_tail_matches_serial_tail_on_two_hop_rows(case):
    """Composed two-hop rows carry duplicate and self lanes; their
    multiplicities are symmetric (paths of length one and two, shared
    rows), so every lane counted against a vertex is taken off again."""
    from repro.d2.bipartite import BipartiteGraph
    from repro.d2.coloring import TwoHopRows
    from repro.graphs import jacobian_tall_skinny

    if case == "distance2":
        g = erdos_renyi(300, 4.0, seed=5)
        adj = jnp.asarray(g.padded_adjacency())
        prov, deg = TwoHopRows(adj, adj, include_first_hop=True), g.degrees
    else:
        bg = jacobian_tall_skinny(200, 24, nnz_per_row=3, seed=1)
        assert isinstance(bg, BipartiteGraph)
        c2r, r2c = bg.padded_halves()
        prov = TwoHopRows(jnp.asarray(c2r), jnp.asarray(r2c),
                          include_first_hop=False)
        deg = bg.col_degrees
    n = int(deg.size)
    deg_ext = jnp.asarray(np.concatenate([deg, [0]]).astype(np.int32))
    rng = np.random.default_rng(43)
    colors = rng.integers(1, 6, n).astype(np.int32)
    for tail in (np.arange(n, dtype=np.int32),
                 rng.choice(n, n // 2, replace=False).astype(np.int32)):
        colors_ext = jnp.asarray(np.concatenate([colors, [0]]))
        wl = C.order_tail(jnp.asarray(tail), deg_ext)
        want = C.serial_tail_step(prov.row1, colors_ext, wl, "bitset")
        got, _ = C.round_tail(prov, colors_ext, wl, tail)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("n", [1, 1000, 1024, 3000])
@pytest.mark.parametrize("share", [0.0, 0.02, 1.0])
def test_nth_true_matches_searchsorted(n, share):
    """The frontier pick: the block-then-halve search finds the positions
    ``jnp.searchsorted`` finds, the sentinel n past the last true flag."""
    flags = np.random.default_rng(n).random(n) < share
    csum = jnp.cumsum(jnp.asarray(flags), dtype=jnp.int32)
    for cap in (1, 256, 4096):
        want = jnp.searchsorted(csum, jnp.arange(1, cap + 1, dtype=jnp.int32),
                                side="left")
        np.testing.assert_array_equal(np.asarray(C._nth_true(csum, cap)),
                                      np.asarray(want))


def test_prefix_sum_matches_cumsum():
    x = np.random.default_rng(3).integers(-5, 6, (3, 5000)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(C._prefix_sum(jnp.asarray(x))),
                                  np.cumsum(x, axis=1))


def test_round_tail_refuses_rows_that_are_not_symmetric():
    # vertex 1 lists 0, which precedes it; 0 does not list 1, so 1 waits
    # for a decrement that never comes
    adj = jnp.asarray(np.array([[3], [0], [3]], np.int32))
    colors_ext = jnp.zeros(4, jnp.int32)
    wl = jnp.arange(3, dtype=jnp.int32)
    with pytest.raises(ValueError, match="not symmetric"):
        C.round_tail(C.DenseRows(adj), colors_ext, wl,
                     np.arange(3, dtype=np.int32))


# --------------------------------------------------------------------------
# hubs past the packed gather: planning, FirstFit and the tail bounded
# --------------------------------------------------------------------------

GRAPH500 = (0.57, 0.19, 0.19, 0.05)


def _kron_with_hub(scale, hub, seed=0):
    """A Graph500 Kronecker graph of 2^scale vertices (edge factor 16) and
    one more vertex joined to the first ``hub`` vertices (leaves added as
    needed): a star hub of degree ``hub``."""
    g0 = rmat(2**scale, 32.0, GRAPH500, seed=seed)
    src, dst = g0.edges()
    n = max(g0.n, hub) + 1
    return csr_from_edges(n, np.concatenate([src, np.full(hub, n - 1)]),
                          np.concatenate([dst, np.arange(hub)]))


def _improper(g, colors):
    """``bench/reference.py``'s count, independent of the program."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "reference.py"
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    src, dst = g.edges()
    return ref.improper_vertices(g.n, src, dst, colors)


@pytest.fixture(scope="module")
def hub_run():
    """SCALE 12 plus a star hub of degree 33,000 through ``repro.color``."""
    import repro
    from repro.obs.spans import SpanRecorder

    g = _kron_with_hub(12, 33000)
    with SpanRecorder() as rec:
        r = repro.color(g, "data_driven")
    return g, r, rec.events


def test_hub_graph_colors_as_the_largest_degree_first_greedy(hub_run):
    """Past the packed gather's 2^15 (colors and degrees gathered apart),
    the coloring is proper, and with the stall handing every vertex to the
    tail it is the host's largest-degree-first greedy, vertex for vertex."""
    g, r, events = hub_run
    assert g.max_degree > 32767 and not C._packed_gather_ok(g.max_degree)
    assert _improper(g, r.colors) == 0
    tails = [e.meta for e in events if e.name == "serial_tail"]
    assert len(tails) == 1 and tails[0]["stalled"]
    assert tails[0]["vertices"] == g.n
    np.testing.assert_array_equal(r.colors,
                                  greedy_serial(g, "largest_degree_first"))


def test_hub_graph_plan_gathers_at_most_three_lanes_per_entry(hub_run):
    g, r, events = hub_run
    steps = [e.meta for e in events if e.name == "superstep"]
    assert steps and all(m["cells"] <= 3 * g.m for m in steps)
    classes, widths = C._resolve_classes(g.degrees, (), "auto")
    assert widths[0] == g.max_degree and classes[0].size == 1  # the hub
    # the colors in play, not the hub's width, set the bitset's words
    tail = [e.meta for e in events if e.name == "serial_tail"][0]
    assert max(m["ff_words"] for m in steps) == FF_MIN_WORDS
    assert tail["ff_words"] == FF_MIN_WORDS and tail["cells"] > g.m


def _classes_before(degrees):
    """The plan before wide classes were split: ``auto_tile_thresholds``'
    classes, degree-0 vertices in the narrowest, wide first."""
    dmax = max(int(degrees.max(initial=0)), 1)
    ts = auto_tile_thresholds(degrees)
    if not ts:
        return [np.arange(degrees.size)], [dmax]
    bounds = list(ts) + [dmax]
    classes, lo = [], 0
    for hi in bounds:
        classes.append(np.where((degrees > lo) & (degrees <= hi))[0])
        lo = hi
    classes[0] = np.concatenate([np.where(degrees == 0)[0], classes[0]])
    pairs = [(c, min(b, dmax)) for c, b in zip(classes, bounds) if c.size]
    return ([c for c, _ in pairs[::-1]], [w for _, w in pairs[::-1]])


@pytest.mark.parametrize("n,seed", [(2**12, 3), (2**14, 3), (2**16, 5)])
def test_rmat_g_recipe_keeps_its_class_plan_and_tail_shapes(n, seed):
    """rmat-g's recipe: every automatic class gathers under
    ``PLAN_LANES_PER_ENTRY`` lanes per entry, so none is split, and the
    round tail pads each class to the lanes it always did."""
    g = rmat(n, 10.0, seed=seed)
    classes, widths = C._resolve_classes(g.degrees, (), "auto")
    want_c, want_w = _classes_before(g.degrees)
    assert widths == want_w
    for got, want in zip(classes, want_c):
        np.testing.assert_array_equal(got, want)
    dev = DeviceCSR.from_csr(g)
    members, tail_widths = C._tail_classes(dev, np.arange(g.n, dtype=np.int32))
    for m, w in zip(members, tail_widths):
        for c in (1, 100, 256, 257, m.size):
            assert C._round_lanes(c, w) == max(C.ROUND_CAP_FLOOR,
                                               next_pow2(c))


@pytest.mark.parametrize("width", [16, 376, 512, 1024, 4096, 162605])
def test_round_lanes_bound_the_floor_by_cells(width):
    for count in (1, 3, 200, 5000):
        lanes = C._round_lanes(count, width)
        assert lanes >= count and lanes & (lanes - 1) == 0
        assert lanes * width <= max(C.ROUND_FLOOR_CELLS, width,
                                    2 * count * width)
    assert C._round_lanes(1, 162605) == 1
    assert C._round_lanes(1, 376) == C.ROUND_CAP_FLOOR


def test_split_classes_gather_under_two_lanes_per_entry():
    g = _kron_with_hub(12, 33000)
    classes, widths = C._resolve_classes(g.degrees, (), "auto")
    assert sorted(np.concatenate(classes).tolist()) == list(range(g.n))
    assert widths == sorted(widths, reverse=True)
    for ids, w in zip(classes, widths):
        deg = g.degrees[ids]
        assert deg.max() <= w
        assert ids.size * w <= max(C.PLAN_LANES_PER_ENTRY * deg.sum(),
                                   2 * deg.sum(), w)


@pytest.mark.parametrize("add", [False, True])
@pytest.mark.parametrize("n", [64, 200, 257])
def test_blocked_scatter_matches_one_scatter(monkeypatch, n, add):
    """Blocks of 64: every index, the sentinel past the end and repeated
    ones land as one scatter puts them."""
    monkeypatch.setattr(C, "SCATTER_BLOCK", 64)
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(-5, 5, n).astype(np.int32))
    idx = jnp.asarray(np.concatenate([rng.permutation(n)[:n // 2],
                                      [n, n + 1, 0, 0]]).astype(np.int32))
    vals = jnp.asarray(rng.integers(1, 9, idx.shape[0]).astype(np.int32))
    if add:
        want = x.at[idx].add(vals, mode="drop")
        got = C.blocked_scatter(x, idx, vals, add=True)
    else:  # a repeated index takes one value: the same one either way
        vals = vals.at[-1].set(vals[-2])
        want = x.at[idx].set(vals, mode="drop")
        got = C.blocked_scatter(x, idx, vals)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bounded_superstep_firstfit_is_bit_identical():
    """A hub 700 wide: the workefficient super-steps read the colors back
    and cap the bitset at 16 of the hub class's 22 words; the fused loop
    runs the full bitset.  Without the tail the super-steps do all the
    coloring, and both give the same colors."""
    from repro.obs.spans import SpanRecorder

    g = _kron_with_hub(10, 700, seed=1)
    with SpanRecorder() as rec:
        bounded = color_data_driven(g, tail_serial=None)
    full = color_data_driven(g, mode="fused", tail_serial=None)
    np.testing.assert_array_equal(bounded.colors, full.colors)
    assert bounded.converged and is_valid_coloring(g, bounded.colors)
    words = [e.meta["ff_words"] for e in rec.events if e.name == "superstep"]
    assert words and max(words) == FF_MIN_WORDS < (g.max_degree + 32) // 32


def _eqn_shapes(jaxpr, out):
    """All operand/result shapes in ``jaxpr``, recursing through sub-jaxprs
    but NOT into pallas_call bodies (kernel-internal VMEM tiles are the
    point of the CSR path — only host-visible arrays count)."""
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            continue
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                out.add(tuple(aval.shape))
        for val in eqn.params.values():
            if hasattr(val, "jaxpr"):          # ClosedJaxpr
                _eqn_shapes(val.jaxpr, out)
            elif hasattr(val, "eqns"):         # raw Jaxpr
                _eqn_shapes(val, out)


def test_csr_superstep_jaxpr_has_no_materialized_tile():
    """Acceptance (§18): the CSR path's superstep jaxpr contains no
    ``(w, W)`` array — the gather happens inside the kernel — while the
    gathered-kernel path provably materializes that tile in HBM."""
    g = GRAPHS["er"]()
    dev = DeviceCSR.from_csr(g)
    W = dev.max_width
    w = 200  # not a multiple of 8: distinct from any kernel-internal block
    wl = jnp.arange(w, dtype=jnp.int32)
    colors_ext = jnp.zeros(g.n + 1, jnp.int32)

    def step(use_kernel):
        def f(colors_ext, wl):
            return C.ragged_superstep(
                lambda ids: dev.gather_rows(ids, W), dev.deg_ext,
                colors_ext, wl, use_kernel=use_kernel, pack_degrees=True,
                provider=dev, width=W)
        return jax.make_jaxpr(f)(colors_ext, wl)

    shapes_csr, shapes_gathered = set(), set()
    _eqn_shapes(step("csr").jaxpr, shapes_csr)
    _eqn_shapes(step(True).jaxpr, shapes_gathered)
    assert (w, W) not in shapes_csr, "CSR path materialized a gather tile"
    assert (w, W) in shapes_gathered  # the control: gathered path does


def test_pick_block_n_vmem_accounting():
    """Satellite: the VMEM budget must cover the bitset words and the
    first-fit (nwords, 32) expansion, not just the input tiles — at large W
    the old divisor (W*4*3) overshot the budget by ~45%."""
    from repro.kernels.superstep.ops import _VMEM_BUDGET, _pick_block_n

    for W in (16, 100, 1000, 5000, 20000):
        for tiles in (3, 4):
            bn = _pick_block_n(10**6, W, tiles=tiles)
            nwords = (W + 1 + 31) // 32
            per_row = tiles * W * 4 + nwords * 4 + nwords * 32 * 4
            assert bn >= 8 and bn % 8 == 0
            # the floor of 8 rows may exceed the budget by construction at
            # extreme W; otherwise the working set must fit
            if bn > 8:
                assert bn * per_row <= _VMEM_BUDGET, (W, tiles, bn)


def test_csr_backend_no_silent_tile_in_engine(monkeypatch):
    """backend='pallas-csr' on the ragged engine must route through the CSR
    kernel (not silently fall back to the gathered kernel) when the packed
    gather is legal and the provider is a DeviceCSR."""
    import repro.kernels.superstep.csr_kernel as ck

    calls = {"step": 0, "tail": 0}
    orig_step, orig_tail = ck.superstep_csr_tpu, ck.serial_tail_csr_tpu
    monkeypatch.setattr(ck, "superstep_csr_tpu",
                        lambda *a, **k: (calls.__setitem__(
                            "step", calls["step"] + 1), orig_step(*a, **k))[1])
    monkeypatch.setattr(ck, "serial_tail_csr_tpu",
                        lambda *a, **k: (calls.__setitem__(
                            "tail", calls["tail"] + 1), orig_tail(*a, **k))[1])
    g = GRAPHS["grid"]()  # cascades: exercises the on-device tail too
    base = color_data_driven(g, backend="jax", tail_serial="auto")
    r = color_data_driven(g, backend="pallas-csr", tail_serial="auto")
    assert calls["step"] > 0, "CSR superstep kernel never engaged"
    assert calls["tail"] > 0, "on-device CSR tail never engaged"
    np.testing.assert_array_equal(base.colors, r.colors)
    assert base.iterations == r.iterations
