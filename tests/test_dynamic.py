"""Streaming incremental recoloring engine (DESIGN.md §14).

Covers the DeltaCSR overlay semantics (property-style consistency against a
rebuilt-from-scratch CSR), the ColoringSession guarantees (validity after
churn, empty-delta bit-identity, full=True cold parity, frontier-
proportional work), the batch-session layer, and the api registration.
"""
import numpy as np
import pytest

from repro import api
from repro.core import (
    SessionBatch,
    color_data_driven,
    is_valid_coloring,
    open_session_batch,
)
from repro.core.csr import csr_from_edges
from repro.core.serial import color_serial
from repro.dynamic import (
    ColoringSession,
    DeltaCSR,
    churn_delta,
    open_session,
)
from repro.graphs import build_graph, erdos_renyi, grid2d, power_law

# --------------------------------------------------------------------------
# DeltaCSR: overlay semantics vs a rebuilt-from-scratch CSR
# --------------------------------------------------------------------------


def _ref_apply(edges: set, op: str, a, b):
    """Track the same mutation on a plain python edge set (the oracle)."""
    for x, y in zip(np.atleast_1d(a), np.atleast_1d(b)):
        x, y = int(x), int(y)
        if x == y:
            continue
        if op == "add":
            edges.add((min(x, y), max(x, y)))
        else:
            edges.discard((min(x, y), max(x, y)))


def _ref_graph(n: int, edges: set):
    if not edges:
        return csr_from_edges(n, np.zeros(0, np.int64), np.zeros(0, np.int64))
    arr = np.array(sorted(edges), dtype=np.int64)
    return csr_from_edges(n, arr[:, 0], arr[:, 1])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_delta_random_sequence_matches_scratch_rebuild(seed):
    """Random insert/delete batches == rebuilding the CSR from scratch."""
    g0 = erdos_renyi(300, 5.0, seed=seed)
    d = DeltaCSR(g0, compact_frac=0.2)
    src, dst = g0.edges()
    und = src < dst
    ref = set(zip(src[und].tolist(), dst[und].tolist()))
    rng = np.random.default_rng(seed + 100)
    for step in range(30):
        k = int(rng.integers(1, 20))
        if rng.random() < 0.5:
            a = rng.integers(0, d.n, k)
            b = rng.integers(0, d.n, k)
            d.add_edges(a, b)
            _ref_apply(ref, "add", a, b)
        else:
            # mix genuine deletions with misses (no-ops)
            cs, cd = d.graph().edges()
            if cs.size:
                pick = rng.integers(0, cs.size, k)
                a, b = cs[pick], cd[pick]
            else:
                a = rng.integers(0, d.n, k)
                b = rng.integers(0, d.n, k)
            d.remove_edges(a, b)
            _ref_apply(ref, "del", a, b)
        if step % 7 == 3:
            gr = _ref_graph(d.n, ref)
            gc = d.graph()
            np.testing.assert_array_equal(gc.row_offsets, gr.row_offsets)
            np.testing.assert_array_equal(gc.col_indices, gr.col_indices)
    gc = d.compact()
    gr = _ref_graph(d.n, ref)
    np.testing.assert_array_equal(gc.row_offsets, gr.row_offsets)
    np.testing.assert_array_equal(gc.col_indices, gr.col_indices)
    np.testing.assert_array_equal(gc.degrees, gr.degrees)
    assert d.overlay_size == 0


def test_delta_noop_mutations_dirty_nothing():
    g = grid2d(6, 6)
    d = DeltaCSR(g)
    src, dst = g.edges()
    # re-adding existing edges: no-op, no dirty ids
    assert d.add_edges(src[:5], dst[:5]).size == 0
    # removing absent edges: no-op
    assert d.remove_edges([0, 1], [35, 30]).size == 0
    assert d.overlay_size == 0
    np.testing.assert_array_equal(d.graph().col_indices, g.col_indices)
    # self loops are dropped
    assert d.add_edges([3, 3], [3, 3]).size == 0


def test_delta_add_remove_roundtrip_restores_graph():
    g = erdos_renyi(120, 4.0, seed=9)
    d = DeltaCSR(g)
    dirty = d.add_edges([0, 1], [50, 60])
    assert set(dirty.tolist()) == {0, 1, 50, 60}
    dirty = d.remove_edges([0, 1], [50, 60])
    assert set(dirty.tolist()) == {0, 1, 50, 60}
    gc = d.compact()
    np.testing.assert_array_equal(gc.row_offsets, g.row_offsets)
    np.testing.assert_array_equal(gc.col_indices, g.col_indices)


def test_delta_vertex_semantics():
    g = grid2d(5, 5)
    d = DeltaCSR(g)
    ids = d.add_vertices(3)
    np.testing.assert_array_equal(ids, [25, 26, 27])
    assert d.n == 28 and d.graph().n == 28
    assert d.graph().degrees[25:].sum() == 0  # isolated until wired
    d.add_edges([25, 26], [0, 25])
    assert d.graph().degrees[25] == 2
    # removing a vertex drops its edges but keeps the slot (ids stable)
    touched = d.remove_vertices([25])
    assert 25 in touched and 0 in touched and 26 in touched
    assert d.n == 28
    assert d.graph().degrees[25] == 0
    # the ex-neighbor kept its other edges
    assert d.graph().degrees[0] == g.degrees[0]
    # removing an already-isolated vertex is a no-op and dirties nobody,
    # even when batched with a connected one (25's removal above isolated 26)
    assert d.remove_vertices([25]).size == 0
    touched = d.remove_vertices([26, 27, 0])
    assert 27 not in touched and 26 not in touched  # both edge-less no-ops
    assert 0 in touched  # 0 really lost its grid edges


def test_delta_auto_compaction_preserves_graph():
    g = erdos_renyi(150, 4.0, seed=3)
    d = DeltaCSR(g, compact_frac=0.01)
    rng = np.random.default_rng(5)
    for _ in range(10):
        d.add_edges(rng.integers(0, 150, 30), rng.integers(0, 150, 30))
    assert d.compactions > 0
    # graph is identical whether or not compaction fired mid-sequence
    d2 = DeltaCSR(g, compact_frac=1e9)
    rng = np.random.default_rng(5)
    for _ in range(10):
        d2.add_edges(rng.integers(0, 150, 30), rng.integers(0, 150, 30))
    assert d2.compactions == 0
    np.testing.assert_array_equal(
        d.graph().col_indices, d2.graph().col_indices)


# --------------------------------------------------------------------------
# ColoringSession guarantees
# --------------------------------------------------------------------------


_churn = churn_delta  # the shared workload generator IS the tested one


def test_open_session_from_coo_and_graph():
    g = erdos_renyi(200, 5.0, seed=1)
    src, dst = g.edges()
    s1 = open_session(src, dst, n=g.n)
    s2 = open_session(g)
    np.testing.assert_array_equal(s1.colors, s2.colors)
    assert s1.validate() and s2.validate()
    with pytest.raises(ValueError, match="max endpoint"):
        open_session([0, 5], [1, 2], n=3)
    with pytest.raises(TypeError, match="CSRGraph"):
        open_session("nope")


def test_cold_session_matches_fused_ragged():
    g = power_law(600, 6.0, seed=7)
    s = open_session(g)
    ref = color_data_driven(g, mode="fused", engine="ragged")
    np.testing.assert_array_equal(s.colors, ref.colors)


def test_empty_delta_recolor_is_bit_identical_noop():
    g = erdos_renyi(300, 5.0, seed=2)
    s = open_session(g)
    before = s.colors.copy()
    r = s.recolor()
    np.testing.assert_array_equal(r.colors, before)
    assert r.work_items == 0 and r.padded_work == 0 and r.iterations == 0
    assert r.converged
    # no-op mutations also leave the frontier empty
    src, dst = g.edges()
    s.apply_delta(add_edges=(src[:3], dst[:3]))
    assert s.frontier().size == 0
    r = s.recolor()
    np.testing.assert_array_equal(r.colors, before)
    assert r.work_items == 0


@pytest.mark.parametrize("mode", ["fused", "workefficient"])
def test_recolor_after_churn_is_valid(mode):
    """Validity matches the serial oracle's on the mutated graph."""
    g = erdos_renyi(800, 6.0, seed=4)
    s = open_session(g, mode=mode)
    rng = np.random.default_rng(11)
    for _ in range(3):
        rem, add = _churn(s.graph, 0.02, rng)
        s.apply_delta(remove_edges=rem, add_edges=add)
        r = s.recolor()
        assert r.converged and r.algorithm == "dynamic_sgr"
        oracle = color_serial(s.graph)
        assert is_valid_coloring(s.graph, r.colors) == is_valid_coloring(
            s.graph, oracle.colors) == True  # noqa: E712


@pytest.mark.parametrize("mode", ["fused", "workefficient"])
def test_stalled_warm_start_tail_matches_the_sequential_tail(monkeypatch,
                                                             mode):
    """A churn delta whose frontier stalls hands the live worklist, among
    settled colors, to the round tail: each tail's colors equal the
    one-vertex-at-a-time ``provider_tail`` over the same inputs."""
    import repro.core.coloring as C
    from repro.graphs import rmat
    from repro.obs.spans import SpanRecorder

    round_tail, checked = C.round_tail, []

    def checking(provider, colors_ext, wl, tail_ids, kind="bitset", **kw):
        out, rounds = round_tail(provider, colors_ext, wl, tail_ids, kind,
                                 **kw)
        want = C.provider_tail(provider, colors_ext, wl, kind=kind)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        checked.append(rounds)
        return out, rounds

    monkeypatch.setattr(C, "round_tail", checking)
    s = open_session(rmat(4000, 9.0, seed=12), mode=mode)
    checked.clear()  # the cold start's tail, if any, is not the point
    rem, add = _churn(s.graph, 0.3, np.random.default_rng(3))
    s.apply_delta(remove_edges=rem, add_edges=add)
    with SpanRecorder() as rec:
        r = s.recolor()
    tails = [e.meta for e in rec.events if e.name == "serial_tail"]
    assert [t["stalled"] for t in tails] == [True]
    assert checked == [tails[0]["rounds"]] and checked[0] > 1
    assert r.converged and s.validate()


def test_insertion_conflict_is_repaired():
    # two same-colored vertices forced adjacent must split colors
    g = grid2d(10, 10)  # bipartite: 2 colors, lots of same-color pairs
    s = open_session(g)
    c = s.colors
    same = np.argwhere(c[:, None] == c[None, :])
    pair = next((p for p in same if p[0] < p[1]), None)
    assert pair is not None
    u, v = int(pair[0]), int(pair[1])
    s.apply_delta(add_edges=([u], [v]))
    r = s.recolor()
    assert r.colors[u] != r.colors[v]
    assert s.validate()


def test_deletion_only_churn_stays_valid():
    g = erdos_renyi(400, 6.0, seed=6)
    s = open_session(g)
    src, dst = g.edges()
    und = src < dst
    s.apply_delta(remove_edges=(src[und][:40], dst[und][:40]))
    r = s.recolor()
    assert s.validate() and r.converged
    assert r.work_items < g.n  # frontier-sized, not n-sized


def test_vertex_stream_grow_and_remove():
    g = erdos_renyi(200, 5.0, seed=8)
    s = open_session(g)
    ids = s.apply_delta(add_vertices=2, add_edges=([200, 200, 201],
                                                   [0, 201, 5]))
    assert {200, 201} <= set(ids.tolist())
    r = s.recolor()
    assert s.n == 202 and r.colors.shape[0] == 202
    assert s.validate()
    assert r.colors[200] > 0 and r.colors[201] > 0
    s.apply_delta(remove_vertices=[200])
    r = s.recolor()
    assert s.validate()
    assert r.colors[200] == 1  # isolated slot takes the trivial color


def test_full_escape_hatch_is_bit_identical_to_cold():
    g = power_law(700, 6.0, seed=10)
    s = open_session(g)
    rng = np.random.default_rng(13)
    rem, add = _churn(g, 0.05, rng)
    s.apply_delta(remove_edges=rem, add_edges=add)
    r = s.recolor(full=True)
    gc = s.delta.graph()
    assert s.delta.overlay_size == 0  # compacted
    ref = color_data_driven(gc, mode="fused", engine="ragged")
    np.testing.assert_array_equal(r.colors, ref.colors)
    assert r.work_items == ref.work_items
    assert s.frontier().size == 0


def test_incremental_work_is_frontier_proportional():
    """Acceptance: >= 5x less work than cold color at 1% churn."""
    g = build_graph("G3_circuit", 0.02)
    s = open_session(g)
    rng = np.random.default_rng(21)
    rem, add = _churn(g, 0.01, rng)
    s.apply_delta(remove_edges=rem, add_edges=add)
    r = s.recolor()
    cold = color_data_driven(s.graph, mode="fused")
    assert s.validate()
    assert cold.work_items >= 5 * r.work_items, (
        f"incremental work {r.work_items} not frontier-proportional vs "
        f"cold {cold.work_items}")


def test_session_survives_many_rounds_with_compaction():
    g = erdos_renyi(500, 5.0, seed=14)
    s = open_session(g, compact_frac=0.05)
    rng = np.random.default_rng(15)
    for _ in range(6):
        rem, add = _churn(s.graph, 0.03, rng)
        s.apply_delta(remove_edges=rem, add_edges=add)
        s.recolor()
        assert s.validate()
    assert s.delta.compactions > 0


# --------------------------------------------------------------------------
# batch sessions + api layer
# --------------------------------------------------------------------------


def test_session_batch_only_dirty_graphs_pay():
    graphs = [erdos_renyi(250 + 17 * i, 5.0, seed=i) for i in range(4)]
    sb = open_session_batch(graphs)
    assert isinstance(sb, SessionBatch) and sb.B == 4
    rng = np.random.default_rng(16)
    rem, add = _churn(graphs[2], 0.05, rng)
    sb.apply_delta(2, remove_edges=rem, add_edges=add)
    assert sb.dirty() == [2]
    before = [s.colors.copy() for s in sb.sessions]
    results = sb.recolor()
    assert len(results) == 4
    for b in (0, 1, 3):
        np.testing.assert_array_equal(results[b].colors, before[b])
        assert results[b].work_items == 0
    assert results[2].work_items > 0
    assert sb.validate()
    # matches a standalone session fed the same delta
    solo = ColoringSession(graphs[2])
    solo.apply_delta(remove_edges=rem, add_edges=add)
    np.testing.assert_array_equal(
        solo.recolor().colors, results[2].colors)


def test_api_registration_and_cold_parity():
    assert "dynamic" in api.algorithms()
    g = erdos_renyi(300, 5.0, seed=17)
    r = api.color(g, algorithm="dynamic")
    ref = api.color(g, algorithm="fused", engine="ragged")
    np.testing.assert_array_equal(r.colors, ref.colors)
    s = api.open_session(g)
    assert s.validate()


def test_recolor_nonconvergence_raises_and_preserves_state():
    g = erdos_renyi(400, 6.0, seed=18)
    s = open_session(g)
    before = s.colors.copy()
    # cripple the incremental engine AFTER the healthy cold start: tail off
    # + 1 super-step means the frontier cannot settle
    s._tail_serial = None
    s._max_iters = 1
    rng = np.random.default_rng(19)
    rem, add = _churn(g, 0.05, rng)
    s.apply_delta(remove_edges=rem, add_edges=add)
    with pytest.raises(RuntimeError, match="before converging"):
        s.recolor()
    np.testing.assert_array_equal(s.colors, before)  # not committed
    assert s.frontier().size > 0  # delta still pending
