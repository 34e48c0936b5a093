"""Telemetry substrate tests (DESIGN.md §16): rings, traces, spans, reports.

Four layers, matching the module layout:

* pure ring/record mechanics (``resolve_trace_cap``, ``HostRing``,
  ``ring_rows``, ``RunTrace`` round-trips) — no jax;
* engine integration: every engine family called with ``trace=True``
  attaches a ``RunTrace`` whose structural invariants hold
  (``retired + conflicts == live`` per row, worklist continuity,
  ``Σ retired == initial worklist``, ``Σ cells == padded_work`` on
  single-graph engines) and whose mode-specific work linkage matches
  ``ColoringResult`` (workefficient: ``Σ live == work_items``; fused:
  ``Σ conflicts == work_items`` — the boot row charges the first
  super-step's incoming worklist);
* conflict counts against hand-built oracles: an edgeless graph retires
  everything in one conflict-free step, a clique's final ``max_color``
  is its order, the serial-tail row drains its worklist with
  ``conflicts == 0``;
* spans: ids and nesting (also on a second thread), the bounded ring,
  ``last_requests``, the realtime clock, compile attribution from JAX's
  monitoring events, the spans of one ``repro.color`` request, and the
  text reports.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro

from repro import api
from repro.core import (
    CSRGraph,
    color_data_driven,
    csr_from_edges,
    is_valid_coloring,
)
from repro.core.batch import color_batch_fused
from repro.d2 import color_distance2
from repro.graphs import build_graph
from repro.obs import (
    NF,
    HostRing,
    RunTrace,
    empty_trace,
    last_requests,
    recorder,
    resolve_trace_cap,
    ring_rows,
    span,
)
from repro.obs import report as obs_report
from repro.obs import spans as obs_spans


def _suite_graph():
    return build_graph("rmat-g", 0.01)


# ---------------------------------------------------------------- mechanics


def test_resolve_trace_cap():
    assert resolve_trace_cap(False) == 0
    assert resolve_trace_cap(None) == 0
    assert resolve_trace_cap(0) == 0
    assert resolve_trace_cap(-3) == 0
    assert resolve_trace_cap(True) == 512
    assert resolve_trace_cap(7) == 7
    # max_iters bounds the ring (+2 for the host's boot/tail rows)
    assert resolve_trace_cap(True, max_iters=10) == 12
    assert resolve_trace_cap(4, max_iters=100) == 4


def test_host_ring_drop_oldest():
    ring = HostRing(3)
    for i in range(5):
        ring.append(live=10 - i, retired=1, conflicts=9 - i, max_color=i,
                    cells=i)
    rows = ring.rows()
    assert ring.recorded == 5
    assert rows.shape == (3, NF)
    # kept window is the most recent 3 rows, in order
    np.testing.assert_array_equal(rows[:, 0], [8, 7, 6])


def test_device_ring_rows_wrap():
    cap = 4
    buf = np.zeros((cap, NF), np.int32)
    for s in range(6):                       # writes at s % cap
        buf[s % cap, 0] = 100 + s
    rows = ring_rows(buf, 6)
    np.testing.assert_array_equal(rows[:, 0], [102, 103, 104, 105])
    assert ring_rows(buf, 0).shape == (0, NF)
    assert ring_rows(buf, 2).shape == (2, NF)


def test_runtrace_roundtrip_and_summary():
    steps = np.array([[8, 0, 8, 1, 0, 0, 0, 0],
                      [8, 5, 3, 2, 64, 0, 0, 0],
                      [3, 3, 0, 3, 24, 1, 0, 0]], np.int64)
    t = RunTrace(steps=steps, iterations=3, engine="unit")
    assert t.check() == []
    assert t.tail_step == 2
    t2 = RunTrace.from_dict(t.to_dict())
    np.testing.assert_array_equal(t2.steps, t.steps)
    s = t.summary(max_points=2)
    assert s["supersteps"] == 3 and s["series_from"] == 1
    assert s["live"] == [8, 3] and s["conflicts"] == [3, 0]
    assert "halo_bytes" not in s          # all-zero series is omitted


def test_runtrace_check_catches_broken_rows():
    steps = np.array([[8, 0, 8, 1, 0, 0, 0, 0],
                      [8, 4, 3, 2, 64, 0, 0, 0]], np.int64)  # 4 + 3 != 8
    bad = RunTrace(steps=steps, iterations=2).check()
    assert any("retired + conflicts" in b for b in bad)
    steps2 = np.array([[8, 0, 8, 1, 0, 0, 0, 0],
                       [5, 5, 0, 2, 64, 0, 0, 0]], np.int64)  # 8 != live 5
    bad2 = RunTrace(steps=steps2, iterations=2).check()
    assert any("continuity" in b for b in bad2)


# ---------------------------------------------------- engine integration


def _assert_coherent(result, *, batch=False):
    t = result.trace
    assert isinstance(t, RunTrace)
    assert t.check(result) == [], t.check(result)
    s = t.steps
    if s.shape[0] == 0:
        return t
    if t.dropped == 0:
        assert int(t.series("retired").sum()) == int(s[0, 0])
        cells = int(t.series("cells").sum())
        if batch:
            assert cells <= result.padded_work
        else:
            assert cells == result.padded_work
    assert int(s[-1, 3]) == result.num_colors
    return t


def test_trace_off_attaches_nothing():
    g = _suite_graph()
    assert color_data_driven(g).trace is None
    assert color_data_driven(g, mode="fused").trace is None


@pytest.mark.parametrize("mode", ["workefficient", "fused"])
def test_single_graph_trace_invariants_and_work_linkage(mode):
    g = _suite_graph()
    off = color_data_driven(g, mode=mode, tail_serial=False)
    on = color_data_driven(g, mode=mode, tail_serial=False, trace=True)
    np.testing.assert_array_equal(off.colors, on.colors)
    assert off.iterations == on.iterations
    t = _assert_coherent(on)
    assert t.iterations == on.iterations
    assert t.tail_step == -1
    # mode-specific work linkage (no tail, no ring drop)
    if mode == "workefficient":
        assert int(t.series("live").sum()) == on.work_items
    else:
        assert int(t.series("conflicts").sum()) == on.work_items


@pytest.mark.parametrize("engine", ["classic", "ragged", "padded"])
def test_engine_matrix_traces(engine):
    g = _suite_graph()
    opts = {"engine": engine, "trace": True}
    if engine == "ragged":
        opts["mode"] = "fused"
    r = color_data_driven(g, **opts)
    _assert_coherent(r)
    assert is_valid_coloring(g, r.colors)


def test_distance2_trace():
    g = _suite_graph()
    r = color_distance2(g, trace=True)
    t = _assert_coherent(r)
    assert "superstep_loop" in {e.name for e in t.spans}


def test_batch_traces_per_graph():
    graphs = [build_graph("rmat-g", 0.01), build_graph("G3_circuit", 0.01)]
    plain = color_batch_fused(graphs)
    traced = color_batch_fused(graphs, trace=True)
    for off, on in zip(plain, traced):
        np.testing.assert_array_equal(off.colors, on.colors)
        assert off.iterations == on.iterations
        _assert_coherent(on, batch=True)
        assert on.trace.spans, "batch results must share the recorded spans"


def test_ring_wraparound_keeps_coherent_window():
    g = _suite_graph()
    full = color_data_driven(g, mode="fused", tail_serial=False, trace=True)
    tiny = color_data_driven(g, mode="fused", tail_serial=False, trace=2)
    t = tiny.trace
    assert t.iterations == full.trace.iterations
    assert t.dropped == t.iterations - 2 > 0
    assert t.check() == [], t.check()        # kept window stays contiguous
    np.testing.assert_array_equal(t.steps, full.trace.steps[-2:])


# -------------------------------------------------------- hand-built oracles


def test_edgeless_graph_oracle():
    """No edges: everything retires in one conflict-free super-step."""
    n = 17
    g = CSRGraph(np.zeros(n + 1, np.int64), np.zeros(0, np.int32))
    r = color_data_driven(g, mode="fused", tail_serial=False, trace=True)
    t = r.trace
    np.testing.assert_array_equal(t.series("live"), [n, n])
    np.testing.assert_array_equal(t.series("conflicts"), [n, 0])
    np.testing.assert_array_equal(t.series("retired"), [0, n])
    assert int(t.steps[-1, 3]) == 1          # one color suffices


def test_clique_oracle():
    """K6 needs exactly 6 colors; the trace's final max_color agrees with
    both the result and the validator's view of the colors array."""
    k = 6
    src, dst = np.triu_indices(k, 1)
    g = csr_from_edges(k, src.astype(np.int64), dst.astype(np.int64))
    r = color_data_driven(g, mode="fused", tail_serial=False, trace=True)
    assert is_valid_coloring(g, r.colors)
    assert r.num_colors == k
    t = r.trace
    assert int(t.steps[-1, 3]) == k == int(np.max(r.colors))
    # conflicts strictly shrink: a clique retires >= 1 vertex per step
    conf = t.series("conflicts")
    assert all(conf[i] > conf[i + 1] for i in range(len(conf) - 1))


def test_serial_tail_row_semantics():
    """Force the tail: its row is last, drains the surviving worklist
    (conflicts == 0), and tail_step points at it."""
    g = _suite_graph()
    r = color_data_driven(g, mode="fused", tail_serial=g.n, trace=True)
    t = r.trace
    assert t.tail_step >= 0
    last = t.steps[-1]
    assert int(last[5]) == 1 and int(last[2]) == 0
    assert t.tail_step == t.dropped + t.steps.shape[0] - 1
    assert t.check(r) == [], t.check(r)


def test_empty_graph_trace():
    g = CSRGraph(np.zeros(1, np.int64), np.zeros(0, np.int32))
    r = color_data_driven(g, trace=True)
    assert r.trace is not None
    assert r.trace.iterations == 0 and r.trace.check(r) == []
    assert empty_trace("x").tail_step == -1


# ------------------------------------------------------------------- spans


def test_span_noop_without_recorder():
    """A recorder sees only what closes inside it; the ring keeps all."""
    with span("never_kept"):
        pass
    with recorder() as rec:
        with span("kept", answer=42):
            pass
    assert [e.name for e in rec.events] == ["kept"]
    assert rec.events[0].meta == {"answer": 42}
    assert [r.name for r, _ in last_requests(1, root="never_kept")] \
        == ["never_kept"]


def test_span_ids_nest_across_threads():
    seen = {}

    def worker():
        with span("t_root") as root:
            with span("t_child") as child:
                pass
        seen.update(root=root, child=child)

    with span("m_root") as m_root:
        with span("m_child") as m_child:
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    assert m_root.parent_id == 0 and m_root.request_id == m_root.span_id
    assert m_child.parent_id == m_root.span_id
    assert m_child.request_id == m_root.span_id
    # the worker thread starts with no open span: its spans form their own
    # request, whatever the main thread has open
    root, child = seen["root"], seen["child"]
    assert root.parent_id == 0 and root.request_id == root.span_id
    assert child.parent_id == root.span_id
    assert child.request_id == root.span_id
    ids = {m_root.span_id, m_child.span_id, root.span_id, child.span_id}
    assert len(ids) == 4


def test_spans_from_many_threads_keep_their_own_nesting():
    """Threads open spans at once while another reads the ring: ids stay
    unique and every child stays under its own thread's root."""
    import sys

    roots: dict = {}
    kids: dict = {}
    stop = threading.Event()

    def worker(w):
        for _ in range(100):
            with span("stress_root", w=w) as root:
                with span("stress_kid", w=w) as kid:
                    pass
            roots.setdefault(w, []).append(root)
            kids.setdefault(w, []).append(kid)

    def reader():
        while not stop.is_set():
            last_requests(5, root="stress_root")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(8)]
        r = threading.Thread(target=reader)
        r.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        r.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not r.is_alive() and not any(t.is_alive() for t in threads)
    ids = [e.span_id for w in roots for e in roots[w] + kids[w]]
    assert len(ids) == len(set(ids)) == 8 * 100 * 2
    for w in range(8):
        for root, kid in zip(roots[w], kids[w]):
            assert root.parent_id == 0 and root.meta["w"] == w
            assert kid.parent_id == root.span_id == kid.request_id


def test_span_ring_is_bounded_and_drops_oldest():
    cap = obs_spans.RING_CAP
    with span("ring_first"):
        pass
    for i in range(cap + 5):
        with span("ring_fill", i=i):
            pass
    kept = list(obs_spans._RING)
    assert len(kept) == cap
    assert all(e.name == "ring_fill" for e in kept)
    assert [e.meta["i"] for e in kept[:2]] == [5, 6]
    assert kept[-1].meta["i"] == cap + 4
    assert last_requests(1, root="ring_first") == []


def test_last_requests_returns_the_last_roots_with_descendants():
    made = []
    for i in range(3):
        with span("lr_root", i=i) as root:
            with span("lr_mid"):
                with span("lr_leaf", i=i):
                    pass
            made.append(root)
        with span("lr_other"):
            pass
    out = last_requests(2, root="lr_root")
    assert [r for r, _ in out] == made[1:]
    for (root, desc), i in zip(out, (1, 2)):
        assert [e.name for e in desc] == ["lr_leaf", "lr_mid"]
        assert desc[0].meta == {"i": i}
        assert all(e.request_id == root.span_id for e in desc)
    assert len(last_requests(10, root="lr_root")) == 3
    assert last_requests(0, root="lr_root") == []
    assert last_requests(3, root="no_such_root") == []


def test_span_stamps_are_on_the_realtime_clock():
    before = time.time_ns()
    with span("clock") as ev:
        time.sleep(0.002)
    after = time.time_ns()
    assert before <= ev.start_ns < ev.end_ns <= after
    assert ev.duration >= 0.002


def _fresh_jitted():
    def attributed(x):
        return x * 3 + 2
    return jax.jit(attributed)


def test_compiles_are_counted_on_the_innermost_span():
    x = jnp.ones(7, jnp.float32)
    f = _fresh_jitted()
    with span("outer") as outer:
        with span("first") as first:
            f(x).block_until_ready()
        with span("second") as second:
            f(x).block_until_ready()
    assert first.meta.get("compiles", 0) >= 1
    assert "compiles" not in second.meta
    assert "compiles" not in outer.meta
    with recorder() as rec:
        with span("again"):
            f(x).block_until_ready()
    assert rec.by_name()["again"]["compiles"] == 0


def test_cache_loads_are_not_counted_as_compiles(tmp_path):
    """Under a warm persistent cache a new jit of the same program is a
    cache load: counted as ``cache_loads``, not as a compile."""
    from jax._src import compilation_cache

    x = jnp.ones(11, jnp.float32)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        with span("cold") as cold:
            _fresh_jitted()(x).block_until_ready()
        with span("warm") as warm:
            _fresh_jitted()(x).block_until_ready()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert cold.meta.get("compiles", 0) >= 1
    assert cold.meta.get("cache_loads", 0) == 0
    assert warm.meta.get("cache_loads", 0) >= 1
    assert warm.meta.get("compiles", 0) == 0


def test_color_request_spans():
    """``repro.color`` opens a ``color`` root; the engine's super-steps and
    serial tail nest under it with what the host knew of them."""
    g = _suite_graph()
    r = repro.color(g, "data_driven")
    (root, desc), = last_requests(1)
    assert root.name == "color" and root.parent_id == 0
    assert root.meta["algorithm"] == "data_driven" and root.meta["n"] == g.n
    assert all(e.request_id == root.span_id for e in desc)
    names = [e.name for e in desc]
    assert {"partition_plan", "csr_build", "superstep_loop"} <= set(names)
    steps = [e for e in desc if e.name == "superstep"]
    assert len(steps) == r.iterations - 2    # bootstrap and tail: no step
    assert steps[0].meta["live"] == g.n and steps[0].meta["cells"] > 0
    assert [e.meta["step"] for e in steps] == list(range(1, len(steps) + 1))
    tail, = [e for e in desc if e.name == "serial_tail"]
    assert tail.meta["vertices"] == g.n and tail.meta["stalled"]
    assert 0 < tail.meta["live"] <= g.n
    inner = sum(e.duration for e in desc if e.name in ("superstep",
                                                       "serial_tail"))
    assert inner <= root.duration
    assert root.start_ns <= min(e.start_ns for e in desc)
    assert root.end_ns >= max(e.end_ns for e in desc)


def test_engine_spans_visible_to_outer_recorder():
    g = _suite_graph()
    with recorder() as rec:
        r = color_data_driven(g, mode="fused", trace=True)
    names = {e.name for e in rec.events}
    assert {"csr_build", "superstep_loop"} <= names
    # the engine's internal recorder captured the same phases on the trace
    assert {"csr_build", "superstep_loop"} <= {e.name for e in r.trace.spans}


def test_session_metrics_and_jit_cache_accounting():
    g = _suite_graph()
    session = api.open_session(g, trace=True)
    rng = np.random.default_rng(5)
    from repro.dynamic import churn_delta

    for _ in range(2):
        rem, add = churn_delta(session.graph, 0.02, rng)
        session.apply_delta(remove_edges=rem, add_edges=add)
        inc = session.recolor()
    assert inc.trace is not None and inc.trace.check(inc) == []
    assert inc.trace.spans, "session recolor must attach spans"
    m = session.metrics()
    assert m["deltas"] == 2 and m["recolors"] == 2
    assert m["engine_cache_hits"] + m["engine_cache_misses"] == 2
    assert m["engine_cache_misses"] >= 1     # first round always compiles
    assert m["supersteps_total"] > 0 and m["work_total"] > 0
    assert m["pending_frontier"] == 0
    assert session.validate()


# ------------------------------------------------------------------ report


def test_report_formats():
    g = _suite_graph()
    r = color_data_driven(g, mode="fused", trace=True)
    line = obs_report.format_result("fused", r)
    assert "colors=" in line and "work=" in line
    table = obs_report.format_trace(r.trace, last=3)
    assert "live" in table and str(r.trace.iterations) in table
    spans_table = obs_report.format_spans(r.trace.spans)
    assert spans_table.count("\n") >= 1
    assert spans_table.splitlines()[0].split()[-2:] == ["compiles", "loads"]
    block = obs_report.format_metrics({"a": 1, "bb": 2.5}, "t:")
    assert block.splitlines()[0] == "t:" and " a " in block


def test_report_bench_document(tmp_path):
    doc = {
        "schema": 6, "backend": "jax", "engine": "ragged",
        "algorithms": {"fused": {"g": {
            "colors": 3, "valid": True,
            "trace": {"supersteps": 2, "tail_step": -1, "series_from": 0,
                      "live": [4, 4], "retired": [0, 4],
                      "conflicts": [4, 0], "max_color": [1, 3],
                      "cells": [0, 32]},
        }}},
        "dynamic": {"g": {
            "rounds_detail": [{"round": 0, "frontier": 9, "work": 40,
                               "supersteps": 3, "tail_step": 2,
                               "cache_hit": False}],
            "jit": {"hits": 0, "misses": 1},
        }},
    }
    p = tmp_path / "BENCH_x.json"
    p.write_text(json.dumps(doc))
    assert obs_report.main([str(p)]) == 0
    assert obs_report.main([str(tmp_path / "missing--"), "x"]) == 2
