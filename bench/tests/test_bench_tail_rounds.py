"""The ``tail_rounds`` reader, on the synthetic span trees of
``test_bench_spans`` with the tail's ``rounds`` added, and on a traced run
of the cell at a small size."""
import collections
import dataclasses
import time

import pytest

import repro.obs
from bench import harness
from bench import program_spans as ps
from bench.tests.test_bench_spans import _ctx, _read, _requests_ring
from repro.obs import spans as obs_spans

ROUNDS = 7


def _with_rounds(events):
    return [dataclasses.replace(e, meta={**e.meta, "rounds": ROUNDS})
            if e.name == "serial_tail" else e for e in events]


@pytest.fixture
def ring(monkeypatch):
    ring = collections.deque(_with_rounds(_requests_ring()),
                             maxlen=obs_spans.RING_CAP)
    monkeypatch.setattr(obs_spans, "_RING", ring)
    return ring


def test_tail_rounds_reads_the_mean_over_the_window_requests(ring):
    # the first request's tail took ROUNDS rounds, the second had no tail
    assert _read("tail_rounds", _ctx(2)) == pytest.approx((ROUNDS + 0) / 2)
    assert _read("tail_rounds", _ctx(1)) == 0


def test_tail_rounds_reads_nothing_from_a_tail_without_rounds(monkeypatch):
    # a program whose tail colors one vertex at a time reports no rounds:
    # the metric is left out, not read as 0
    monkeypatch.setattr(obs_spans, "_RING", collections.deque(_requests_ring()))
    assert _read("tail_rounds", _ctx(2)) is None
    assert _read("tail_vertices", _ctx(2)) == pytest.approx(50)
    # a window without a tail reads 0 on that program too
    assert _read("tail_rounds", _ctx(1)) == 0


@pytest.mark.parametrize("drop", ["color_root", "last_requests", "requests"])
def test_tail_rounds_reads_none_without_requests(monkeypatch, ring, drop):
    ctx = _ctx(2)
    if drop == "color_root":
        monkeypatch.setattr(obs_spans, "_RING", collections.deque(
            e for e in _with_rounds(_requests_ring()) if e.name != "color"))
    elif drop == "last_requests":
        # a program whose repro.obs has no last_requests (before the ring)
        monkeypatch.delattr(repro.obs, "last_requests")
    else:
        ctx = _ctx(0)  # a window that holds no request
    assert _read("tail_rounds", ctx) is None


def test_a_traced_run_reports_tail_rounds():
    """A traced run of the cell at a small size on the host: the harness
    finds the reader by name, and it reads the rounds of the window's
    requests' tails, one whole cycle of the orders."""
    from bench.tests.test_bench_correctness import SEED, small_spec

    spec = small_spec("rmat-g.oneshot")
    spec["per_layer"] = [m for m in harness.cell_spec("rmat-g.oneshot")
                         ["per_layer"] if m["name"] == "tail_rounds.oneshot"]
    assert len(spec["per_layer"]) == 1
    r = harness.execute("rmat-g.oneshot", SEED, 0.3, True,
                        t_start=time.perf_counter(), spec=spec)
    k = spec["traffic"]["trace_requests"]
    assert r["correct"] and r["attempted"] == k == spec["config"]["orders"]
    requests = ps.window_requests({"layer": {"requests": k}})
    tails = [e for _, desc in requests for e in desc
             if e.name == "serial_tail"]
    assert len(requests) == k
    assert tails and all(e.meta["rounds"] >= 1 for e in tails)
    assert r["metrics"]["tail_rounds.oneshot"]["value"] == pytest.approx(
        sum(e.meta["rounds"] for e in tails) / k)
