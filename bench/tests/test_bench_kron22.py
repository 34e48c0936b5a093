"""The ``kron22`` configuration, the ``oneshot-single`` mix and the readers
``plan_lanes``, ``tail_lanes`` and ``ff_words``: at a small size on the
host, and on synthetic span rings."""
import collections
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import test_bench_correctness as tc
from bench.tests.test_bench_spans import EPOCH, S, _ev
from repro.obs import spans as obs_spans

SMALL_N = 4096  # Graph500 SCALE 12
NEW_READERS = ("plan_lanes", "tail_lanes", "ff_words")


@pytest.fixture
def small_kron22(monkeypatch):
    config = harness.load_json(harness.BENCH / "configs" / "kron22.json")
    monkeypatch.setitem(tc.SMALL, "kron22",
                        dict(config["graph"], n=SMALL_N))
    return tc.small_spec("kron22.oneshot-single")


class _CountingRng:
    """A generator that records the size of every draw."""

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)
        self.draws = []

    def random(self, size):
        self.draws.append(size)
        return self.rng.random(size)


def test_kron22_is_graph500_scale22_drawn_16_edges_a_vertex(small_kron22):
    config = harness.load_json(harness.BENCH / "configs" / "kron22.json")
    assert config["graph"]["n"] == 2**22 == config["published"]["vertices"]
    assert config["graph"]["abcd"] == config["published"]["initiator"]
    assert config["reduced"] == []
    gen = harness.load_module(harness.BENCH / "graphs" / "rmat.py",
                              "bench_graph_rmat")
    rng = _CountingRng(7)
    g = gen.generate(small_kron22["config"]["graph"], rng)
    assert rng.draws == [16 * SMALL_N] * 12   # one draw a level, SCALE 12
    assert g.n == SMALL_N and g.entries > 0
    deg = g.degrees
    assert deg.max() > 20 * deg.mean()         # hubs: a power law


def test_one_order_gives_every_seed_the_same_graph(small_kron22):
    config = small_kron22["config"]
    assert config["orders"] == 1
    first = harness.generate_graphs(config, 1)
    for seed in (2, tc.SEED, 2**31 - 1):
        again = harness.generate_graphs(config, seed)
        assert len(again) == len(first) == 1
        np.testing.assert_array_equal(again[0].row_offsets,
                                      first[0].row_offsets)
        np.testing.assert_array_equal(again[0].col_indices,
                                      first[0].col_indices)


def test_oneshot_single_traces_one_request(small_kron22):
    """A traced run of the cell at a small size: one request, correct,
    and the new readers read the spans of the program it ran."""
    spec = small_kron22
    assert spec["traffic"]["trace_requests"] == 1
    oneshot = harness.load_json(harness.BENCH / "traffic" / "oneshot.json")
    assert {k: v for k, v in spec["traffic"].items()
            if k not in ("about", "trace_requests")} == {
        k: v for k, v in oneshot.items()
        if k not in ("about", "trace_requests")}
    spec["per_layer"] = [m for m in harness.cell_spec("kron22.oneshot")
                         ["per_layer"] if m["name"].split(".")[0]
                         in NEW_READERS]
    assert len(spec["per_layer"]) == len(NEW_READERS)
    r = harness.execute("kron22.oneshot", tc.SEED, 0.3, True,
                        t_start=time.perf_counter(), spec=spec)
    assert r["correct"] and r["attempted"] == 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == {f"{name}.kron22" for name in NEW_READERS}
    assert 0 < m["plan_lanes.kron22"] <= 3
    assert m["tail_lanes.kron22"] > 0
    assert m["ff_words.kron22"] >= 1


def test_kron22_control_is_not_correct(small_kron22):
    from bench import control

    r = control.execute_control("kron22.oneshot", tc.SEED, 0.3,
                                spec=small_kron22)
    assert r["correct"] is False and r["attempted"] >= 1
    assert r["compared"]["improper_vertices"]["value"] > 0


class _Graph:
    entries = 1000


def _ring():
    """Two requests: two super-steps and a tail, then one super-step."""
    return [
        _ev("superstep", 2, 1, 1, 2, 1, cells=3000, ff_words=1),
        _ev("superstep", 3, 1, 2, 3, 1, cells=1000, ff_words=2),
        _ev("serial_tail", 4, 1, 4, 8, 1, cells=9000, ff_words=16,
            rounds=5),
        _ev("color", 1, 0, 0, 10),
        _ev("superstep", 6, 5, 21, 22, 5, cells=2000, ff_words=1),
        _ev("color", 5, 0, 20, 25),
    ]


def _read(name, requests=2):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}").read(
        {"layer": {"requests": requests}, "graph": _Graph()})


def _use_ring(monkeypatch, events):
    monkeypatch.setattr(obs_spans, "_RING",
                        collections.deque(events, maxlen=obs_spans.RING_CAP))


def test_new_readers_read_the_span_meta(monkeypatch):
    _use_ring(monkeypatch, _ring())
    assert _read("plan_lanes") == pytest.approx((3000 + 1000 + 2000) / 3
                                                / 1000)
    assert _read("tail_lanes") == pytest.approx((9000 + 0) / 2 / 1000)
    assert _read("ff_words") == 16
    assert _read("ff_words", requests=1) == 1   # the last request only
    assert _read("tail_lanes", requests=1) == 0


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_without_the_meta(monkeypatch, name):
    """A program whose spans carry no ``cells`` on the tail or no
    ``ff_words`` (the parent's) reads nothing, as does one with no spans."""
    bare = []
    for e in _ring():
        meta = {k: v for k, v in e.meta.items() if k not in (
            "ff_words", "cells" if e.name == "serial_tail" else "")}
        if name == "plan_lanes":
            meta.pop("cells", None)
        bare.append(_ev(e.name, e.span_id, e.parent_id,
                        (e.start_ns - EPOCH) / S, (e.end_ns - EPOCH) / S,
                        e.request_id, **meta))
    _use_ring(monkeypatch, bare)
    assert _read(name) is None
    _use_ring(monkeypatch, [])
    assert _read(name) is None
    assert _read(name, requests=0) is None
