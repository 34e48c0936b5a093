"""The readers of the program's phase spans, on synthetic span trees and
synthetic device intervals: ``tail_s``, ``superstep_s``, ``host_s``,
``host_idle_s`` and ``tail_vertices``."""
import collections

import pytest

import repro.obs
from bench import harness
from bench import program_spans as ps
from bench.profile_reduce import DeviceTrace, TraceSummary
from repro.obs import spans as obs_spans
from repro.obs.spans import SpanEvent

S = 10**9
EPOCH = 1_700_000_000 * S  # where the synthetic spans sit on the epoch clock
LAG = 2_000  # ns by which the bench.color annotation outlives the root span
READERS = ("tail_s", "superstep_s", "host_s", "host_idle_s", "tail_vertices")


def _ev(name, span_id, parent_id, start_s, end_s, root_id=None, **meta):
    return SpanEvent(name, EPOCH + int(start_s * S), EPOCH + int(end_s * S),
                     span_id, parent_id, root_id or span_id, meta)


def _requests_ring():
    """Two requests, in closing order: the first with two super-steps and a
    serial tail, the second with one super-step and no tail."""
    return [
        _ev("partition_plan", 2, 1, 0.1, 0.2, 1),
        _ev("superstep", 4, 3, 1, 2, 1, step=1, live=100, cells=900),
        _ev("superstep", 5, 3, 2, 3, 1, step=2, live=90, cells=900),
        _ev("superstep_loop", 3, 1, 1, 3, 1),
        _ev("serial_tail", 6, 1, 4, 8, 1, vertices=100, live=80,
            stalled=True),
        _ev("color", 1, 0, 0, 10, algorithm="data_driven", n=100),
        _ev("unrelated", 7, 0, 11, 12),
        _ev("superstep", 9, 8, 21, 22, 8, step=1, live=100, cells=900),
        _ev("superstep_loop", 8, 10, 21, 22, 10),
        _ev("color", 10, 0, 20, 25, algorithm="data_driven", n=100),
    ]


def _trace():
    """The device on the trace's clock (seconds from the trace's start,
    which here is the spans' epoch): the tail's program covers only half
    a second of its four."""
    programs = [(1.2 * S, 1.9 * S, "jit_provider_tiled_superstep"),
                (2.1 * S, 2.9 * S, "jit_provider_tiled_superstep"),
                (4.5 * S, 5.0 * S, "jit_provider_tail"),
                (21.1 * S, 21.9 * S, "jit_provider_tiled_superstep")]
    annotations = [(-1 * S, 30 * S, "bench.window"),
                   (-0.5 * S, 10 * S + LAG, "bench.color"),
                   (10 * S + LAG, 10.5 * S, "bench.readback"),
                   (19.5 * S, 25 * S + LAG, "bench.color")]
    return TraceSummary([DeviceTrace("/device:TPU:0", programs, {})],
                        annotations)


def _ctx(requests):
    return {"layer": {"requests": requests}, "trace": _trace(),
            "window_ns": (-1 * S, 30 * S)}


def _read(name, ctx):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}").read(ctx)


@pytest.fixture
def ring(monkeypatch):
    ring = collections.deque(_requests_ring(), maxlen=obs_spans.RING_CAP)
    monkeypatch.setattr(obs_spans, "_RING", ring)
    return ring


def test_each_reader_reads_the_mean_over_the_window_requests(ring):
    ctx = _ctx(2)
    assert _read("tail_s", ctx) == pytest.approx((4 + 0) / 2)
    assert _read("superstep_s", ctx) == pytest.approx((2 + 1) / 2)
    assert _read("tail_vertices", ctx) == pytest.approx((100 + 0) / 2)


def test_host_s_is_the_root_less_its_device_waits(ring):
    # request 1: 10 s less 2 s of super-steps and a 4 s tail; request 2:
    # 5 s less one 1 s super-step.  partition_plan is host work: kept
    assert _read("host_s", _ctx(2)) == pytest.approx(((10 - 6) + (5 - 1)) / 2)
    for root, desc in ps.window_requests(_ctx(2)):
        total = (ps.seconds(desc, "serial_tail") + ps.seconds(desc, "superstep")
                 + ps.host_self_s(root, desc))
        assert total == pytest.approx(root.duration)


def test_host_idle_leaves_out_idle_under_device_waits(ring):
    # idle in the window [-1, 30] s: 31 s less 2.8 s of programs; of it,
    # 0.3 + 0.2 s lie under the first request's super-steps, 3.5 s under
    # its tail and 0.2 s under the second request's super-step
    expected = (31 - 2.8 - (0.3 + 0.2 + 3.5 + 0.2)) / 2
    assert _read("host_idle_s", _ctx(2)) == pytest.approx(expected, rel=1e-6)
    trace = _trace()
    idle = sum(e - s for s, e in ps.pr.idle_gaps(trace.devices[0].programs,
                                                 -1 * S, 30 * S))
    assert idle * 1e-9 / 2 > expected


def test_spans_are_put_on_the_trace_clock_by_the_request_annotations(ring):
    requests = ps.window_requests(_ctx(2))
    trace = _trace()
    assert ps.clock_offset_ns(requests, trace.annotations, -S, 30 * S) \
        == EPOCH - LAG
    # no annotation around the requests: no offset, no reading
    assert ps.clock_offset_ns(requests, [], -S, 30 * S) is None
    ctx = _ctx(2)
    ctx["trace"] = TraceSummary(trace.devices, trace.annotations[:1])
    assert _read("host_idle_s", ctx) is None


def test_a_window_without_a_tail_reads_zero(ring):
    ctx = _ctx(1)  # the last request only: one super-step, no tail
    assert _read("tail_s", ctx) == 0
    assert _read("tail_vertices", ctx) == 0
    assert _read("superstep_s", ctx) == pytest.approx(1.0)
    assert _read("host_s", ctx) == pytest.approx(4.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_a_color_root(monkeypatch, name):
    ring = collections.deque([e for e in _requests_ring()
                              if e.name != "color"])
    monkeypatch.setattr(obs_spans, "_RING", ring)
    assert _read(name, _ctx(2)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_on_a_program_without_spans(monkeypatch, ring,
                                                        name):
    # a program whose repro.obs has no last_requests (before the span ring)
    monkeypatch.delattr(repro.obs, "last_requests")
    assert _read(name, _ctx(2)) is None
    # and a window that holds no request
    monkeypatch.undo()
    assert _read(name, _ctx(0)) is None


def test_a_traced_run_reports_the_span_metrics():
    """A traced run of the cell at a small size on the host: the harness
    finds the readers by name and each reads the mean over the window's
    requests, one whole cycle of the orders.  The host backend has no
    device plane, so ``host_idle_s`` reads nothing."""
    import time

    from bench.tests.test_bench_correctness import SEED, small_spec

    spec = small_spec("rmat-g.oneshot")
    spec["per_layer"] = [m for m in harness.cell_spec("rmat-g.oneshot")
                         ["per_layer"] if m["name"].split(".")[0] in READERS]
    assert len(spec["per_layer"]) == len(READERS)
    r = harness.execute("rmat-g.oneshot", SEED, 0.3, True,
                        t_start=time.perf_counter(), spec=spec)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    k = spec["traffic"]["trace_requests"]
    assert k == spec["config"]["orders"]
    assert r["correct"] and r["attempted"] == k
    assert "host_idle_s.oneshot" not in m
    requests = ps.window_requests({"layer": {"requests": k}})
    assert len(requests) == k
    assert m["tail_vertices.oneshot"] == pytest.approx(sum(
        e.meta["vertices"] for _, desc in requests for e in desc
        if e.name == "serial_tail") / k)
    assert m["tail_s.oneshot"] + m["superstep_s.oneshot"] \
        + m["host_s.oneshot"] == pytest.approx(
            sum(root.duration for root, _ in requests) / k)
    assert m["superstep_s.oneshot"] > 0 and m["host_s.oneshot"] > 0
