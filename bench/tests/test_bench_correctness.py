"""``correct`` on whole runs at a small size on the host: sound runs read
correct, the control and every fault the cells can have read not correct.

Each test drives ``harness.execute``, everything of a run but the look for
a chip, with the program (or a fault planted in it) underneath.
"""
import contextlib
import time

import numpy as np
import pytest

from bench import control, harness

SMALL = {
    "rmat-g": {"n": 2048, "avg_degree": 10.0, "abcd": [0.45, 0.15, 0.15, 0.25]},
}
CELLS = ["rmat-g.oneshot"]
SEED = 2**31 + 11  # past 32 signed bits: run seeds may be that large
SECONDS = 0.3


def small_spec(workload):
    """The cell's configuration and traffic at a small size."""
    config, traffic = workload.split(".")
    spec = {
        "cell": {"name": workload, "config": config, "traffic": traffic,
                 "chips": 1},
        "config": harness.load_json(harness.BENCH / "configs" / f"{config}.json"),
        "traffic": harness.load_json(harness.BENCH / "traffic" / f"{traffic}.json"),
        "end_to_end": [{"name": "coloring_s", "unit": "s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    spec["config"]["graph"] = SMALL[config]
    return spec


def run(workload, spec=None, seed=SEED):
    return harness.execute(workload, seed, SECONDS, False,
                           t_start=time.perf_counter(),
                           spec=spec or small_spec(workload))


def improper(result):
    return result["compared"]["improper_vertices"]["value"]


@pytest.fixture(scope="module")
def repro():
    import repro

    return repro


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"] is True
    assert improper(r) == 0
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"coloring_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = control.execute_control(workload, SEED, SECONDS,
                                spec=small_spec(workload))
    assert r["correct"] is False
    assert improper(r) > 0


@contextlib.contextmanager
def patched(obj, name, wrap):
    real = getattr(obj, name)
    setattr(obj, name, wrap(real))
    try:
        yield
    finally:
        setattr(obj, name, real)


def alter_one_answer(colors, g):
    """A vertex with a neighbor takes that neighbor's color."""
    colors = np.array(colors)
    v = int(np.argmax(np.diff(g.row_offsets) > 0))
    colors[v] = colors[g.col_indices[g.row_offsets[v]]]
    return colors


def oneshot_fault(repro, kind, warmups):
    """A coloring broken after the ``warmups`` warm-up requests."""
    def wrap(real):
        calls = [0]

        def color(g, *a, **kw):
            r = real(g, *a, **kw)
            calls[0] += 1
            if calls[0] <= warmups:
                return r
            if kind == "answer_altered":
                r.colors = alter_one_answer(r.colors, g)
            elif kind == "half_left_out":
                r.colors = np.array(r.colors)
                r.colors[g.n // 2:] = 0
            elif kind == "never_answers":
                raise RuntimeError("planted: the request fails")
            return r
        return color
    return patched(repro, "color", wrap)


@pytest.mark.parametrize("kind", ["answer_altered", "half_left_out",
                                  "never_answers"])
@pytest.mark.parametrize("workload", CELLS)
def test_oneshot_fault_is_not_correct(repro, workload, kind):
    spec = small_spec(workload)
    with oneshot_fault(repro, kind, warmups=spec["config"]["orders"]):
        r = run(workload, spec)
    assert r["correct"] is False
    assert improper(r) > 0
    if kind == "never_answers":
        assert r["failed"] == r["attempted"]


def orders(spec):
    return spec["config"]["orders"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_a_window_holds_whole_cycles_of_the_orders(workload, trace):
    spec = small_spec(workload)
    spec["traffic"]["trace_requests"] = orders(spec) + 1
    r = harness.execute(workload, SEED, SECONDS, trace,
                        t_start=time.perf_counter(), spec=spec)
    assert r["correct"] is True
    assert r["attempted"] >= orders(spec) and r["attempted"] % orders(spec) == 0
    if trace:  # the first whole cycle that reaches trace_requests
        assert r["attempted"] == 2 * orders(spec)


def _graph_key(g):
    return g.row_offsets.tobytes(), g.col_indices.tobytes()


@pytest.mark.parametrize("workload", CELLS)
def test_seeds_color_the_same_orders_in_other_sequences(workload):
    config = small_spec(workload)["config"]
    k = config["orders"]
    seeds = [SEED + i for i in range(k)]
    runs = [[_graph_key(g) for g in harness.generate_graphs(config, s)]
            for s in seeds]
    assert all(len(keys) == k for keys in runs)
    assert len(set(runs[0])) == k  # K distinct orders
    assert all(sorted(keys) == sorted(runs[0]) for keys in runs)
    assert len({tuple(keys) for keys in runs}) == k  # K distinct sequences


@pytest.mark.parametrize("workload", CELLS)
def test_nothing_compiles_in_the_window_after_the_warmup(repro, workload,
                                                         monkeypatch):
    runs, calls = [], []
    real_run, real_color = harness.Run, repro.color

    def keep(*a, **kw):
        runs.append(real_run(*a, **kw))
        return runs[-1]

    def color(g, *a, **kw):
        calls.append((_graph_key(g), time.perf_counter()))
        return real_color(g, *a, **kw)

    monkeypatch.setattr(harness, "Run", keep)
    monkeypatch.setattr(repro, "color", color)
    r = run(workload)
    assert r["correct"] is True
    (window_run,) = runs
    assert len(window_run.graphs) == orders(small_spec(workload))
    # set-up colors each order once, in the run's sequence
    assert [key for key, t in calls if t < window_run.t_open] == \
        [_graph_key(g) for g in window_run.graphs]
    assert window_run.window_compiles["compiles"] == 0
