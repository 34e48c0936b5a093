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


def oneshot_fault(repro, kind):
    """A coloring broken after the warm-up request."""
    def wrap(real):
        calls = [0]

        def color(g, *a, **kw):
            r = real(g, *a, **kw)
            calls[0] += 1
            if calls[0] == 1:
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
    with oneshot_fault(repro, kind):
        r = run(workload)
    assert r["correct"] is False
    assert improper(r) > 0
    if kind == "never_answers":
        assert r["failed"] == r["attempted"]
