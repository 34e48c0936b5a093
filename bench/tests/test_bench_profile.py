"""The trace reduction and the roofline arithmetic on synthetic traces,
one real trace recorded here, and the run's refusal off the chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import profile_reduce as pr
from bench import roofline
from bench.profile_reduce import DeviceTrace, TraceSummary

ROOT = Path(__file__).resolve().parents[2]

# one device: programs at [10,20] and [15,30] overlap, [50,60] stands alone,
# [95,120] runs past the window [0,100]
PROGRAMS = [(10, 20, "jit_step"), (15, 30, "jit_provider_tail"),
            (50, 60, "jit_provider_tail(3)"), (95, 120, "jit_step")]
ANNOTATIONS = [(0, 100, "bench.window"), (0, 40, "bench.color"),
               (30, 45, "bench.readback"), (45, 100, "bench.color")]


def test_busy_is_the_union_of_program_intervals_inside_the_window():
    assert pr.merge(PROGRAMS) == [(10, 30), (50, 60), (95, 120)]
    assert pr.busy_ns(PROGRAMS, 0, 100) == 20 + 10 + 5


def test_idle_gaps_cover_the_rest_of_the_window():
    gaps = pr.idle_gaps(PROGRAMS, 0, 100)
    assert gaps == [(0, 10), (30, 50), (60, 95)]
    assert sum(e - s for s, e in gaps) + pr.busy_ns(PROGRAMS, 0, 100) == 100


def test_program_time_matches_by_name_and_clips_to_the_window():
    assert pr.program_ns(PROGRAMS, 0, 100, "provider_tail") == 15 + 10
    assert pr.program_ns(PROGRAMS, 0, 100, "step") == 10 + 5


def test_gaps_go_to_the_innermost_annotation_at_their_midpoint():
    assert pr.attribute((30, 50), ANNOTATIONS) == "bench.readback"
    assert pr.attribute((60, 95), ANNOTATIONS) == "bench.color"
    assert pr.attribute((0, 10), ANNOTATIONS) == "bench.color"
    assert pr.attribute((200, 210), ANNOTATIONS) == "host:outside"


def test_breakdown_lists_ops_and_the_longest_gaps():
    dev = DeviceTrace("/device:TPU:0", PROGRAMS, {"fusion.1": 30.0, "while": 70.0})
    out = pr.breakdown(TraceSummary([dev], ANNOTATIONS), 0, 100)
    assert [n for n, _ in out["device_ops"]] == ["while", "fusion.1"]
    assert [s for _, s in out["device_ops"]] == pytest.approx([70e-9, 30e-9])
    assert [n for n, _ in out["idle_gaps"]] == ["bench.color", "bench.readback",
                                                "bench.color"]
    assert [s for _, s in out["idle_gaps"]] == pytest.approx([35e-9, 20e-9, 10e-9])
    # no op line: the programs stand in for the ops
    dev = DeviceTrace("/device:TPU:0", PROGRAMS, {})
    out = pr.breakdown(TraceSummary([dev], ANNOTATIONS), 0, 100)
    assert dict(out["device_ops"]) == pytest.approx(
        {"jit_step": 15e-9, "jit_provider_tail": 15e-9,
         "jit_provider_tail(3)": 10e-9})


def test_window_must_be_one_annotation():
    assert pr.window_of(ANNOTATIONS, "bench.window") == (0, 100)
    with pytest.raises(RuntimeError):
        pr.window_of(ANNOTATIONS, "bench.color")


def test_tail_share_reader():
    from bench import harness

    summary = TraceSummary([DeviceTrace("/device:TPU:0", PROGRAMS, {})],
                           ANNOTATIONS)
    ctx = {"trace": summary, "window_ns": (0, 100), "busy_s": 35e-9,
           "window_s": 100e-9, "layer": {}}
    tail = harness.load_module(harness.BENCH / "metrics" / "tail_share.py", "t_tail")
    assert tail.read(ctx) == pytest.approx(100.0 * 25 / 35)
    assert tail.read({"layer": {}}) is None


def test_roofline_counts_the_least_traffic_of_a_coloring():
    # 4 (n + 1) offsets + 8 per entry + 4 n color writes
    assert roofline.coloring_min_bytes(10, 30) == 44 + 240 + 40
    peak = roofline.peak("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    # bytes that take exactly the busy time at peak read 100%
    assert roofline.roofline_share(819e9 * 0.5, 0.5, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.roofline_share(819e6, 2.0, "TPU v5 lite") == pytest.approx(0.05)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline.roofline_share(1.0, 0.0, "TPU v5 lite")


def test_a_recorded_trace_yields_the_window_and_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.cumsum(x) * 2)
    x = jnp.arange(1024.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.color"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    summary = pr.read_xspace(pr.find_xspace(str(tmp_path)))
    lo, hi = pr.window_of(summary.annotations, "bench.window")
    assert hi > lo
    names = {n for _, _, n in summary.annotations}
    assert names == {"bench.window", "bench.color"}
    # the host backend has no TPU plane
    assert summary.devices == []


def run_bench(cwd, env_extra=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat-g.oneshot",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_a_run_off_the_chip_exits_nonzero_and_prints_no_result():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_a_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        base = m["name"].partition(".")[0]
        assert (ROOT / "bench" / "metrics" / f"{base}.py").is_file()
