"""Bring-up check: drive the coloring system's main path once on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

One chip, in order, each phase through a public entry point:

* ``oneshot rmat-g``: ``repro.color(g, "data_driven")`` on the rmat-g
  stand-in at the paper's Table-1 size (n = 1,048,576);
* ``kernel rmat-g``: the same call with ``tail_serial=None``, under
  ``backend="jax"`` and ``backend="pallas"``; with the serial tail off the
  super-steps choose every color, and the two must agree bit for bit.
  Every super-step kernel the engine traces must be compiled, not
  interpreted, and lower to ``tpu_custom_call``;
* ``oneshot G3_circuit``: the tail-heavy circuit grid at its published size
  (n = 1,602,048), which runs the serial tail on the device;
* ``session rmat-g``: ``repro.open_session``, one delta that removes about
  1% of the edges and inserts as many, ``recolor()`` and ``validate()``;
* ``service``: a ``ColoringService`` answers one-shot requests on graphs of
  10^4-10^5 vertices, then one session open, delta and recolor.

Four chips: ``repro.color(g, "data_driven", engine="sharded")`` over all
four devices on rmat-g, compared bit for bit with the one-device ``ragged``
run in the same process: once with the default options, and once with the
serial tail off for the first ``SHARDED_SPEC_STEPS`` super-steps, whose
colors come from the shards and their halo exchange alone.

Every finished coloring is checked for validity, convergence and an empty
degradation ledger; ``ensure_valid`` stays off, so a failed device run
cannot be repaired by the host oracle unseen.  Each phase prints n, entries,
colors, the serial greedy oracle's colors, supersteps and a wall time that
is cold (it includes compilation) and is not a speed measurement.

The suite graphs are fixed by their recipes; ``--seed`` drives the churn
deltas and the service's request graphs.  The script needs a TPU: it exits
non-zero before coloring anything when JAX finds none.  The last line of
its output is the JSON result.
"""
import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# super-steps (bootstrap included) of the four-chip tail-off comparison: on
# rmat-g the worklist shrinks slowly for about the first eight steps, then
# retires fast.  Each sharded step at a new pow2 halo width is one more cold
# compile, and the fast half brings a new width almost every step.
SHARDED_SPEC_STEPS = 10


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def tpu_devices(chips: int):
    """The TPU devices, or a SmokeFailure naming what JAX found instead."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no usable backend: {e}") from None
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's default device is {devs[0].platform!r}")
    check(len(devs) == chips,
          f"--chips {chips} needs {chips} TPU devices, JAX has {len(devs)}")
    return devs


def check_coloring(g, result, label: str) -> None:
    from repro.core import is_valid_coloring

    check(result.converged, f"{label}: did not converge")
    check(not result.degradations,
          f"{label}: degraded: {list(result.degradations)}")
    check(is_valid_coloring(g, result.colors), f"{label}: invalid coloring")


def report(label: str, g, colors: int, oracle: int, supersteps, wall: float,
           extra: str = "") -> None:
    print(f"[{label}] n={g.n} entries={g.m} colors={colors} "
          f"oracle_colors={oracle} supersteps={supersteps} "
          f"wall_s={wall:.3f} (cold, includes compile){extra}", flush=True)


def oracle_colors(g) -> int:
    from repro.core.serial import greedy_serial

    return int(greedy_serial(g).max(initial=0))


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_oneshot(name: str, g, **opts):
    import repro

    r, wall = timed(lambda: repro.color(g, "data_driven", **opts))
    label = f"oneshot {name}" + (f" {opts}" if opts else "")
    check_coloring(g, r, label)
    report(label, g, r.num_colors, oracle_colors(g), r.iterations, wall)
    return r


@contextlib.contextmanager
def superstep_kernel_calls():
    """Record each super-step ``pallas_call`` the engine traces inside the
    block: its static parameters ``(w, W, block_n, heuristic, interpret)``
    and the shapes of its operands."""
    import jax

    from repro.kernels.superstep import ops

    build = ops.superstep_pallas_call
    calls = []

    def recording(*params):
        call = build(*params)

        def traced(*args):
            calls.append((params, tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                                        for a in args)))
            return call(*args)

        return traced

    ops.superstep_pallas_call = recording
    try:
        yield calls
    finally:
        ops.superstep_pallas_call = build


def phase_kernel(name: str, g) -> None:
    """``backend="pallas"`` against ``"jax"`` with the serial tail off, so
    the super-steps, and with them the kernel, choose every color."""
    import jax

    from repro.kernels.superstep.kernel import superstep_pallas_call

    ref = phase_oneshot(name, g, tail_serial=None)
    with superstep_kernel_calls() as calls:
        r = phase_oneshot(name, g, backend="pallas", tail_serial=None)
    label = f"kernel {name}"
    check(np.array_equal(r.colors, ref.colors),
          f"{label}: backend='pallas' colors differ from backend='jax'")
    check(calls, f"{label}: the engine traced no super-step kernel")
    check(all(not params[-1] for params, _ in calls),
          f"{label}: a super-step kernel ran in interpret mode")
    for params, args in set(calls):
        hlo = jax.jit(superstep_pallas_call(*params)).lower(*args).as_text()
        check("tpu_custom_call" in hlo,
              f"{label}: no tpu_custom_call in the kernel call {params}")
    print(f"[{label}] tail_serial=None: colors bit-identical to "
          f"backend='jax' over {r.iterations} super-steps; {len(calls)} "
          f"kernel calls traced ({len(set(calls))} distinct), none "
          "interpreted, each lowers to tpu_custom_call", flush=True)


def phase_session(name: str, g, seed: int) -> None:
    import repro
    from repro.dynamic import churn_delta

    s, wall_open = timed(lambda: repro.open_session(g))
    rem, add = churn_delta(g, 0.01, np.random.default_rng(seed))
    t0 = time.perf_counter()
    dirty = s.apply_delta(remove_edges=rem, add_edges=add)
    r = s.recolor()
    wall = time.perf_counter() - t0
    label = f"session {name}"
    check(r.converged, f"{label}: recolor did not converge")
    check(not r.degradations, f"{label}: degraded: {list(r.degradations)}")
    check(s.validate(), f"{label}: repaired coloring is invalid")
    g2 = s.delta.graph()
    report(label, g2, int(s.colors.max(initial=0)), oracle_colors(g2),
           r.iterations, wall,
           f" removed={rem[0].size} inserted={add[0].size} "
           f"frontier={dirty.size} open_wall_s={wall_open:.3f}")


def service_graphs(seed: int):
    from repro.graphs import generators as G

    return [
        G.erdos_renyi(20000, 6.0, seed=seed),
        G.power_law(25000, 7.0, seed=seed + 1),
        G.grid2d(120, 100),
        G.small_world(40000, 6, seed=seed + 2),
        G.road(60000, seed=seed + 3),
    ]


def phase_service(session_graph, seed: int) -> None:
    import repro
    from repro.dynamic import churn_delta

    graphs = service_graphs(seed)
    with repro.ColoringService(pool_size=2, queue_limit=64) as svc:
        t0 = time.perf_counter()
        tickets = [svc.color(g, wait=False) for g in graphs]
        results = [t.wait() for t in tickets]
        wall = time.perf_counter() - t0
        for i, (g, r) in enumerate(zip(graphs, results)):
            label = f"service color #{i}"
            check_coloring(g, r, label)
            report(label, g, r.num_colors, oracle_colors(g), r.iterations,
                   wall, " (wall for the whole batch of requests)")

        sid = "smoke-0"
        rem, add = churn_delta(session_graph, 0.01,
                               np.random.default_rng(seed + 1))
        t0 = time.perf_counter()
        svc.open_session(sid, session_graph)
        svc.apply_delta(sid, remove_edges=rem, add_edges=add)
        r = svc.recolor(sid)
        colors = np.asarray(svc.colors(sid))
        wall = time.perf_counter() - t0
        m = svc.metrics()

    # the same operations on a session of our own: the service must agree
    own = repro.open_session(session_graph)
    own.apply_delta(remove_edges=rem, add_edges=add)
    own.recolor()
    g2 = own.delta.graph()
    label = "service session"
    check(r.converged and not r.degradations,
          f"{label}: recolor converged={r.converged} "
          f"degradations={list(r.degradations)}")
    check(own.validate(), f"{label}: reference session is invalid")
    check(np.array_equal(colors, own.colors),
          f"{label}: colors differ from a direct session")
    check(m["failed"] == 0 and m["completed"] == m["admitted"],
          f"{label}: service counters {m}")
    report(label, g2, int(colors.max(initial=0)), oracle_colors(g2),
           r.iterations, wall,
           f" requests completed={m['completed']} failed={m['failed']}")


def sharded_vs_ragged(g, ndev: int, label: str, **opts):
    """``engine="sharded"`` and the one-device ``"ragged"`` engine under the
    same options; their colors must agree bit for bit."""
    import repro

    ref, wall_ref = timed(
        lambda: repro.color(g, "data_driven", engine="ragged", **opts))
    r, wall = timed(
        lambda: repro.color(g, "data_driven", engine="sharded", **opts))
    check(r.algorithm == f"sharded_sgr_{ndev}dev",
          f"{label}: ran {r.algorithm!r}, not the {ndev}-device engine")
    check(r.iterations == ref.iterations,
          f"{label}: {r.iterations} super-steps, ragged ran {ref.iterations}")
    check(np.array_equal(r.colors, ref.colors),
          f"{label}: colors differ from the one-device ragged run")
    return r, wall, ref, wall_ref


def phase_sharded(name: str, g, ndev: int) -> None:
    """The sharded engine against one-device ``ragged``: once with the
    default options to a validated coloring, and once with the serial tail
    off for ``SHARDED_SPEC_STEPS`` super-steps, so that every color
    compared comes from the shards' super-steps and their halo exchange
    (the default run's stall tail recolors every vertex on one device)."""
    label = f"sharded {name}"
    r, wall, ref, wall_ref = sharded_vs_ragged(g, ndev, label)
    check_coloring(g, ref, f"ragged {name}")
    check_coloring(g, r, label)
    report(label, g, r.num_colors, oracle_colors(g), r.iterations, wall,
           f" algorithm={r.algorithm} "
           f"halo_bytes_per_step={r.halo_bytes_per_step!r} "
           f"ragged_wall_s={wall_ref:.3f} bit_identical=True")

    label = f"sharded {name} tail_serial=None"
    r, wall, ref, wall_ref = sharded_vs_ragged(
        g, ndev, label, tail_serial=None, max_iters=SHARDED_SPEC_STEPS)
    src, dst = g.edges()
    conflicts = int(np.count_nonzero(r.colors[src] == r.colors[dst])) // 2
    print(f"[{label}] n={g.n} entries={g.m} supersteps={r.iterations} "
          f"colors={r.num_colors} conflicting_edges_left={conflicts} "
          f"converged={r.converged} "
          f"halo_bytes_per_step={r.halo_bytes_per_step!r} "
          f"wall_s={wall:.3f} ragged_wall_s={wall_ref:.3f} "
          "(cold, includes compile) bit_identical=True", flush=True)


def run(chips: int, seed: int) -> dict:
    devs = tpu_devices(chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.graphs import build_graph
    except ImportError as e:
        raise SmokeFailure(f"the repro package is not next to this script: "
                           f"{e}") from None
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"devices: {devs}", flush=True)

    rmat_g = build_graph("rmat-g", 16)
    if chips == 4:
        phase_sharded("rmat-g", rmat_g, chips)
    else:
        phase_oneshot("rmat-g", rmat_g)
        phase_kernel("rmat-g", rmat_g)
        phase_oneshot("G3_circuit", build_graph("G3_circuit", 170))
        phase_session("rmat-g", rmat_g, seed)
        phase_service(build_graph("rmat-g", 1), seed)
    d = devs[0]
    return {"ok": True,
            "device": {"platform": d.platform, "kind": d.device_kind,
                       "count": len(devs)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-engine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        out = run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          "(cold, includes compile)", flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
