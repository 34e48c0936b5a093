"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload rmat-g.oneshot --seed 7 --seconds 51 --trace 0

From the root of a checkout.  The cell, its configuration and its traffic
come from ``BENCHMARK.json``.  The run needs a TPU with at least as many
chips as the cell asks for: without one it exits non-zero and prints no
result.  Diagnostics go to standard error, ending with each number compared
beside its limit; the last line of standard output is the result as JSON.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


class NoChip(Exception):
    pass


def require_chips(chips: int) -> None:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    if not (ROOT / "src" / "repro").is_dir():
        harness.log(f"no program to measure: {ROOT / 'src' / 'repro'} is missing")
        return 2
    spec = harness.cell_spec(args.workload)
    try:
        require_chips(int(spec["cell"]["chips"]))
    except NoChip as e:
        harness.log(str(e))
        return 2
    harness.enable_compile_cache()
    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, spec=spec)
    for name, c in result["compared"].items():
        harness.log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
