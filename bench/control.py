"""The control: a run whose coloring breaks the guarantee the check holds.

The check (``drivers.LIMITS``) must read a control as not correct.  A
traffic mix states its control as ``control_options``: for ``oneshot``,
the program's own path with conflict resolution cut short (the serial
tail off, the super-step budget of a default run).  It is the shortcut a
faster coloring would tempt: stop speculating early and skip the tail.

On the chip, at the cell's size, over several seeds in one process:

    python3 bench/control.py --workload rmat-g.oneshot --seconds 10 --seeds 1 2 3

prints each seed's compared numbers, then one JSON line with all of them.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def execute_control(workload: str, seed: int, seconds: float, *,
                    spec: dict | None = None) -> dict:
    """One run of the cell with the control in the program's place."""
    from bench import harness

    spec = copy.deepcopy(spec or harness.cell_spec(workload))
    traffic = spec["traffic"]
    traffic["options"] = dict(traffic.get("options", {}),
                              **traffic["control_options"])
    return harness.execute(workload, seed, seconds, False,
                           t_start=time.perf_counter(), spec=spec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    harness.enable_compile_cache()
    readings = {}
    for seed in args.seeds:
        r = execute_control(args.workload, seed, args.seconds)
        readings[seed] = {"correct": r["correct"], "attempted": r["attempted"],
                          "compared": r["compared"]}
        harness.log(f"control {args.workload} seed {seed}: {readings[seed]}")
    print(json.dumps({"workload": args.workload, "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
