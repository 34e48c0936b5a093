import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# --- the two lines above MUST run before any jax import (device count locks
# at first init).  Tests may shrink the placeholder fleet via env override:
if os.environ.get("REPRO_DRYRUN_FLAGS"):
    os.environ["XLA_FLAGS"] = os.environ["REPRO_DRYRUN_FLAGS"]

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding resolution is coherent (SPMD partitioner accepts it),
  * the program fits (memory_analysis),
  * and extracts the roofline inputs: cost_analysis FLOPs/bytes plus
    collective bytes parsed from the post-SPMD HLO.

Results append incrementally to a JSON file consumed by
``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config, list_archs
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    replicated,
    state_shardings,
)
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training import AdamWConfig, init_train_state, make_train_step

SHAPES = {
    "train_4k": dict(seq=4096, batch=256, mode="train"),
    "prefill_32k": dict(seq=32768, batch=32, mode="prefill"),
    "decode_32k": dict(seq=32768, batch=128, mode="decode"),
    "long_500k": dict(seq=524288, batch=1, mode="decode"),
}

# long_500k needs sub-quadratic attention: SWA (mixtral), RG-LRU hybrid,
# linear-attention RWKV.  Pure full-attention archs skip it (DESIGN.md §9).
SUBQUADRATIC = {"mixtral-8x22b", "recurrentgemma-2b", "rwkv6-1.6b"}


def plan_cells() -> list[tuple[str, str, str | None]]:
    """(arch, shape, skip_reason|None) for all 40 nominal cells."""
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in SHAPES:
            skip = None
            if cfg.family == "encoder" and shape in ("decode_32k", "long_500k"):
                skip = "encoder-only: no decode step"
            elif shape == "long_500k" and arch not in SUBQUADRATIC:
                skip = "full quadratic attention at 500k"
            cells.append((arch, shape, skip))
    return cells


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
# instruction lines look like:
#   %x = s32[16,1024]{1,0} all-gather(%y), channel_id=3, replica_groups=[64,4]<=[256], ...
# operands print WITHOUT type annotations, so transfer volume is accounted
# from the OUTPUT shape + the replica group size n (ring-algorithm costs):
#   all-gather:         out * (n-1)/n         (out = gathered size)
#   all-reduce:         out * 2(n-1)/n
#   reduce-scatter:     out * (n-1)            (input = n * out)
#   all-to-all:         out * (n-1)/n
#   collective-permute: out
_LINE_RE = re.compile(
    r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" + "|".join(_COLL_OPS) + r")(-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _coll_bytes(op: str, out_bytes: int, n: int) -> float:
    n = max(n, 2)
    if op == "all-gather":
        return out_bytes * (n - 1) / n
    if op == "all-reduce":
        return out_bytes * 2 * (n - 1) / n
    if op == "reduce-scatter":
        return out_bytes * (n - 1)
    if op == "all-to-all":
        return out_bytes * (n - 1) / n
    return float(out_bytes)  # collective-permute


def collective_stats(hlo_text: str) -> dict:
    stats: dict[str, dict] = {
        op: {"count": 0, "out_bytes": 0, "moved_bytes": 0.0} for op in _COLL_OPS
    }
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m or "-done(" in line:
            continue
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        nelem = 1
        for d in dims.split(","):
            if d:
                nelem *= int(d)
        out_bytes = nelem * _DTYPE_BYTES[dtype]
        gm = _GROUPS_RE.search(line)
        if gm:
            n = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            n = len(gb.group(1).split(",")) if gb else 2
        stats[op]["count"] += 1
        stats[op]["out_bytes"] += out_bytes
        stats[op]["moved_bytes"] += _coll_bytes(op, out_bytes, n)
    stats["total_factored_bytes"] = sum(
        s["moved_bytes"] for s in stats.values() if isinstance(s, dict)
    )
    return stats


def memory_stats(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover - backend-dependent
        return {"error": str(e)}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def cost_stats(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    keep = {}
    for k in ("flops", "bytes accessed", "transcendentals", "utilization"):
        if k in ca:
            keep[k] = float(ca[k])
    return keep


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def build_lowered(arch: str, shape_name: str, mesh, cfg=None):
    cfg = cfg or get_config(arch)
    model = build_model(cfg, mesh=mesh)
    info = SHAPES[shape_name]
    S, B, mode = info["seq"], info["batch"], info["mode"]

    params_s = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params_sh = state_shardings(params_s, mesh)

    if mode == "train":
        state_s = jax.eval_shape(
            lambda: init_train_state(model, jax.random.PRNGKey(0)))
        state_sh = state_shardings(state_s, mesh)
        batch_s = model.input_specs(B, S, "train")
        batch_sh = batch_shardings(batch_s, mesh)
        step = make_train_step(model, AdamWConfig())
        jitted = jax.jit(
            step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,))
        return jitted.lower(state_s, batch_s)

    if mode == "prefill":
        batch_s = model.input_specs(B, S, "prefill")
        batch_sh = batch_shardings(batch_s, mesh)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, S)

        jitted = jax.jit(prefill_fn, in_shardings=(params_sh, batch_sh))
        return jitted.lower(params_s, batch_s)

    # decode: one token against a seq_len-deep cache
    specs = model.input_specs(B, S, "decode")
    caches_s, token_s = specs["caches"], specs["token"]
    cache_sh = cache_shardings(caches_s, mesh)
    token_sh = batch_shardings(token_s, mesh)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_fn(params, caches, token, pos):
        return model.decode_step(params, caches, token, pos)

    jitted = jax.jit(
        decode_fn,
        in_shardings=(params_sh, cache_sh, token_sh, replicated(mesh)),
        donate_argnums=(1,),
    )
    return jitted.lower(params_s, caches_s, token_s, pos_s)


def run_cell(arch: str, shape_name: str, mesh_kind: str, mesh=None) -> dict:
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    cfg = get_config(arch)
    info = SHAPES[shape_name]
    n_total, n_active = cfg.params_estimate()
    tokens = info["batch"] * (info["seq"] if info["mode"] != "decode" else 1)
    flops_per_tok = 6 if info["mode"] == "train" else 2
    rec.update(
        params_total=n_total,
        params_active=n_active,
        model_flops=float(flops_per_tok * n_active * tokens),
        mode=info["mode"],
    )
    try:
        if mesh is None:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "pod"))
        t0 = time.time()
        lowered = build_lowered(arch, shape_name, mesh)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["cost"] = cost_stats(compiled)
        rec["memory"] = memory_stats(compiled)
        text = compiled.as_text()
        rec["collectives"] = collective_stats(text)
        hc = analyze_hlo(text)
        rec["analysis"] = {
            "flops": hc.flops,
            "traffic_bytes": hc.traffic,
            "collective_bytes": hc.collective_bytes,
            "collectives": hc.collectives,
            "unknown_trip_counts": hc.unknown_trip_counts,
        }
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------

def _load(out):
    if os.path.exists(out):
        with open(out) as f:
            return json.load(f)
    return []


def _save(out, records):
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(records, f, indent=1)
    os.replace(tmp, out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "pod", "both"), default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--mesh-shape", help="override, e.g. 2x4 (tests)")
    ap.add_argument("--mesh-axes", help="override, e.g. data,model (tests)")
    args = ap.parse_args()

    if args.list:
        for arch, shape, skip in plan_cells():
            print(f"{arch:22s} {shape:12s} {'SKIP: ' + skip if skip else 'run'}")
        return

    mesh_override = None
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        axes = tuple(args.mesh_axes.split(",")) if args.mesh_axes else (
            ("data", "model") if len(shape) == 2 else ("pod", "data", "model"))
        mesh_override = jax.make_mesh(
            shape, axes, axis_types=(AxisType.Auto,) * len(axes))

    records = _load(args.out)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in records if r.get("ok")}

    cells = plan_cells()
    if not args.all:
        cells = [
            (a, s, sk) for a, s, sk in cells
            if (args.arch is None or a == args.arch)
            and (args.shape is None or s == args.shape)
        ]
    meshes = ["single", "pod"] if args.mesh == "both" else [args.mesh]

    for arch, shape, skip in cells:
        for mesh_kind in meshes:
            key = (arch, shape, mesh_kind)
            if skip:
                if not any(
                    r["arch"] == arch and r["shape"] == shape
                    and r["mesh"] == mesh_kind for r in records
                ):
                    records.append({
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "ok": True, "skipped": skip,
                    })
                    _save(args.out, records)
                print(f"SKIP {arch} {shape} {mesh_kind}: {skip}", flush=True)
                continue
            if key in done and not args.force:
                print(f"done {arch} {shape} {mesh_kind} (cached)", flush=True)
                continue
            print(f"RUN  {arch} {shape} {mesh_kind} ...", flush=True)
            rec = run_cell(arch, shape, mesh_kind, mesh=mesh_override)
            records = [
                r for r in records
                if (r["arch"], r["shape"], r["mesh"]) != key
            ] + [rec]
            _save(args.out, records)
            status = "OK" if rec.get("ok") else f"FAIL {rec.get('error')}"
            print(
                f"  -> {status} lower={rec.get('lower_s')}s "
                f"compile={rec.get('compile_s')}s "
                f"flops={rec.get('cost', {}).get('flops')}",
                flush=True,
            )


if __name__ == "__main__":
    main()
