"""chipbench: one process, one cell, once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

Resolves, by name and from data files only,
``workloads/<cell>.json`` -> ``configs/<config>.{json,py}`` ->
``drivers/<driver>.py``, and every metric that ``BENCHMARK.json`` (or
``chipbench/candidates.json``, the cells kept for later) lists for the cell
-> ``end_to_end/<metric>.py`` or ``layer_metrics/<metric>.py``.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced).

Without a TPU, or with fewer devices than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` (with JAX_PLATFORMS=cpu) is
the only CPU mode: the sizes of the cell's ``rehearse`` block, ``device``
says ``cpu``, and ``metrics`` keeps counts only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import re                            # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def _load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module, found by name."""
    if not NAME_RE.match(name):
        sys.exit(f"chipbench: bad {kind} name {name!r}")
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        sys.exit(f"chipbench: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def registry():
    """BENCHMARK.json merged with candidates.json (same schema: the cells
    and metrics PERF.md keeps for later; the driver never reads it)."""
    merged = _load_json(ROOT, "BENCHMARK.json")
    extra = os.path.join(HERE, "candidates.json")
    if os.path.isfile(extra):
        more = _load_json(extra)
        for key in ("workloads", "end_to_end", "per_layer"):
            merged[key] = merged.get(key, []) + more.get(key, [])
    return merged


def metrics_for(cell_name: str, listed: list) -> list:
    """The entries of one BENCHMARK.json metric list that this cell
    reports: all without a ``workloads`` key, and those that name it."""
    return [m for m in listed
            if "workloads" not in m or cell_name in m["workloads"]]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) \
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one parameter of the cell's file, for a "
                         "sweep (the driver never passes it)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit("chipbench: no paddle_tpu/ beside chipbench/: the system "
                 "under test is not in this directory")
    if not NAME_RE.match(args.workload):
        sys.exit(f"chipbench: bad workload name {args.workload!r}")
    sys.path.insert(0, ROOT)

    bench = registry()
    cell_path = os.path.join(HERE, "workloads", f"{args.workload}.json")
    if not os.path.isfile(cell_path):
        sys.exit(f"chipbench: no workloads/{args.workload}.json")
    cell = _load_json(cell_path)
    for item in args.set:
        key, _, value = item.partition("=")
        cell[key] = json.loads(value)
    sizes = _load_json(HERE, "configs", f"{cell['config']}.json")
    if args.rehearse:
        over = cell.get("rehearse", {})
        sizes = _merge(sizes, over.get("sizes", {}))
        cell = _merge(cell, {k: v for k, v in over.items() if k != "sizes"})
        # virtual CPU devices for a cell that spans chips (before JAX starts)
        flags = os.environ.get("XLA_FLAGS", "")
        have = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if cell["chips"] > 1 and (not have or int(have.group(1)) < cell["chips"]):
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                           flags)
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell['chips']}").strip()

    import jax

    from chipbench.lib import device, peaks
    from chipbench.lib.context import RunContext

    devices = device.require_devices(cell["chips"], args.rehearse)
    # one persistent cache, where the program itself keeps it
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), and every
    # program in it however quickly it compiled, so that a second run of a
    # cell finds all of them
    if not args.rehearse:
        from paddle_tpu.core import compile_cache
        compile_cache.cache_dir()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = RunContext(
        args, ROOT, cell, sizes, _load_module("configs", cell["config"]),
        devices,
        None if args.rehearse else peaks.peaks_for(devices[0].device_kind),
        T_START)
    ctx.mark("imported")
    verdict = _load_module("drivers", cell["driver"]).run(ctx)

    which = "per_layer" if args.trace else "end_to_end"
    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_for(args.workload, bench[which]):
        if args.rehearse and entry["unit"] != "count":
            continue            # a CPU run gives no time, rate or share
        value = _load_module(folder, entry["name"]).compute(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": bool(verdict["correct"]),
            "attempted": int(verdict["attempted"]),
            "failed": int(verdict["failed"]),
            "metrics": metrics, "device": dev}
    if ctx.trace is not None:
        if not ctx.trace.busy_s > 0:
            sys.exit("chipbench: the traced window holds no device "
                     "operation; no result")
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        line["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                             "idle_gaps": ctx.trace.top_gaps(10)}
    line["detail"] = ctx.detail
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
