#!/usr/bin/env python3
"""On-chip benchmark of the storage layer under a JAX training job.

    python3 tpubench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 tpubench/run.py --workload <cell> --seed <n> --seconds 2 --rehearse

A cell of ``BENCHMARK.json`` names a configuration (``tpubench/configs``)
and a traffic mix (``tpubench/traffic``).  The run builds the system's
training objects, warms up every program the window will use (set-up),
measures for ``--seconds``, then checks what the window produced against
the plain reference (``tpubench/reference``).  ``--trace 1`` also records
a profiler trace of the window and reports the per-layer metrics
(``tpubench/metrics``) in place of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last the numbers compared, each with its limit.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.  ``--rehearse`` runs on the CPU at the sizes under
each file's ``rehearsal`` key, with Pallas interpreted, and marks its
result as a rehearsal.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(CHECKOUT, "src")]

from bench.spec import Cell, load_cell, quantity  # noqa: E402

#: fixed, so that the persistent compilation cache is found again
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
WORK_DIR = os.path.join(BENCH, ".work")


class NoAccelerator(SystemExit):
    pass


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def rehearsal(cell: Cell) -> Cell:
    """The cell at the sizes its files give for a CPU rehearsal."""
    cell.config = _merge(cell.config, cell.config.get("rehearsal", {}))
    cell.traffic = _merge(cell.traffic, cell.traffic.get("rehearsal", {}))
    return cell


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' rehearsal sizes")
    return ap.parse_args(argv)


def load_metric_reader(bench_dir: str, name: str):
    """``metrics/<name>.py``, or for a qualified name with no reader of its
    own, the reader of the quantity it qualifies (``step_mfu.save`` reads
    as ``step_mfu``)."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(bench_dir, "metrics", f"{quantity(name)}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader {path} for metric {name}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(argv: Optional[List[str]] = None, fault: Optional[str] = None,
        bench_dir: str = BENCH, require_tpu: bool = True) -> Dict[str, Any]:
    """One run; returns the result object (also printed last)."""
    args = parse_args(argv)
    cell = load_cell(args.workload, bench_dir=bench_dir)
    if args.rehearse:
        cell = rehearsal(cell)
        require_tpu = False

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX finds {devices[0].platform}; "
              f"a measured run needs the chip", file=sys.stderr)
        raise NoAccelerator(4)
    if len(devices) < cell.chips:
        print(f"[bench] the cell asks for {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        raise NoAccelerator(4)

    from bench import drive, flops
    from bench.trace import load as load_trace

    work = os.path.join(WORK_DIR, f"{cell.name}.{os.getpid()}")
    drive.clean_stale(WORK_DIR)
    drive.clean(work)
    ctx = drive.RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), work=work, chips=cell.chips,
                           fault=fault)
    ctx.spans.annotate = ctx.trace
    ctx.trace_dir = os.path.join(work, "trace")
    kind = cell.traffic["kind"]
    try:
        drive.DRIVERS[kind](ctx)
        setup_s = ctx.window[0] - T_START
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell.chips,
                  "memory_peak_bytes": ctx.out.get("memory_peak_bytes")}
        result: Dict[str, Any] = {"correct": None, "attempted": ctx.out["attempted"],
                                  "failed": ctx.out["failed"], "metrics": {},
                                  "device": device}
        if args.rehearse:
            result["rehearsal"] = True
        if not args.trace:
            vals = dict(ctx.out["e2e"], setup_s=setup_s)
            for m in cell.end_to_end:
                v = vals[m.name] if m.name in vals else vals[quantity(m.name)]
                result["metrics"][m.name] = {"value": v, "unit": m.unit}
        else:
            tr = load_trace(ctx.trace_dir, devices=cell.chips)
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            ctx.trace_data = tr
            for m in cell.per_layer:
                v = load_metric_reader(bench_dir, m.name)(ctx)
                if v is not None:
                    result["metrics"][m.name] = {"value": v, "unit": m.unit}
            result["breakdown"] = {"device_ops": tr.top_ops(10),
                                   "idle_gaps": tr.idle_gaps(10)}
        state_bytes = ctx.out.get("train_state_bytes")
        print(f"[bench] train_state_bytes {state_bytes} (configuration file: "
              f"{flops.train_state_bytes(cell.config)}), device "
              f"memory_peak_bytes {device['memory_peak_bytes']} (the larger of "
              f"peak_bytes_in_use {ctx.out.get('peak_bytes_in_use')} and the "
              f"compiled step's {ctx.out.get('step_memory_bytes')}), host peak "
              f"RSS {drive._host_peak_rss()} bytes, compiles in the window "
              f"{ctx.out['window_compiles']}", flush=True)
        if "loss_gap" in ctx.out:
            print(f"[bench] loss_gap {ctx.out['loss_gap']!r} (read, not "
                  f"compared); leaves left out of the comparison "
                  f"{ctx.out['leaves_left_out']}", flush=True)
        checks = {k: {"value": v, "limit": lim} for k, (v, lim) in ctx.checks.items()}
        result["correct"] = bool(checks) and all(
            c["value"] <= c["limit"] for c in checks.values())
        result["checks"] = checks
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


def main() -> None:
    try:
        run()
    except NoAccelerator as e:
        sys.exit(e.code)


if __name__ == "__main__":
    main()
