#!/usr/bin/env python3
"""Readings that the limits of a training cell's comparison are set from.

    python3 tpubench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds ...] [--witness-seeds ...] [--fault-seeds ...] \\
        [--rehearse]

For each of ``--seeds``, the timed path's first three steps (through
``Trainer.fit``, as a run drives them) against the float32 reference: the
program's readings, which set the lower end of each limit.  For each of
``--control-seeds``, the reference itself computed in fp8 in the
program's place (the control), and for each of ``--fault-seeds`` the
timed path with half of every batch left out: the readings that set the
upper end.  ``--witness-seeds`` reads the reference computed in bfloat16,
the configuration's own precision, beside them.  One JSON line per
reading, with the per-leaf norms it was taken from; everything runs in
one process.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--witness-seeds", default="",
                    help="seeds on which the reference with bfloat16 "
                         "products is read too, beside the control")
    ap.add_argument("--resume-control-seeds", default="",
                    help="seeds of the resume cell's control (a restore "
                         "through bfloat16), run on --workload")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    import jax
    import numpy as np

    import run as bench_run
    from bench import drive
    from bench.compare import train_gaps
    from bench.spec import load_cell

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration readings need the chip (or --rehearse)")

    def cell():
        c = load_cell(args.workload)
        return bench_run.rehearsal(c) if args.rehearse else c

    work = os.path.join(bench_run.WORK_DIR, f"calibrate.{os.getpid()}")

    def program(seed, fault=None):
        drive.clean(work)
        ctx = drive.RunContext(cell=cell(), seed=seed, seconds=0.0,
                               trace=False, work=work, fault=fault,
                               keep_grad1=True)
        try:
            drive.run_train(ctx)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        return ctx

    def norms(readings):
        return {k: np.asarray(readings[k]).tolist()
                for k in ("grad1", "delta3", "grad1_diff") if k in readings}

    for s in seeds(args.seeds):
        ctx = program(s)
        refr = ctx.out["reference_readings"]
        gaps = {k: v for k, (v, _) in ctx.checks.items() if k.startswith("grad1")
                or k.startswith("delta3")}
        print(json.dumps({"kind": "program", "seed": s,
                          "loss_gap": ctx.out["loss_gap"], **gaps,
                          "program": norms(ctx.out["program_readings"]),
                          "reference": norms(refr)}), flush=True)
        for kind, quant, chosen in (("control", "fp8", args.control_seeds),
                                    ("witness:bf16", "bf16", args.witness_seeds)):
            if s not in seeds(chosen):
                continue
            from reference import load_reference

            c = ctx.cell
            ref = load_reference(c.config)
            low = ref.train_readings(c.config, jax.random.PRNGKey(s),
                                     ctx.out["first_batches"], quant=quant,
                                     grad1_of=refr["grad1_leaves"])
            print(json.dumps({"kind": kind, "seed": s,
                              **train_gaps(low, refr, low["grad1_diff"]),
                              "readings": norms(low)}), flush=True)
    for s in seeds(args.resume_control_seeds):
        drive.clean(work)
        ctx = drive.RunContext(cell=cell(), seed=s, seconds=0.0, trace=False,
                               work=work, fault="restore_lowered")
        try:
            drive.run_resume(ctx)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        print(json.dumps({"kind": "control:resume", "seed": s,
                          **{k: v for k, (v, _) in ctx.checks.items()}}),
              flush=True)
    for s in seeds(args.fault_seeds):
        ctx = program(s, fault="half_batch")
        gaps = {k: v for k, (v, _) in ctx.checks.items() if k.startswith("grad1")
                or k.startswith("delta3")}
        print(json.dumps({"kind": "fault:half_batch", "seed": s,
                          "loss_gap": ctx.out["loss_gap"], **gaps}),
              flush=True)


if __name__ == "__main__":
    main()
