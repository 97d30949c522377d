#!/usr/bin/env python3
"""Compile each training cell's step for a described TPU v5e, without the
chip, and print its memory analysis.

    JAX_PLATFORMS=cpu python3 tpubench/aot.py [--workload <cell> ...]

The step is the system's (``launch/steps.make_train_step``) at the cell's
configuration, batch and length, jitted as the trainer jits it (state
donated), with the Pallas attention kernel selected explicitly (on the
CPU host ``"auto"`` would pick the XLA reference).  A compile that passes
is not a chip run: it says the program fits and the kernel is in it,
nothing about time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from bench.spec import load_cell  # noqa: E402


def compile_cell(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.launch.steps import make_train_state, make_train_step
    from bench import drive, flops

    cell = load_cell(name)
    cell.config["program_config"]["attn_impl"] = "pallas"
    model, _ = drive.build_model(cell)
    opt = drive.opt_config(cell)
    one = SingleDeviceSharding(topo.devices[0])
    like = jax.eval_shape(lambda r: make_train_state(model, opt, r),
                          jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                        sharding=one), like)
    B, S = int(cell.traffic["batch"]), int(cell.traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0,))
    compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    ma = compiled.memory_analysis()
    return {
        "cell": name,
        "train_state_bytes": flops.train_state_bytes(cell.config),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bj = json.load(f)
    names = args.workload or [
        w["name"] for w in bj["workloads"]
        if load_cell(w["name"]).traffic["kind"] == "train"]
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)


if __name__ == "__main__":
    main()
