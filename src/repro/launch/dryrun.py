import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver jits the real step function (train_step for
train shapes, prefill/serve steps for inference shapes) against
ShapeDtypeStruct inputs with full production shardings, compiles it, and
records:

* ``memory_analysis()``  — proves the cell fits per-device HBM;
* ``cost_analysis()``    — HLO FLOPs / bytes for the roofline;
* per-device collective bytes, parsed from the compiled HLO
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute operand sizes) — cost_analysis does not report them.

Reports land in ``reports/dryrun/<arch>__<shape>__<mesh>.json``.

Usage::

    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all                 # every runnable cell
    python -m repro.launch.dryrun --all --multi-pod     # 2x16x16 pass
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import model_flops
from repro.configs import ARCH_IDS, SHAPES, SKIP_CELLS, get_config, resolve
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_specs, input_specs
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step, train_state_shape)
from repro.models.api import build_model
from repro.models.common import set_sharding_profile
from repro.optim.adamw import AdamWConfig

_last_profile = [None]  # set by lower_cell; read by run_cell for the report

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _resident_bytes_per_device(sds_trees, spec_trees, mesh) -> int:
    """Exact per-device bytes of sharded residents (state/params/cache):
    sum over leaves of nbytes / (product of mesh-axis sizes in its spec)."""
    from jax.sharding import PartitionSpec

    total = 0
    for sds_tree, spec_tree in zip(sds_trees, spec_trees):
        leaves = jax.tree.leaves(sds_tree)
        specs = jax.tree.leaves(spec_tree,
                                is_leaf=lambda x: isinstance(x, PartitionSpec))
        for leaf, spec in zip(leaves, specs):
            frac = 1
            for axis in tuple(spec):
                if axis is None:
                    continue
                for a in (axis if isinstance(axis, tuple) else (axis,)):
                    frac *= mesh.shape[a]
            total += leaf.size * leaf.dtype.itemsize // frac
    return total


def _shape_bytes(sig: str) -> int:
    """Sum byte sizes of every array shape in an HLO result signature."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (result sizes of the
    per-partition SPMD module)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+(\w[\w-]*)\(", s)
        if not m:
            continue
        op = m.group(2)
        # fusion(...) etc. won't match a collective name; *-start ops count,
        # their corresponding *-done ops don't (avoid double counting).
        base = op.replace("-start", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            out[base] += _shape_bytes(m.group(1))
            out["count"] += 1
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               opt_overrides: Dict[str, Any] = None,
               profile: str = None):
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    # serve cells engage the model axis via activation sharding ("tp");
    # train cells use the arch default (fsdp except DeepSeek's EP).
    if profile is None:
        profile = cfg.sharding_profile if shape.kind == "train" else "tp"
    set_sharding_profile(profile)
    _last_profile[0] = profile

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig(**(opt_overrides or {}))
            state_sds = train_state_shape(model, opt_cfg)
            batch_sds = input_specs(cfg, shape)
            pspecs = shd.param_specs(state_sds["params"], mesh)
            state_specs = {"params": pspecs,
                           "opt": shd.opt_state_specs(state_sds["opt"], pspecs, mesh)}
            bspecs = shd.batch_specs(batch_sds, mesh, profile)
            step = make_train_step(model, opt_cfg)
            metrics_specs = {"loss": P(), "grad_norm": P(), "lr": P()}
            jitted = jax.jit(step,
                             in_shardings=(shd.named(state_specs, mesh),
                                           shd.named(bspecs, mesh)),
                             out_shardings=(shd.named(state_specs, mesh),
                                            shd.named(metrics_specs, mesh)),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
            resident = _resident_bytes_per_device(
                [state_sds, batch_sds], [state_specs, bspecs], mesh)
        elif shape.kind == "prefill":
            params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            batch_sds = input_specs(cfg, shape)
            pspecs = shd.param_specs(params_sds, mesh)
            bspecs = shd.batch_specs(batch_sds, mesh, profile)
            step = make_prefill_step(model, shape.seq_len)
            _, cache_sds = jax.eval_shape(step, params_sds, batch_sds)
            cspecs = shd.cache_specs(cache_sds, mesh, profile)
            logits_spec = shd.spec_from_prefs(
                (shape.global_batch, cfg.padded_vocab),
                [(-2, "dp"), (-1, "model")], mesh, profile)
            jitted = jax.jit(step,
                             in_shardings=(shd.named(pspecs, mesh),
                                           shd.named(bspecs, mesh)),
                             out_shardings=(shd.named(logits_spec, mesh),
                                            shd.named(cspecs, mesh)))
            lowered = jitted.lower(params_sds, batch_sds)
            resident = _resident_bytes_per_device(
                [params_sds, batch_sds], [pspecs, bspecs], mesh)
        else:  # decode
            params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            pspecs = shd.param_specs(params_sds, mesh)
            # the serve-time cache: same structure prefill would produce
            if model.is_enc_dec:
                pre_batch = input_specs(cfg, SHAPES["train_4k"])
                pre_batch["tokens"] = jax.ShapeDtypeStruct(
                    (shape.global_batch, 8), jnp.int32)
                pre_batch["frames"] = jax.ShapeDtypeStruct(
                    (shape.global_batch, cfg.enc_dec.n_audio_ctx, cfg.d_model),
                    jnp.bfloat16)
                pre_batch.pop("labels", None)
                _, cache_sds = jax.eval_shape(
                    lambda p, b: model.prefill(p, b, shape.seq_len),
                    params_sds, pre_batch)
            else:
                from repro.models import lm

                cache_sds = jax.eval_shape(
                    lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len))
            cspecs = shd.cache_specs(cache_sds, mesh, profile)
            tok_sds, pos_sds = decode_specs(cfg, shape)
            bspec = shd.spec_from_prefs((shape.global_batch,),
                                        [(-1, "dp")], mesh, profile)
            logits_spec = shd.spec_from_prefs(
                (shape.global_batch, cfg.padded_vocab),
                [(-2, "dp"), (-1, "model")], mesh, profile)
            step = make_decode_step(model)
            jitted = jax.jit(step,
                             in_shardings=(shd.named(pspecs, mesh),
                                           shd.named(cspecs, mesh),
                                           shd.named(bspec, mesh),
                                           shd.named(bspec, mesh)),
                             out_shardings=(shd.named(logits_spec, mesh),
                                            shd.named(cspecs, mesh)),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_sds, cache_sds, tok_sds, pos_sds)
            resident = _resident_bytes_per_device(
                [params_sds, cache_sds], [pspecs, cspecs], mesh)
    return lowered, mesh, resident


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             opt_overrides=None, verbose: bool = True,
             profile: str = None, tag: str = "") -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    lowered, mesh, resident = lower_cell(arch, shape_name, multi_pod,
                                         opt_overrides, profile=profile)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = analyze_hlo(compiled.as_text())
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mflops = model_flops(cfg, shape, shape.kind) / mesh.size
    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "profile": _last_profile[0],
        "devices": int(mesh.size),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            # NB: the forced-host-platform memory_analysis aggregates across
            # partitions and is unreliable for argument sizes; resident_bytes
            # is computed exactly from the sharded input trees.
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "resident_bytes_per_device": resident,
            "temp_bytes_per_device": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            // mesh.size,
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        "hlo": hlo.to_dict(),
        "model_flops_per_dev": mflops,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = f"{out_dir}/{resolve(arch)}__{shape_name}__{mesh_name}{tag}.json"
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    if verbose:
        hbm = report["memory"]["resident_bytes_per_device"] + \
            report["memory"]["temp_bytes_per_device"]
        print(f"[dryrun] {arch:22s} {shape_name:12s} {mesh_name:8s} "
              f"OK  lower={t_lower:6.1f}s compile={t_compile:6.1f}s "
              f"hbm/dev={_gb(hbm)}  dotflops/dev={hlo.dot_flops:.3e} "
              f"(model {mflops:.3e})  coll/dev={_gb(hlo.collective_bytes)}",
              flush=True)
    return report


def _gb(n) -> str:
    if n is None:
        return "?"
    return f"{n / (1 << 30):.2f}GiB"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--no-master", action="store_true",
                    help="memory-lean optimizer (no fp32 master copy)")
    ap.add_argument("--profile", default=None, choices=["tp", "fsdp"],
                    help="override the arch's sharding profile")
    ap.add_argument("--tag", default="", help="report filename suffix")
    args = ap.parse_args()

    opt_overrides = {"keep_master": False} if args.no_master else None
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                if (a, s) in SKIP_CELLS:
                    print(f"[dryrun] SKIP {a} {s}: {SKIP_CELLS[(a, s)]}")
                    continue
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((resolve(args.arch), args.shape))

    failures = []
    for mp in meshes:
        for a, s in cells:
            try:
                run_cell(a, s, mp, args.out, opt_overrides,
                         profile=args.profile, tag=args.tag)
            except Exception as e:
                failures.append((a, s, mp, repr(e)))
                print(f"[dryrun] FAIL {a} {s} multi_pod={mp}: {e}",
                      flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
