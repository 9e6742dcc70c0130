"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
against the production mesh and report memory / cost / roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --cells lm --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --cells krr --mesh multipod

The first two lines below MUST run before any other import: jax locks the
device count at first init, and the dry-run needs 512 placeholder CPU devices
to build the 2x16x16 production mesh.  (Do NOT copy this into tests or
benchmarks — they are supposed to see 1 device.)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import registry
from ..configs.base import SHAPES
from ..configs.wlsh_krr import CONFIG as KRR_CONFIG, KRR_SHAPES
from ..core.bucket_fns import get_bucket_fn
from ..core.distributed import KRRStepConfig, make_krr_step
from ..core.lsh import LSHParams
from ..hlo_analysis import analyze_compiled
from ..models import model
from ..optim import AdamWConfig
from ..optim.adamw import AdamWState
from .mesh import make_production_mesh
from .specs import (batch_shardings, batch_specs, cache_shardings,
                    decode_specs, param_shardings, useful_flops)
from .steps import make_decode_step, make_prefill_step, make_train_step

SDS = jax.ShapeDtypeStruct


def _opt_abstract(cfg):
    ps = model.abstract_params(cfg, jnp.float32)
    return AdamWState(step=SDS((), jnp.int32), m=ps, v=ps)


def _opt_shardings(cfg, mesh):
    pshard = param_shardings(cfg, mesh)
    return AdamWState(step=NamedSharding(mesh, P()), m=pshard, v=pshard)


# microbatch count per arch for train cells (activation-memory lever; chosen
# so temp bytes/device fit the 16 GB v5e HBM — see EXPERIMENTS.md §Dry-run)
TRAIN_MICROBATCHES: dict[str, int] = {
    "phi3-mini-3.8b": 1, "qwen3-14b": 1, "gemma3-1b": 1,
    "command-r-plus-104b": 4, "llama4-scout-17b-a16e": 2, "mixtral-8x22b": 4,
    "zamba2-7b": 2, "rwkv6-1.6b": 1, "llama-3.2-vision-90b": 4,
    "whisper-large-v3": 1,
}
# NOTE: these are the POST-hillclimb shipping values (EXPERIMENTS.md §Perf);
# the frozen baseline grid in reports/pod.jsonl used 8 for the big models.


def lower_lm_cell(arch: str, shape_name: str, mesh, micro: int | None = None):
    """Returns (lowered, compiled, model_flops) for one LM cell."""
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    mf = useful_flops(cfg, shape)

    if shape.kind == "train":
        nm = micro if micro is not None else TRAIN_MICROBATCHES.get(arch, 1)
        step = make_train_step(cfg, AdamWConfig(), num_microbatches=nm)
        args = (model.abstract_params(cfg, jnp.float32), _opt_abstract(cfg),
                batch_specs(cfg, shape))
        in_sh = (param_shardings(cfg, mesh), _opt_shardings(cfg, mesh),
                 batch_shardings(cfg, shape, mesh))
        out_sh = (in_sh[0], in_sh[1], None)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 1))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, max_cache_len=shape.seq_len)
        args = (model.abstract_params(cfg, jnp.bfloat16),
                batch_specs(cfg, shape))
        in_sh = (param_shardings(cfg, mesh, jnp.bfloat16),
                 batch_shardings(cfg, shape, mesh))
        out_sh = (None, cache_shardings(cfg, shape, mesh), None)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    else:  # decode
        from ..sharding import spec_for
        step = make_decode_step(cfg)
        cache, tok, pos = decode_specs(cfg, shape)
        args = (model.abstract_params(cfg, jnp.bfloat16), cache, tok, pos)
        csh = cache_shardings(cfg, shape, mesh)
        b = shape.global_batch
        tok_sh = NamedSharding(mesh, spec_for(("batch", None), (b, 1), mesh))
        pos_sh = NamedSharding(mesh, spec_for(("batch",), (b,), mesh))
        in_sh = (param_shardings(cfg, mesh, jnp.bfloat16), csh, tok_sh, pos_sh)
        out_sh = (None, csh)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(1,))

    from ..sharding import use_rules_mesh
    with use_rules_mesh(mesh):
        lowered = jitted.lower(*args)
    compiled = lowered.compile()
    return lowered, compiled, mf


def lower_krr_cell(shape_name: str, mesh, variant: str = "psum"):
    """Lower the paper's own distributed KRR step.

    variant 'psum' is the paper-faithful baseline (dense CountSketch table
    merged with a psum); 'hashjoin' is the beyond-paper optimized version
    (sharded table + nonzero routing via all_to_all) — see §Perf.
    """
    from ..core.distributed import make_krr_step_hashjoin
    spec = KRR_SHAPES[shape_name]
    n, m, b = spec["n_points"], spec["m"], spec["table_size"]
    d = KRR_CONFIG.dim
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cfg = KRRStepConfig(m=m, table_size=b, lam=KRR_CONFIG.lam,
                        cg_iters=KRR_CONFIG.cg_iters, data_axes=data_axes,
                        model_axis="model", backend=KRR_CONFIG.backend)
    f = get_bucket_fn(KRR_CONFIG.bucket)
    # cap_factor 1.25: at krr_4m the per-destination load is 65536 +- 248
    # (binomial), so 1.25x mean is a +66-sigma overflow margin — free traffic
    # reduction vs the conservative 2.0 default.  Wire dtype follows the
    # config ('bf16' default halves the all_to_all bytes again).
    wire = jnp.bfloat16 if KRR_CONFIG.wire_dtype == "bf16" else jnp.float32
    step = (make_krr_step_hashjoin(mesh, cfg, f, cap_factor=1.25,
                                   payload_dtype=wire)
            if variant == "hashjoin" else make_krr_step(mesh, cfg, f))
    lsh = LSHParams(w=SDS((m, d), jnp.float32), z=SDS((m, d), jnp.float32),
                    r1=SDS((m, d), jnp.uint32), r2=SDS((m, d), jnp.uint32))
    jitted = jax.jit(step)
    lowered = jitted.lower(SDS((n, d), jnp.float32), SDS((n,), jnp.float32),
                           lsh)
    compiled = lowered.compile()
    # useful FLOPs: per CG iter, featurized matvec = scatter + gather + dots:
    # ~6 flops per (instance, point) plus table psum is comms, not flops.
    mf = (cfg.cg_iters + 2) * (6.0 * m * n) + 10.0 * m * n  # featurize ~10/pt
    return lowered, compiled, mf


def run_cell(kind: str, arch: str, shape_name: str, mesh, mesh_name: str,
             micro: int | None = None, krr_variant: str = "psum"):
    t0 = time.time()
    if kind == "krr":
        lowered, compiled, mf = lower_krr_cell(shape_name, mesh, krr_variant)
        suffix = "" if krr_variant == "psum" else f"+{krr_variant}"
        name = f"wlsh_krr{suffix}/{shape_name}/{mesh_name}"
    else:
        lowered, compiled, mf = lower_lm_cell(arch, shape_name, mesh, micro)
        name = f"{arch}/{shape_name}/{mesh_name}"
    dt = time.time() - t0
    chips = mesh.devices.size
    roof = analyze_compiled(name, compiled, chips=chips, model_flops=mf)
    mem = compiled.memory_analysis()
    row = roof.row()
    row.update({
        "compile_s": round(dt, 1),
        "arg_bytes_per_device": int(getattr(mem, "argument_size_in_bytes", 0)),
        "temp_bytes_per_device": int(getattr(mem, "temp_size_in_bytes", 0)),
        "output_bytes_per_device": int(getattr(mem, "output_size_in_bytes", 0)),
        "collective_counts": dict(roof.stats.collective_counts),
        "collective_bytes_by_op": {k: int(v) for k, v in
                                   roof.stats.collective_bytes_by_op.items()},
        "xla_flops_per_dev": roof.stats.xla_flops,
    })
    print(f"[dryrun] {name}: compile {dt:.1f}s  "
          f"args/dev {row['arg_bytes_per_device']/1e9:.2f} GB  "
          f"temp/dev {row['temp_bytes_per_device']/1e9:.2f} GB  "
          f"flops {roof.hlo_flops:.3e}  coll {roof.collective_bytes/1e9:.3f} GB  "
          f"dominant={roof.dominant}  roofline_frac={roof.roofline_frac:.3f}")
    return row


def iter_cells(cells: str, arch: str | None, shape: str | None):
    if cells in ("lm", "all"):
        for a in registry.ARCH_IDS:
            if arch and a != arch:
                continue
            for s in SHAPES:
                if shape and s != shape:
                    continue
                if not registry.runs_shape(a, s):
                    continue
                yield ("lm", a, s)
    if cells in ("krr", "all") and (arch in (None, "wlsh_krr")):
        for s in KRR_SHAPES:
            if shape and s != shape:
                continue
            yield ("krr", "wlsh_krr", s)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--cells", default="all", choices=["lm", "krr", "all"])
    ap.add_argument("--out", default=None, help="append-mode JSONL report")
    ap.add_argument("--micro", type=int, default=None,
                    help="override train microbatch count")
    ap.add_argument("--krr-variant", default="psum",
                    choices=["psum", "hashjoin"])
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer-group scan (perf experiment)")
    args = ap.parse_args()
    if args.unroll:
        model.UNROLL_GROUPS = True

    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod2x16x16", make_production_mesh(multi_pod=True)))

    failures = []
    for mesh_name, mesh in meshes:
        for kind, a, s in iter_cells(args.cells, args.arch, args.shape):
            try:
                row = run_cell(kind, a, s, mesh, mesh_name, args.micro,
                               args.krr_variant)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                    with open(args.out, "a") as fh:
                        fh.write(json.dumps(row) + "\n")
            except Exception:
                failures.append((mesh_name, a, s))
                print(f"[dryrun] FAILED {a}/{s}/{mesh_name}")
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILED cells: {failures}")
        return 1
    print("[dryrun] all requested cells lowered + compiled OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
