import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * build the model + parameter/optimizer/cache partition specs,
  * jax.jit(step).lower(**ShapeDtypeStructs).compile()   (no allocation),
  * record memory_analysis(), cost_analysis(), and the collective schedule
    parsed from the partitioned HLO -> launch/out/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--resume]
  python -m repro.launch.dryrun --arch veloann --shape serve_batch
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import hlo_analysis, mesh as mesh_mod, shapes as shapes_mod
from repro.models import model as Mod
from repro.models import sharding as Sh
from repro.train import optimizer as Opt
from repro.train import train_step as TS

OUT_DIR = os.path.join(os.path.dirname(__file__), "out", "dryrun")


# ----------------------------------------------------------- cache shardings


def cache_pspecs(model, caches_shape, dp, seq_len):
    """Partition specs for decode caches: batch over dp when divisible, else
    the KV sequence axis (long_500k), else the head/channel axis."""
    dp_size = 1
    mesh = Sh._ACTIVE["mesh"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in dp:
        dp_size *= sizes[a]

    def spec(path, leaf):
        shape = leaf.shape
        names = [str(getattr(e, "key", getattr(e, "idx", e))) for e in path]
        stacked = "groups" in names
        off = 1 if stacked else 0
        field = names[-1]
        B = shape[off]
        out = [None] * len(shape)
        if field in ("k", "v", "ck", "cv"):
            S = shape[off + 2]
            if B % dp_size == 0 and B >= dp_size:
                out[off] = dp
            elif S % dp_size == 0:
                out[off + 2] = dp           # long-context: shard the sequence
            # KV heads never divide the 16-way model axis (kv in {1,4,8,12}),
            # so the model axis shards the SEQUENCE instead: decode attention
            # is a seq-reduction, XLA inserts the softmax partials' psum, and
            # per-device cache drops 16x (yi decode_32k 48 GiB -> ~3 GiB).
            if S % sizes.get("model", 1) == 0 and out[off + 2] is None:
                out[off + 2] = "model"
        elif field in ("conv", "ssm"):
            if B % dp_size == 0 and B >= dp_size:
                out[off] = dp
            elif shape[off + (2 if field == "conv" else 1)] % sizes.get("model", 1) == 0:
                out[off + (2 if field == "conv" else 1)] = "model"
        elif field in ("tshift", "wkv", "cshift"):
            if B % dp_size == 0 and B >= dp_size:
                out[off] = dp
            elif field == "wkv" and shape[off + 1] % sizes.get("model", 1) == 0:
                out[off + 1] = "model"
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, caches_shape)


# ------------------------------------------------------------------ the cell


def run_lm_cell(arch: str, shape: str, multi_pod: bool, microbatches: int | None,
                opt_name: str = "adamw", ce_chunk: int = 256) -> dict:
    cfg = configs.get(arch)
    reason = shapes_mod.skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    dp = mesh_mod.dp_axes(mesh)
    ndev = mesh_mod.n_devices(mesh)
    Sh.set_active_mesh(mesh, dp_axes=dp)

    model = Mod.build(cfg)
    cell = shapes_mod.input_specs(cfg, model, shape)

    params_shape = Mod.params_specs(model)
    pspecs = Sh.param_pspecs(params_shape)
    pspecs, degraded = Sh.check_divisible(params_shape, pspecs, mesh)
    psh = Sh.named(mesh, pspecs)

    t0 = time.time()
    if cell.kind == "train":
        opt_init, _ = Opt.OPTIMIZERS[opt_name]
        opt_shape = jax.eval_shape(opt_init, params_shape)
        ospecs = jax.tree.map(
            lambda leaf: P(), opt_shape
        )
        # moments mirror their parameter's sharding
        ospecs = {
            "m": pspecs, "v": pspecs,
            "step": P(),
        } if opt_name == "adamw" else ospecs
        osh = Sh.named(mesh, ospecs)

        batch_sh = {
            k: NamedSharding(mesh, P(dp, *([None] * (len(v.shape) - 1))))
            for k, v in cell.batch.items()
        }
        mb = microbatches or max(1, cell.global_batch // (ndev // dict(zip(mesh.axis_names, mesh.devices.shape))["model"]))

        def batch_shardings(ndim):
            return NamedSharding(mesh, P(None, dp, *([None] * (ndim - 2))))

        step_fn = TS.make_train_step(
            model, opt_name=opt_name, microbatches=mb, ce_chunk=ce_chunk,
            grad_pspecs=psh, batch_shardings=batch_shardings,
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(psh, osh, batch_sh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_shape, opt_shape, cell.batch)
    elif cell.kind == "prefill":
        batch_sh = {
            k: NamedSharding(mesh, P(dp, *([None] * (len(v.shape) - 1))))
            for k, v in cell.batch.items()
        }

        def prefill_fn(params, batch):
            return Mod.prefill(model, params, batch)

        jitted = jax.jit(
            prefill_fn,
            in_shardings=(psh, batch_sh),
            out_shardings=None,
        )
        lowered = jitted.lower(params_shape, cell.batch)
    else:  # decode
        cspecs = cache_pspecs(model, cell.caches, dp, cell.seq_len)
        csh = Sh.named(mesh, cspecs)
        B = cell.tokens.shape[0]
        tok_sh = NamedSharding(mesh, P(dp) if B % ndev == 0 or B >= 16 else P())

        def decode_fn(params, caches, tokens, pos):
            return Mod.decode_step(model, params, caches, tokens, pos)

        jitted = jax.jit(
            decode_fn,
            in_shardings=(psh, csh, tok_sh, NamedSharding(mesh, P())),
            out_shardings=(None, csh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(
            params_shape, cell.caches, cell.tokens,
            jax.ShapeDtypeStruct((), jnp.int32),
        )
    lower_s = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    out = _collect(compiled, arch, shape, multi_pod, ndev, cfg)
    out.update(lower_s=round(lower_s, 1), compile_s=round(compile_s, 1),
               degraded_shardings=degraded[:20], kind=cell.kind,
               seq_len=cell.seq_len, global_batch=cell.global_batch)
    Sh.clear_active_mesh()
    return out


def run_veloann_cell(multi_pod: bool) -> dict:
    from repro.velo import dist_search
    from repro.velo.index import synthetic_specs

    vcfg = configs.get("veloann")
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    ndev = mesh_mod.n_devices(mesh)
    axes = mesh.axis_names

    per_shard = vcfg.corpus_size // ndev
    # sharded DeviceIndex: arrays carry a +1 sentinel row PER SHARD, so the
    # global array has ndev sentinel rows: n_global = ndev * (per_shard + 1)
    n_global = ndev * (per_shard + 1) - 1  # synthetic_specs adds the last +1
    idx = synthetic_specs(n_global, vcfg.dim, vcfg.R)
    offsets = jax.ShapeDtypeStruct((ndev,), jnp.int32)
    queries = jax.ShapeDtypeStruct((vcfg.query_batch, vcfg.dim), jnp.float32)

    # the CPU target has no Pallas: lower each shard's stage-1 GEMM through
    # the jnp path (interpret-mode Pallas would unroll its grid into the HLO)
    search = dist_search.make_distributed_search(
        mesh, axes, mode=vcfg.mode, L=vcfg.rerank, k=vcfg.k, use_kernel=False,
    )

    t0 = time.time()
    lowered = jax.jit(search).lower(idx, offsets, queries)
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    out = _collect(compiled, "veloann", "serve_batch", multi_pod, ndev, None)
    out.update(lower_s=round(lower_s, 1), compile_s=round(compile_s, 1),
               kind="serve", seq_len=0, global_batch=vcfg.query_batch)
    return out


def _collect(compiled, arch, shape, multi_pod, ndev, cfg) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    xla_flops = float(ca.get("flops", 0.0)) if isinstance(ca, dict) else 0.0
    xla_bytes = float(ca.get("bytes accessed", 0.0)) if isinstance(ca, dict) else 0.0
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo, ndev)
    cost = hlo_analysis.cost_stats(hlo, ndev)

    rec = {
        "arch": arch,
        "shape": shape,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_devices": ndev,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "cost": {
            # trip-count-corrected (hlo_analysis); XLA's raw numbers kept for
            # reference (they count while bodies once — see hlo_analysis doc)
            "flops_per_device": cost["flops_per_device"],
            "bytes_accessed_per_device": cost["bytes_per_device"],
            "xla_flops_per_device_raw": xla_flops,
            "xla_bytes_per_device_raw": xla_bytes,
        },
        "collectives": coll,
        "hlo_chars": len(hlo),
    }
    if cfg is not None:
        rec["model"] = {
            "params": cfg.params_count(),
            "active_params": cfg.active_params_count(),
        }
    return rec


def cell_path(arch, shape, multi_pod):
    pod = "pod2" if multi_pod else "pod1"
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{pod}.json")


def run_and_save(arch, shape, multi_pod, **kw):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = cell_path(arch, shape, multi_pod)
    try:
        if arch == "veloann":
            rec = run_veloann_cell(multi_pod)
        else:
            rec = run_lm_cell(arch, shape, multi_pod, kw.get("microbatches"))
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec = {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        mem = rec["memory"]["peak_estimate_bytes"] / 2**30
        extra = f" mem/dev={mem:.2f}GiB flops/dev={rec['cost']['flops_per_device']:.3g} compile={rec.get('compile_s')}s"
    print(f"[dryrun] {arch} {shape} {'pod2' if multi_pod else 'pod1'}: {status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    if args.all:
        for arch in configs.all_archs():
            for shape in shapes_mod.SHAPES:
                for mp in meshes:
                    cells.append((arch, shape, mp))
        for mp in meshes:
            cells.append(("veloann", "serve_batch", mp))
    else:
        assert args.arch
        shapes = [args.shape] if args.shape else list(shapes_mod.SHAPES)
        if args.arch == "veloann":
            shapes = ["serve_batch"]
        for shape in shapes:
            for mp in meshes:
                cells.append((args.arch, shape, mp))

    for arch, shape, mp in cells:
        if args.resume and os.path.exists(cell_path(arch, shape, mp)):
            with open(cell_path(arch, shape, mp)) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    continue
        run_and_save(arch, shape, mp, microbatches=args.microbatches)


if __name__ == "__main__":
    main()
