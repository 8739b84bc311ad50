import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove every (architecture × input shape × mesh) cell
lowers AND compiles under SPMD, then extract memory / cost / collective
statistics for the roofline analysis.

The two lines above must run before any jax import — jax locks the device
count at first init.  Do NOT replicate them in conftest.py: tests and
benchmarks are supposed to see one real CPU device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models import transformer
from repro.models.config import SHAPES, ModelConfig, supported_shapes
from repro.roofline import analysis
from repro.sharding import partition
from repro.train import step as step_lib
from repro.launch.mesh import make_production_mesh


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; zero device allocation)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape_name: str, mesh):
    """Abstract model inputs for one (arch, shape) cell.

    train:   {"tokens","labels"} (B, S) int32 (+ "frames" for enc-dec)
    decode:  (token (B,1), pos ()) plus the KV/recurrent cache.
    """
    shp = SHAPES[shape_name]
    B, S = shp.global_batch, shp.seq_len
    bspec = partition.batch_pspec(mesh, B)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32,
                               sharding=NamedSharding(mesh, bspec))
    if shp.kind == "train":
        out = {"tokens": tok, "labels": tok}
        if cfg.is_enc_dec:
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, bspec))
        if cfg.frontend == "vq_tokens":
            out["modality_mask"] = tok
        return out
    if shp.kind == "prefill":
        out = {"tokens": tok}
        if cfg.is_enc_dec:
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, bspec))
        return out
    if shp.kind == "decode":
        one = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                   sharding=NamedSharding(mesh, bspec))
        return {"token": one,
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    raise ValueError(shp.kind)


def cache_specs(cfg: ModelConfig, shape_name: str, mesh):
    shp = SHAPES[shape_name]
    B, S = shp.global_batch, shp.seq_len

    def build():
        c, _ = transformer.init_cache(cfg, B, S)
        return c
    shapes = jax.eval_shape(build)
    box = {}

    def build2():
        c, sp = transformer.init_cache(cfg, B, S)
        box["sp"] = sp
        return c
    jax.eval_shape(build2)
    shardings = partition.tree_shardings(box["sp"], shapes, mesh)
    return shapes, shardings


# --------------------------------------------------------------------------
# one cell
# --------------------------------------------------------------------------

def _lower_one(cfg, shp, mesh):
    max_seq = shp.seq_len if cfg.pos == "learned" else 0
    rules = None
    if shp.kind == "decode" and cfg.serve_weights_stationary:
        rules = partition.serve_rules(mesh)
    state_sh, state_shapes = step_lib.state_shardings(cfg, mesh, max_seq,
                                                      rules)
    ins = input_specs(cfg, shp.name, mesh)
    if shp.kind == "train":
        fn = step_lib.make_train_step(cfg, mesh)
        batch_sh = {k: v.sharding for k, v in ins.items()
                    if k != "modality_mask"}
        batch = {k: v for k, v in ins.items() if k != "modality_mask"}
        jfn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                      out_shardings=None, donate_argnums=(0,))
        with jax.set_mesh(mesh):
            return jfn.lower(state_shapes, batch)
    cshapes, csh = cache_specs(cfg, shp.name, mesh)
    logit_sh = NamedSharding(
        mesh, partition.batch_pspec(mesh, SHAPES[shp.name].global_batch))
    if shp.kind == "prefill":
        fn = step_lib.make_prefill(cfg, mesh)
        frames = ins.get("frames")
        jfn = jax.jit(fn, in_shardings=(
            state_sh["params"], ins["tokens"].sharding, csh)
            + ((frames.sharding,) if frames is not None else ()),
            out_shardings=(logit_sh, csh), donate_argnums=(2,))
        args = (state_shapes["params"], ins["tokens"], cshapes) \
            + ((frames,) if frames is not None else ())
        with jax.set_mesh(mesh):
            return jfn.lower(*args)
    fn = step_lib.make_serve_step(cfg, mesh)
    jfn = jax.jit(fn, in_shardings=(state_sh["params"], csh,
                                    ins["token"].sharding, None),
                  out_shardings=(logit_sh, csh), donate_argnums=(1,))
    with jax.set_mesh(mesh):
        return jfn.lower(state_shapes["params"], cshapes, ins["token"],
                         jax.ShapeDtypeStruct((), jnp.int32))


def lower_cell(arch: str, shape_name: str, mesh, *, overrides=None,
               probe=True):
    """Lower + compile one (arch, shape, mesh) cell.

    Three compiles: the full scanned module (sharding/memory proof) and two
    unrolled depth probes (1 and 2 pattern-periods) whose cost_analysis is
    depth-extrapolated — XLA counts while-loop bodies once, so the scanned
    module's numbers cannot be used directly (see roofline/analysis.py).
    """
    import dataclasses
    cfg = configs.get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shp = SHAPES[shape_name]

    t0 = time.time()
    lowered = _lower_one(cfg, shp, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    probes = None
    if probe:
        pstats = []
        for k in (1, 2):
            pcfg = dataclasses.replace(
                cfg, n_layers=k * cfg.period,
                enc_layers=k if cfg.is_enc_dec else 0,
                scan_layers=False, microbatches=1, attn_chunk=0)
            pl = _lower_one(pcfg, shp, mesh)
            pstats.append(analysis.raw_stats(pl.compile()))
        probes = tuple(pstats)

    return analysis.collect(cfg, shp, mesh, lowered, compiled,
                            t_lower=t_lower, t_compile=t_compile,
                            probes=probes)


def run_cells(archs, shapes, meshes, out_dir=None, overrides=None,
              tag=""):
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
        for arch in archs:
            cfg = configs.get_config(arch)
            names = [s.name for s in supported_shapes(cfg)]
            for shape_name in shapes:
                if shape_name not in names:
                    print(f"SKIP {arch} {shape_name} ({mesh_name}): "
                          "full-attention arch, long-context infeasible "
                          "(DESIGN.md §Arch-applicability)")
                    continue
                key = f"{arch}|{shape_name}|{mesh_name}"
                try:
                    st = lower_cell(arch, shape_name, mesh,
                                    overrides=overrides)
                    st["cell"] = key
                    st["tag"] = tag
                    results.append(st)
                    print(f"OK   {key}: compile={st['t_compile']:.1f}s "
                          f"flops={st['flops']:.3e} "
                          f"bytes={st['bytes_accessed']:.3e} "
                          f"coll={st['collective_bytes']:.3e} "
                          f"mem/dev={st['bytes_per_device']/1e9:.2f}GB")
                except Exception as e:
                    print(f"FAIL {key}: {e}")
                    traceback.print_exc()
                    results.append({"cell": key, "error": str(e),
                                    "tag": tag})
                if out_dir:
                    import pathlib
                    p = pathlib.Path(out_dir)
                    p.mkdir(parents=True, exist_ok=True)
                    fname = key.replace("|", "_").replace(".", "_")
                    if tag:
                        fname += f"_{tag}"
                    (p / f"{fname}.json").write_text(
                        json.dumps(results[-1], indent=1, default=str))
                sys.stdout.flush()
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimb lever)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        import ast
        try:
            v = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            pass
        overrides[k] = v

    archs = configs.list_archs() if args.all or not args.arch \
        else [args.arch]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    results = run_cells(archs, shapes, meshes, out_dir=args.out,
                        overrides=overrides or None, tag=args.tag)
    ok = sum(1 for r in results if "error" not in r)
    print(f"\n{ok}/{len(results)} cells compiled")
    return 0 if ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
