import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), record memory/cost analysis and
the per-device collective schedule for §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod] [--jobs k/N]

Results land in results/dryrun/<arch>__<shape>__<mesh>[__variant].json.
"""  # noqa: E402

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import cells, get_config
from repro.launch.hlo_analysis import (collective_bytes, model_flops,
                                       roofline_terms)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models.lm import Model
from repro.models.params import ShardPlan, logical_axes
from repro.parallel.sharding import (batch_logical, cache_logical,
                                     make_act_sharder,
                                     spec_for_logical, tree_shardings)
from repro.training.train_step import build_train_step, train_state_shapes

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

VARIANTS = {
    # §Perf hillclimb variants: cfg field overrides + Model opts overrides
    "base": {},
    "nosp": {"opts": {"sp": False}},
    "remat_dots": {"cfg": {"remat": "dots"}},
    "remat_full": {"cfg": {"remat": "full"}},
    "remat_none": {"cfg": {"remat": "none"}},
    "ce2048": {"opts": {"ce_chunk": 2048}},
    "ce512": {"opts": {"ce_chunk": 512}},
    "qc2048": {"opts": {"q_chunk": 2048, "kv_chunk": 2048}},
    "blockskip": {"opts": {"block_skip": True}},          # analysis-mode only
    "cf1": {"cfg": {"capacity_factor": 1.0}},
    "ssm256": {"opts": {"ssm_chunk": 256}},
    "ssm64": {"opts": {"ssm_chunk": 64}},
    "ssm512": {"opts": {"ssm_chunk": 512}},
    "ssm128": {"opts": {"ssm_chunk": 128}},
    "replica": {"opts": {"strategy": "replica"}},
    "ssd16": {"opts": {"ssd_dtype": "bfloat16"}},
    # composed hillclimb variants
    "llama4_opt": {"cfg": {"capacity_factor": 1.0},
                   "opts": {"block_skip": True}},
    "mamba2_opt": {"opts": {"strategy": "replica", "ssd_dtype": "bfloat16",
                            "ssm_chunk": 64}},
    "mamba2_opt2": {"opts": {"strategy": "replica", "ssd_dtype": "bfloat16",
                             "ssm_chunk": 256}},
    "ssd16_256": {"opts": {"ssd_dtype": "bfloat16", "ssm_chunk": 256}},
}


def _apply_variant(cfg, variant: str):
    if variant not in VARIANTS:
        raise KeyError(f"unknown variant {variant!r}; known: {sorted(VARIANTS)}")
    v = VARIANTS[variant]
    if "cfg" in v:
        cfg = dataclasses.replace(cfg, **v["cfg"])
    return cfg, dict(v.get("opts", {}))


def _analysis_opts(shape, base_opts):
    """Unrolled-mode opts: python loops so HLO cost analysis sees every block;
    chunk sizes bumped so the unrolled HLO stays compilable."""
    o = dict(base_opts)
    o["unroll"] = True
    if shape.kind != "decode":
        o.setdefault("q_chunk", max(1024, shape.seq_len // 8))
        o.setdefault("kv_chunk", max(1024, shape.seq_len // 8))
        o.setdefault("ce_chunk", max(1024, shape.seq_len // 4))
    return o


def _depth_reduced(cfg, groups: int):
    """Same-family config with `groups` scan groups (layer-linear cost probe)."""
    from repro.models.params import resolve_dims as _rd
    from repro.models.params import ShardPlan as _SP
    gl = _rd(cfg, _SP()).group_layers
    upd = dict(n_layers=groups * gl)
    if cfg.enc_layers:
        full_groups = cfg.n_layers // gl
        upd["enc_layers"] = max(1, round(cfg.enc_layers * groups / full_groups))
    return dataclasses.replace(cfg, **upd)


def build_cell(arch: str, shape_name: str, mesh, variant: str = "base",
               analysis: bool = False, depth_groups: int = 0):
    """Returns (jitted_fn, args, meta, cfg, shape) ready to lower."""
    cfg = get_config(arch)
    cfg, opts = _apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    if depth_groups:
        cfg = _depth_reduced(cfg, depth_groups)
    if analysis:
        opts = _analysis_opts(shape, opts)
    # parallelism strategy: pure-FSDP for training (no TP activation
    # all-reduces; ZeRO-3 weights), TP for serving (KV-cache sharding)
    from repro.parallel.sharding import STRATEGIES
    strategy = opts.pop("strategy", "fsdp" if shape.kind == "train" else "tp")
    rules = STRATEGIES[strategy]
    tp = mesh.shape["model"] if strategy == "tp" else 1
    fsdp = mesh.shape["data"]
    dp = mesh.shape.get("pod", 1)
    plan = ShardPlan(tp=tp, fsdp=fsdp, dp=dp, vocab_multiple=256)
    model = Model(cfg, plan, mesh=mesh,
                  act_shard=make_act_sharder(mesh, rules), opts=opts)

    batch_sds = input_specs(cfg, shape)
    blog = batch_logical(cfg, shape.kind)
    bsh = {k: NamedSharding(mesh, spec_for_logical(blog[k], v.shape, mesh, rules))
           for k, v in batch_sds.items()}

    lax_tree = logical_axes(cfg, plan)
    psh = tree_shardings(lax_tree, model.param_shapes(), mesh, rules)

    if shape.kind == "train":
        state_sds = train_state_shapes(model)
        zrules = dict(rules)              # ZeRO-1 across pods for opt state
        zrules["fsdp"] = rules["fsdp+"]
        mvsh = jax.tree.map(
            lambda lg, s: NamedSharding(mesh, spec_for_logical(lg, s.shape, mesh,
                                                               zrules)),
            lax_tree, state_sds["opt"]["m"],
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        state_sh = {"params": psh,
                    "opt": {"m": mvsh, "v": mvsh,
                            "step": NamedSharding(mesh, P())}}
        step = build_train_step(model)
        fn = jax.jit(step, in_shardings=(state_sh, bsh),
                     out_shardings=(state_sh, None))
        args = (state_sds, batch_sds)
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        def prefill_fn(params, batch):
            return model.prefill(params, batch, cache_len=shape.seq_len)
        fn = jax.jit(prefill_fn, in_shardings=(psh, bsh))
        args = (model.param_shapes(), batch_sds)
        tokens = shape.global_batch * shape.seq_len
    else:  # decode
        cache_sds = model.init_cache(shape.global_batch, shape.seq_len,
                                     abstract=True)
        clog = cache_logical(cfg, long_context=shape.long_context)
        csh = {k: NamedSharding(
            mesh, spec_for_logical(clog[k], cache_sds[k].shape, mesh, rules))
            for k in cache_sds}
        scalar_sh = NamedSharding(mesh, P())

        def decode_fn(params, cache, cur_len, token):
            return model.decode(params, cache, cur_len, token)

        fn = jax.jit(decode_fn,
                     in_shardings=(psh, csh, scalar_sh, bsh["token"]),
                     donate_argnums=(1,))
        args = (model.param_shapes(), cache_sds,
                jax.ShapeDtypeStruct((), jnp.int32), batch_sds["token"])
        tokens = shape.global_batch
    meta = dict(arch=arch, shape=shape_name, kind=shape.kind, tokens=tokens,
                n_params=cfg.n_params(), n_active=cfg.n_active_params())
    return fn, args, meta, cfg, shape


def run_rnsg_cell(multi_pod: bool, variant: str = "base", save: bool = True):
    """Dry-run of the paper's own system at production scale: a 16.8M-vector
    corpus (65536 per 'data' shard × 16, d=128, m=32) served with the
    range-partitioned shard_map search.  Variant 'qshard' additionally shards
    the query batch over the 'model' axis (every model rank serves its own
    1/16 slice — 16× throughput at identical per-query work)."""
    from repro.core.beam import beam_search_batch
    from repro.search import (merge_topk, rank_interval_jax, remap_ids_jax,
                              select_entry)

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = 512 if multi_pod else 256
    data_sz = mesh.shape["data"] * mesh.shape.get("pod", 1)
    ns, d, m, logn = 65536, 128, 32, 17
    nq, k, ef = 1024, 10, 64
    qshard = variant == "qshard"

    def body(vecs, nbrs, attrs, rmq, dist_c, order, qv, ranges):
        vecs, nbrs, attrs = vecs[0], nbrs[0], attrs[0]
        rmq, dist_c, order = rmq[0], dist_c[0], order[0]
        lo, hi = rank_interval_jax(attrs, ranges)
        entry = select_entry(rmq, dist_c, lo, hi, ns)
        ids, dists, _ = beam_search_batch(vecs, nbrs, qv, lo, hi, entry,
                                          k=k, ef=ef)
        orig = remap_ids_jax(order, ids)
        ids_g = jax.lax.all_gather(orig, "data")
        d_g = jax.lax.all_gather(jnp.where(ids >= 0, dists, jnp.inf), "data")
        return merge_topk(ids_g, d_g, k)

    shard = P(("pod", "data") if multi_pod else "data")
    q_spec = P("model") if qshard else P()
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(shard,) * 6 + (q_spec, q_spec),
                       out_specs=(q_spec, q_spec), check_vma=False)
    S = data_sz
    args = (jax.ShapeDtypeStruct((S, ns, d), jnp.float32),
            jax.ShapeDtypeStruct((S, ns, m), jnp.int32),
            jax.ShapeDtypeStruct((S, ns), jnp.float32),
            jax.ShapeDtypeStruct((S, logn, ns), jnp.int32),
            jax.ShapeDtypeStruct((S, ns), jnp.float32),
            jax.ShapeDtypeStruct((S, ns), jnp.int32),
            jax.ShapeDtypeStruct((nq, d), jnp.float32),
            jax.ShapeDtypeStruct((nq, 2), jnp.float32))
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    try:
        ma = compiled.memory_analysis()
        mem = dict(temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
                   argument_bytes=getattr(ma, "argument_size_in_bytes", 0))
    except Exception as e:
        mem = {"error": str(e)}
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(flops_dev, bytes_dev, float(coll["total"]), chips)
    rec = dict(arch="rnsg-serve", shape=f"q{nq}_n{S*ns}", mesh=mesh_name,
               chips=chips, variant=variant, compile_s=round(t_compile, 2),
               cost=dict(flops_per_device=flops_dev, bytes_per_device=bytes_dev),
               memory=mem, collectives=coll, roofline=terms,
               note="beam while-loop body counted once by HLO cost analysis; "
                    "use per-query dist-eval stats from benchmarks for "
                    "absolute work accounting")
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        tag = f"rnsg-serve__q{nq}__{mesh_name}" + \
              ("" if variant == "base" else f"__{variant}")
        (RESULTS / f"{tag}.json").write_text(json.dumps(rec, indent=1,
                                                        default=float))
    print(f"[dryrun] rnsg-serve {mesh_name} {variant}: compile={t_compile:.1f}s"
          f" flops/dev={flops_dev:.2e} coll={coll['total']/2**20:.1f}MiB/dev"
          f" args={mem.get('argument_bytes', 0)/2**30:.1f}GiB")
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, variant: str = "base",
             save: bool = True, verbose: bool = True, analysis: bool = True):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}" + \
          ("" if variant == "base" else f"__{variant}")
    out_path = RESULTS / f"{tag}.json"
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if not shape.applicable(cfg):
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name, variant=variant,
                   skipped=f"long_500k requires sub-quadratic attention "
                           f"({cfg.family} is full-attention)")
        if save:
            out_path.parent.mkdir(parents=True, exist_ok=True)
            out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256

    def compile_once(analysis: bool, depth_groups: int = 0):
        t0 = time.perf_counter()
        fn, args, meta, cfg, shape = build_cell(arch, shape_name, mesh, variant,
                                                analysis=analysis,
                                                depth_groups=depth_groups)
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
        return compiled, t_lower, t_compile, meta, cfg, shape

    # 1) production artifact (scanned layers): proves compile, gives memory
    compiled_p, t_lower, t_compile, meta, cfg, shape = compile_once(False)
    try:
        ma = compiled_p.memory_analysis()
        mem = dict(argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
                   output_bytes=getattr(ma, "output_size_in_bytes", 0),
                   temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
                   peak_bytes=getattr(ma, "peak_memory_in_bytes", 0),
                   code_bytes=getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    if not analysis:   # multi-pod pass: prove the pod axis shards, skip probes
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
                   variant=variant, meta=meta, lower_s=round(t_lower, 2),
                   compile_s=round(t_compile, 2), memory=mem)
        if save:
            out_path.parent.mkdir(parents=True, exist_ok=True)
            out_path.write_text(json.dumps(rec, indent=1, default=float))
        if verbose:
            print(f"[dryrun] {tag}: compile={t_compile:.1f}s (production only) "
                  f"temp={mem.get('temp_bytes', 0)/2**30:.2f}GiB/dev")
        return rec

    # 2) analysis artifacts: fully unrolled (python-loop) models at depths
    #    g=1 and g=2; per-group cost increments are exact because groups are
    #    homogeneous, so cost(G) = cost(1) + (cost(2) - cost(1))·(G - 1).
    #    (Needed because XLA's HloCostAnalysis counts a while body once.)
    from repro.models.params import resolve_dims as _rd
    from repro.models.params import ShardPlan as _SP
    g_full = get_config(arch).n_layers // _rd(get_config(arch), _SP()).group_layers

    def probe(g):
        compiled, _, tc, *_ = compile_once(True, depth_groups=g)
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        return (float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)),
                float(coll["total"]), coll, tc)

    f1, b1, c1, coll1, tc1 = probe(1)
    if g_full > 1:
        f2, b2, c2, coll2, tc2 = probe(2)
    else:
        f2, b2, c2, coll2, tc2 = f1, b1, c1, coll1, 0.0
    t_compile_a = tc1 + tc2
    flops_dev = f1 + (f2 - f1) * (g_full - 1)
    bytes_dev = b1 + (b2 - b1) * (g_full - 1)
    coll_dev = c1 + (c2 - c1) * (g_full - 1)
    coll = {k: coll1[k] + (coll2[k] - coll1[k]) * (g_full - 1)
            for k in coll1}
    terms = roofline_terms(flops_dev, bytes_dev, coll_dev, chips)
    mf = model_flops(cfg, shape, meta["n_active"])
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
               variant=variant, meta=meta,
               lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
               compile_analysis_s=round(t_compile_a, 2),
               cost=dict(flops_per_device=flops_dev,
                         bytes_per_device=bytes_dev),
               memory=mem, collectives=coll, roofline=terms,
               model_flops=mf,
               useful_ratio=(mf / terms["flops_global"]
                             if terms["flops_global"] else None))
    if save:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1, default=float))
    if verbose:
        print(f"[dryrun] {tag}: compile={t_compile:.1f}s "
              f"dom={terms['dominant']} t={terms['t_bound']*1e3:.2f}ms "
              f"useful={rec['useful_ratio'] and round(rec['useful_ratio'],3)} "
              f"coll={coll['total']/2**20:.1f}MiB/dev "
              f"temp={mem.get('temp_bytes',0)/2**30:.2f}GiB/dev")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--jobs", default="",
                    help="k/N: process cells with index %% N == k (sharded driver)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-analysis", action="store_true",
                    help="production compile only (multi-pod pass)")
    args = ap.parse_args()

    if args.arch == "rnsg-serve":
        run_rnsg_cell(args.multipod, args.variant)
        return

    todo = []
    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multipod]
        for mp in meshes:
            for arch, shape, skip in cells(include_inapplicable=True):
                todo.append((arch, shape, mp))
    else:
        todo = [(args.arch, args.shape, args.multipod)]
    if args.jobs:
        k, n = map(int, args.jobs.split("/"))
        todo = [t for i, t in enumerate(todo) if i % n == k]

    failures = []
    for arch, shape, mp in todo:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        tag = f"{arch}__{shape}__{mesh_name}" + \
              ("" if args.variant == "base" else f"__{args.variant}")
        out_path = RESULTS / f"{tag}.json"
        if out_path.exists() and not args.force:
            print(f"[dryrun] {tag}: cached")
            continue
        try:
            run_cell(arch, shape, mp, args.variant,
                     analysis=not args.no_analysis and not mp)
        except Exception:
            failures.append(tag)
            print(f"[dryrun] {tag}: FAILED")
            traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
