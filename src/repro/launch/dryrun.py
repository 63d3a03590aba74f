import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede every other import: jax fixes the device
# count at first initialization, and the production meshes below need 512
# placeholder host devices (2 pods x 16 x 16).

import argparse
import dataclasses
import json
import re
import time
from functools import partial
from math import comb
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch, all_archs, ArchSpec, ShapeCell
from repro.core.distributed import make_sharded_decomposition
from repro.distributed import sharding as shard_rules
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts")

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective in post-SPMD HLO text."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "-done(" in line:
            continue  # -done carries no new payload
        op = m.group(1)
        shapes = SHAPE_RE.findall(line)
        if not shapes:
            continue
        # first match = result; remaining inside parens = operands.  Sum the
        # operands (the data actually put on the wire); if operand shapes are
        # not printed fall back to the result shape.
        paren = line[m.end() - 1:]
        operands = SHAPE_RE.findall(paren)
        use = operands if operands else shapes[:1]
        out[op] = out.get(op, 0) + sum(_shape_bytes(d, s) for d, s in use)
    return out


# ---------------------------------------------------------------------------
# per-family lowering
# ---------------------------------------------------------------------------

def _abstract(fn, *args, **kwargs):
    return jax.eval_shape(fn, *args, **kwargs)


def _gnn_cfg_for_cell(spec: ArchSpec, cell: ShapeCell):
    cfg = spec.make_config()
    d = cell.dims
    node_level = cell.name != "molecule"
    n_out = d.get("n_classes", 1) if node_level else 1
    kw = dict(cfg.__dict__)
    kw["d_in"] = d["d_feat"]
    if "n_classes" in kw:
        kw["n_classes"] = n_out
        if "graph_level" in kw:
            kw["graph_level"] = not node_level
    if "n_out" in kw:
        kw["n_out"] = n_out
    return cfg.__class__(**kw)


def lower_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
               opt_overrides: Optional[Dict[str, Any]] = None):
    """Lower + compile one (arch x shape x mesh) cell; returns artifacts."""
    opt_cfg = adamw.AdamWConfig()
    key = jax.random.PRNGKey(0)
    dp = shard_rules.data_axes(mesh)

    if spec.family == "lm":
        cfg = spec.make_config()
        if opt_overrides:
            cfg = dataclasses.replace(cfg, **opt_overrides)
        params_a = _abstract(lambda k: T.init_params(k, cfg), key)
        rules = shard_rules.lm_param_rules(mesh, moe=cfg.moe is not None,
                                           moe_ep_data=cfg.moe_ep_data)
        p_spec = shard_rules.tree_specs(params_a, rules, mesh)
        p_sh = shard_rules.shard_tree(p_spec, mesh)
        specs = spec.input_specs(cfg, cell)
        if cell.kind == "train":
            opt_a = _abstract(adamw.init_state, params_a)
            o_sh = jax.tree.map(
                lambda l: NamedSharding(mesh, P()) if l.ndim == 0 else None,
                opt_a)
            # moments shard exactly like params
            o_sh = adamw.AdamWState(
                step=NamedSharding(mesh, P()),
                mu=jax.tree.map(lambda s: s, p_sh), nu=jax.tree.map(lambda s: s, p_sh))
            b_sh = {k: NamedSharding(mesh, P(dp, None))
                    for k in specs["batch"]}
            fn = partial(S.lm_train_step, cfg=cfg, opt_cfg=opt_cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None))
            lowered = jfn.lower(params_a, opt_a, specs["batch"])
        elif cell.kind == "prefill":
            b_sh = {"tokens": NamedSharding(mesh, P(dp, None))}
            fn = partial(S.lm_prefill_step, cfg=cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jfn.lower(params_a, specs["batch"])
        else:  # decode
            c_spec = shard_rules.lm_cache_spec(mesh, cfg.n_kv_heads,
                                               cfg.mla is not None)
            cache_a = specs["cache"]
            c_sh = tuple(
                NamedSharding(mesh, shard_rules.safe_spec(
                    a.shape, list(sp), mesh))
                for a, sp in zip(cache_a, c_spec))
            tok_sh = NamedSharding(mesh, P(dp, None))
            fn = partial(S.lm_decode_step, cfg=cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, tok_sh, c_sh,
                                            NamedSharding(mesh, P())),
                          out_shardings=(None, c_sh, None))
            lowered = jfn.lower(params_a, specs["tokens"], cache_a,
                                specs["cache_len"])

    elif spec.family == "gnn":
        cfg = _gnn_cfg_for_cell(spec, cell)
        arch = spec.arch_id
        mod = S._GNN[arch]
        params_a = _abstract(lambda k: mod.init_params(k, cfg), key)
        p_sh = shard_rules.shard_tree(
            shard_rules.tree_specs(params_a, shard_rules.gnn_rules(mesh),
                                   mesh), mesh)
        specs = spec.input_specs(cfg, cell)
        shard_nodes = cell.dims.get("n_nodes", 0) >= 1_000_000
        bspecs = shard_rules.gnn_batch_specs(mesh, shard_nodes)
        b_sh = {k: NamedSharding(
            mesh, shard_rules.safe_spec(v.shape, list(bspecs.get(
                k, P())) if bspecs.get(k) else [], mesh))
            for k, v in specs["batch"].items()}
        opt_a = _abstract(adamw.init_state, params_a)
        o_sh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                                mu=jax.tree.map(lambda s: s, p_sh),
                                nu=jax.tree.map(lambda s: s, p_sh))
        fn = partial(S.gnn_train_step, cfg=cfg, arch=arch,
                     n_graphs=specs["n_graphs"],
                     node_level=specs["node_level"], opt_cfg=opt_cfg)
        jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                      out_shardings=(p_sh, o_sh, None))
        lowered = jfn.lower(params_a, opt_a, specs["batch"])

    elif spec.family == "recsys":
        cfg = spec.make_config()
        from repro.models import din as DINM
        params_a = _abstract(lambda k: DINM.init_params(k, cfg), key)
        p_sh = shard_rules.shard_tree(
            shard_rules.tree_specs(params_a, shard_rules.din_rules(mesh),
                                   mesh), mesh)
        specs = spec.input_specs(cfg, cell)
        if cell.kind == "retrieval":
            all_ax = tuple(mesh.axis_names)
            b_sh = {k: NamedSharding(
                mesh, P(all_ax) if k.startswith("cand") else P())
                for k in specs["batch"]}
            fn = partial(S.din_retrieval_step, cfg=cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jfn.lower(params_a, specs["batch"])
        elif cell.kind == "serve":
            b_sh = {k: NamedSharding(
                mesh, shard_rules.safe_spec(
                    v.shape, [shard_rules.data_axes(mesh)], mesh))
                for k, v in specs["batch"].items()}
            fn = partial(S.din_serve_step, cfg=cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jfn.lower(params_a, specs["batch"])
        else:
            b_sh = {k: NamedSharding(
                mesh, shard_rules.safe_spec(
                    v.shape, [shard_rules.data_axes(mesh)], mesh))
                for k, v in specs["batch"].items()}
            opt_a = _abstract(adamw.init_state, params_a)
            o_sh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                                    mu=jax.tree.map(lambda s: s, p_sh),
                                    nu=jax.tree.map(lambda s: s, p_sh))
            fn = partial(S.din_train_step, cfg=cfg, opt_cfg=opt_cfg)
            jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None))
            lowered = jfn.lower(params_a, opt_a, specs["batch"])

    elif spec.family == "core":
        from repro.configs.nucleus import make_peel_schedule, max_rounds_bound
        d = cell.dims
        n_dev = int(np.prod(mesh.devices.shape))
        n_s_pad = -(-d["n_s"] // n_dev) * n_dev
        cfg = spec.make_config()
        cfg.update(opt_overrides or {})
        sched = make_peel_schedule(cfg, cell)
        fn, in_sh, out_sh = make_sharded_decomposition(
            mesh, d["n_r"], n_s_pad, d["C"], sched,
            max_rounds=max_rounds_bound(cfg, cell),
            compress=bool(cfg.get("compress", False)))
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jfn.lower(
            jax.ShapeDtypeStruct((n_s_pad, d["C"]), jnp.int32),
            jax.ShapeDtypeStruct((d["n_r"],), jnp.int32))
    else:
        raise ValueError(spec.family)

    return lowered


def _extrapolate_lm_cost(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                         opt_overrides: Optional[Dict[str, Any]] = None):
    """True LM cost totals via layer extrapolation.

    XLA's HloCostAnalysis counts scan bodies once, so the scanned production
    program under-reports per-layer work by ~n_layers.  Lowering the SAME
    model at n_layers=1 and n_layers=2 with cost_unroll=True (cheap: tiny
    HLO) gives exact per-layer deltas:  cost(L) = c1 + (L-1) * (c2 - c1).
    Exact for layer-uniform programs (all archs here); collectives dicts are
    extrapolated the same way per op type.
    """
    cfg_full = spec.make_config()
    if opt_overrides:
        cfg_full = dataclasses.replace(cfg_full, **opt_overrides)
    L = cfg_full.n_layers
    outs = []
    for nl in (1, 2):
        cfg_n = dataclasses.replace(cfg_full, n_layers=nl, cost_unroll=True)
        spec_n = dataclasses.replace(spec, make_config=lambda c=cfg_n: c)
        lowered = lower_cell(spec_n, cell, mesh, None)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        outs.append((cost, coll))
    (c1, k1), (c2, k2) = outs

    def extr(a, b):
        return a + (L - 1) * (b - a)

    cost_x = {key: extr(c1.get(key, 0.0) or 0.0, c2.get(key, 0.0) or 0.0)
              for key in ("flops", "bytes accessed", "transcendentals")}
    ops = set(k1) | set(k2)
    coll_x = {op: int(extr(k1.get(op, 0), k2.get(op, 0))) for op in ops}
    return cost_x, coll_x


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             opt_overrides: Optional[Dict[str, Any]] = None,
             tag: str = "") -> Dict[str, Any]:
    spec = get_arch(arch_id)
    cell = spec.shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "tag": tag,
    }
    if cell.skip_reason:
        result["status"] = "skipped"
        result["skip_reason"] = cell.skip_reason
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    # mesh context: required for PartitionSpec-based sharding constraints
    # inside the models (jax.lax.with_sharding_constraint)
    with jax.set_mesh(mesh):
        lowered = lower_cell(spec, cell, mesh, opt_overrides)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    cost_x = coll_x = None
    if spec.family == "lm":
        try:
            with jax.set_mesh(mesh):
                cost_x, coll_x = _extrapolate_lm_cost(spec, cell, mesh,
                                                      opt_overrides)
        except Exception as e:
            result["extrapolation_error"] = repr(e)[:500]
    result.update({
        "status": "ok",
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals")
                 if k in cost},
        "collectives": coll,
        "collective_bytes_total": int(sum(coll.values())),
        "n_devices": int(np.prod(mesh.devices.shape)),
    })
    if cost_x is not None:
        result["cost_extrapolated"] = cost_x
        result["collectives_extrapolated"] = coll_x
        result["collective_bytes_total_extrapolated"] = int(
            sum(coll_x.values()))
    return result


def artifact_path(arch_id: str, shape_name: str, multi_pod: bool,
                  tag: str = "") -> str:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"-{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR,
                        f"{arch_id}--{shape_name}--{mesh_name}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=False)
    ap.add_argument("--shape", required=False)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--tag", default="")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.list:
        for aid, spec in sorted(all_archs().items()):
            for c in spec.shapes:
                skip = f"  [skip: {bool(c.skip_reason)}]" if c.skip_reason else ""
                print(f"{aid:24s} {c.name:16s} {c.kind}{skip}")
        return

    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    archs = [args.arch] if args.arch else sorted(all_archs())
    for aid in archs:
        spec = get_arch(aid)
        shapes = ([args.shape] if args.shape
                  else [c.name for c in spec.shapes])
        for sname in shapes:
            for mp in meshes[args.mesh]:
                path = artifact_path(aid, sname, mp, args.tag)
                if os.path.exists(path) and not args.force:
                    # error artifacts are retried, not treated as cached:
                    # an unreadable/failed record must never mask a cell.
                    try:
                        with open(path) as f:
                            prev_status = json.load(f).get("status")
                    except (OSError, ValueError):
                        prev_status = None
                    if prev_status in ("ok", "skipped"):
                        print(f"SKIP (cached) {path}")
                        continue
                    print(f"RERUN (cached status={prev_status}) {path}")
                print(f"== {aid} x {sname} x "
                      f"{'multi' if mp else 'single'} ==", flush=True)
                try:
                    res = run_cell(aid, sname, mp, tag=args.tag)
                except Exception as e:  # record failures as artifacts too
                    res = {"arch": aid, "shape": sname,
                           "mesh": "pod2x16x16" if mp else "pod16x16",
                           "status": "error", "error": repr(e)[:2000],
                           "tag": args.tag}
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
                print(json.dumps(
                    {k: res.get(k) for k in
                     ("status", "compile_s", "collective_bytes_total",
                      "error")}, indent=None), flush=True)


if __name__ == "__main__":
    main()
