import os

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell, prove it fits (memory_analysis), and extract the roofline raw
terms (cost_analysis + HLO collective traffic).

Because XLA cost analysis counts a while-loop body ONCE, the scan-over-
layers/microbatch costs are measured with *unrolled probes*: the same step
function at depth 1 and 2 layer-groups (python-unrolled), same mesh and
shardings; the per-group cost is the difference, and the full-depth cost
is  A + n_groups * B  (x n_micro for the gradient-accumulation scan, plus
an analytic optimizer term).  The full-depth scan version is still
compiled for real — that is the artifact that proves the cell works.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all [--force]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import base as cfgbase
from repro.launch import hlo_analysis, mesh as mesh_lib
from repro.models import lm
from repro.sharding import planner
from repro.train import optimizer as opt_lib, step as step_lib

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ---------------------------------------------------------------------------
# abstract inputs + shardings per cell
# ---------------------------------------------------------------------------


def _abstract_cell(cfg, shape, plan, *, with_opt, param_dtype=None):
    import jax.numpy as _jnp
    if param_dtype is None:
        param_dtype = _jnp.float32
    aparams = lm.abstract_params(cfg, dtype=param_dtype)
    pspecs = planner.param_specs(cfg, aparams, plan)
    specs = lm.input_specs(cfg, shape)
    out = {"params": (aparams, pspecs)}
    if shape.mode == "decode":
        sspecs = planner.decode_state_specs(cfg, plan, specs["state"])
        tspec = planner.batch_specs(cfg, shape, plan, specs["tokens"]) \
            if plan.decode_batch_shard else jax.tree.map(
                lambda l: jax.sharding.PartitionSpec(
                    *([None] * len(l.shape))), specs["tokens"])
        out["tokens"] = (specs["tokens"], tspec)
        out["state"] = (specs["state"], sspecs)
    else:
        bspecs = planner.batch_specs(cfg, shape, plan, specs["batch"])
        out["batch"] = (specs["batch"], bspecs)
    if with_opt:
        aopt = jax.eval_shape(opt_lib.init_opt_state, aparams)
        out["opt"] = (aopt, {"m": planner.opt_specs(cfg, aparams, plan),
                             "v": planner.opt_specs(cfg, aparams, plan),
                             "step": jax.sharding.PartitionSpec()})
    return out


def _sh(mesh, spec_tree):
    P = jax.sharding.PartitionSpec
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def lower_cell(cfg, shape, mesh, plan, *, unroll=False, probe=False,
               n_micro=None, param_dtype=None):
    """Lower the cell's step.  probe=True -> fwd+bwd only (train)."""
    n_micro = plan.n_micro if n_micro is None else n_micro
    ab = _abstract_cell(cfg, shape, plan, with_opt=(shape.mode == "train"
                                                    and not probe),
                        param_dtype=param_dtype)
    P = jax.sharding.PartitionSpec
    repl = jax.sharding.NamedSharding(mesh, P())
    pshard = _sh(mesh, ab["params"][1])

    if shape.mode == "train":
        opt_cfg = opt_lib.AdamWConfig()
        if probe:
            def probe_step(params, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: lm.forward_train(p, batch, cfg, remat=True,
                                               unroll=unroll))(params)
                return grads
            bshard = _sh(mesh, ab["batch"][1])
            fn = jax.jit(probe_step, in_shardings=(pshard, bshard),
                         out_shardings=pshard)
            with mesh:
                return fn.lower(ab["params"][0], ab["batch"][0])
        step = step_lib.make_train_step(cfg, opt_cfg, n_micro=n_micro)
        oshard = _sh(mesh, ab["opt"][1])
        bshard = _sh(mesh, ab["batch"][1])
        metr = {"grad_norm": repl, "lr": repl, "loss": repl}
        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, metr),
                     donate_argnums=(0, 1))
        with mesh:
            return fn.lower(ab["params"][0], ab["opt"][0], ab["batch"][0])

    if shape.mode == "prefill":
        def prefill(params, batch):
            return lm.forward_prefill(params, batch, cfg, unroll=unroll)
        bshard = _sh(mesh, ab["batch"][1])
        # state shardings: infer from abstract output specs
        out_state = jax.eval_shape(prefill, ab["params"][0], ab["batch"][0])
        sspecs = planner.decode_state_specs(cfg, plan, out_state[1])
        fn = jax.jit(prefill, in_shardings=(pshard, bshard),
                     out_shardings=(repl, _sh(mesh, sspecs)))
        with mesh:
            return fn.lower(ab["params"][0], ab["batch"][0])

    # decode
    def decode(params, tokens, state):
        return lm.forward_decode(params, tokens, state, cfg, unroll=unroll)
    tshard = _sh(mesh, ab["tokens"][1])
    sshard = _sh(mesh, ab["state"][1])
    fn = jax.jit(decode, in_shardings=(pshard, tshard, sshard),
                 out_shardings=(repl, sshard), donate_argnums=(2,))
    with mesh:
        return fn.lower(ab["params"][0], ab["tokens"][0], ab["state"][0])


def _probe_cfg(cfg, depth_groups):
    """Config truncated to ``depth_groups`` layer groups (for cost probes)."""
    import dataclasses as dc
    from repro.models import blocks
    gs = blocks.group_size(cfg)
    changes = {"n_layers": gs * depth_groups,
               "name": f"{cfg.name}-probe{depth_groups}"}
    if cfg.is_encoder_decoder:
        changes["n_enc_layers"] = depth_groups
    return dc.replace(cfg, **changes)


def _analyze(compiled, n_chips):
    cost = dict(compiled.cost_analysis())
    coll = hlo_analysis.collective_stats(compiled.as_text())
    mem = compiled.memory_analysis()
    memd = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        memd[f] = getattr(mem, f, None)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": coll.total_bytes,
        "coll_counts": coll.counts,
        "coll_by_kind": coll.bytes_by_kind,
        "memory": memd,
    }


def _local_param_bytes(cfg, plan, mesh):
    aparams = lm.abstract_params(cfg)
    pspecs = planner.param_specs(cfg, aparams, plan)
    total = 0
    for leaf, spec in zip(jax.tree.leaves(aparams),
                          jax.tree.leaves(
                              pspecs, is_leaf=lambda x: isinstance(
                                  x, jax.sharding.PartitionSpec))):
        sh = jax.sharding.NamedSharding(mesh, spec)
        shard_shape = sh.shard_shape(leaf.shape)
        n = 1
        for dsz in shard_shape:
            n *= dsz
        total += n * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, probes=True,
             out_dir: Path = OUT_DIR, force=False, plan_overrides=None,
             tag="baseline", serve_bf16=False, moe_scan=False,
             moe_local=False):
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}__{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = cfgbase.get_config(arch)
    shape = cfgbase.SHAPES_BY_NAME[shape_name]
    enabled, why = cfgbase.cell_enabled(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "timestamp": time.time()}
    if not enabled:
        rec.update(status="skipped", reason=why)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    plan = planner.make_plan(cfg, shape, mesh, **(plan_overrides or {}))
    rec["plan"] = {"fsdp": plan.fsdp, "n_micro": plan.n_micro,
                   "data_axes": plan.data_axes,
                   "n_chips": plan.n_chips,
                   "cache_seq_model": plan.cache_seq_model,
                   "decode_batch_shard": plan.decode_batch_shard,
                   "serve_bf16": serve_bf16, "moe_scan": moe_scan,
                   "moe_local": moe_local}
    from repro.models import moe as _moe
    _moe.DISPATCH_SCAN = moe_scan
    _moe.DISPATCH_GROUPS = plan.data_size if moe_local else 0
    _moe.GROUP_AXES = tuple(plan.data_axes)
    _moe.MESH = mesh if moe_local else None
    pdtype = (jnp.bfloat16 if serve_bf16 and shape.mode != "train"
              else jnp.float32)
    try:
        t0 = time.time()
        lowered = lower_cell(cfg, shape, mesh, plan, param_dtype=pdtype)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        rec["real"] = _analyze(compiled, plan.n_chips)
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        del compiled, lowered
    except Exception as e:  # a failing cell is a bug: record it loudly
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    if probes:
        try:
            rec["probe"] = _run_probes(cfg, shape, mesh, plan,
                                       param_dtype=pdtype)
        except Exception as e:
            rec["probe_error"] = f"{type(e).__name__}: {e}"

    rec["derived"] = _derive_roofline(cfg, shape, mesh, plan, rec)
    rec["status"] = "ok"
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def _run_probes(cfg, shape, mesh, plan, param_dtype=None):
    """Unrolled depth-1/2 probes under the real shardings."""
    from repro.models import blocks
    out = {}
    for d in (1, 2):
        pcfg = _probe_cfg(cfg, d)
        pshape = shape
        if shape.mode == "train":
            # probe one microbatch
            pshape = dataclasses.replace(
                shape, global_batch=max(shape.global_batch // plan.n_micro,
                                        1))
        pplan = dataclasses.replace(plan, n_micro=1)
        lowered = lower_cell(pcfg, pshape, mesh, pplan, unroll=True,
                             probe=(shape.mode == "train"),
                             param_dtype=param_dtype)
        compiled = lowered.compile()
        out[f"d{d}"] = _analyze(compiled, plan.n_chips)
        del compiled, lowered
    return out


def _derive_roofline(cfg, shape, mesh, plan, rec):
    """Combine probes + analytic optimizer into per-device roofline terms."""
    from repro.models import blocks
    ng = cfg.n_layers // blocks.group_size(cfg)
    n_chips = plan.n_chips
    if "probe" in rec:
        d1, d2 = rec["probe"]["d1"], rec["probe"]["d2"]
        terms = {}
        for key in ("flops", "bytes", "coll_bytes"):
            B = max(d2[key] - d1[key], 0.0)
            A = max(d1[key] - B, 0.0)
            tot = A + ng * B
            if shape.mode == "train":
                tot *= plan.n_micro
            terms[key] = tot
        if shape.mode == "train":
            # analytic AdamW: read p/m/v/g + write p/m/v (fp32), ~12 flop/p
            pl_bytes = _local_param_bytes(cfg, plan, mesh)
            terms["bytes"] += 7 * pl_bytes
            terms["flops"] += 3 * pl_bytes  # 12 flops per 4-byte param
            # grad sync was inside every probe; real pipeline syncs once
            if plan.n_micro > 1:
                dsz = plan.data_size
                gsync = 2 * (1 - 1 / dsz) * pl_bytes
                terms["coll_bytes"] -= (plan.n_micro - 1) * gsync
                terms["coll_bytes"] = max(terms["coll_bytes"], 0.0)
        method = "probe"
    else:
        terms = {k: rec["real"][k] for k in ("flops", "bytes", "coll_bytes")}
        method = "real(while-body-once; underestimates scans)"

    t_c = terms["flops"] / mesh_lib.PEAK_FLOPS_BF16
    t_m = terms["bytes"] / mesh_lib.HBM_BW
    t_x = terms["coll_bytes"] / mesh_lib.ICI_LINK_BW
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
              key=lambda kv: kv[1])[0]
    pc = cfg.param_counts()
    n_active = pc["active"]
    if shape.mode == "train":
        model_flops = 6 * n_active * shape.tokens
    elif shape.mode == "prefill":
        model_flops = 2 * n_active * shape.tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    hlo_flops_global = terms["flops"] * n_chips
    return {
        "method": method,
        "flops_per_device": terms["flops"],
        "hbm_bytes_per_device": terms["bytes"],
        "coll_bytes_per_device": terms["coll_bytes"],
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_collective_s": t_x,
        "dominant": dom,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else 0.0),
        "roofline_bound_s": max(t_c, t_m, t_x),
        "roofline_fraction": (t_c / max(t_c, t_m, t_x)
                              if max(t_c, t_m, t_x) > 0 else 0.0),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def force_placeholder_devices(n: int = 512):
    """The dry-run builds the production meshes (16x16 single-pod,
    2x16x16 multi-pod) out of host placeholder devices.  MUST run before
    jax initialises its backend — main() calls it first thing, BEFORE
    any jax array op.  Deliberately NOT a module-level side effect:
    importing this module (tests, tooling) must never change the device
    topology of the importing process."""
    import jax
    backends = getattr(getattr(jax._src, "xla_bridge", None),
                       "_backends", None)
    if backends:  # backend already up: too late
        raise RuntimeError(
            "force_placeholder_devices must run before jax init")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={n}")


def main():
    force_placeholder_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="bf16 params for prefill/decode cells")
    ap.add_argument("--moe-scan", action="store_true",
                    help="associative-scan MoE dispatch")
    ap.add_argument("--moe-local", action="store_true",
                    help="group-local MoE dispatch (no token exchange)")
    ap.add_argument("--fsdp", default="auto", choices=("auto", "on", "off"))
    ap.add_argument("--cache-seq-model", action="store_true",
                    help="shard decode KV cache length over model axis")
    ap.add_argument("--no-decode-batch-shard", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    args = ap.parse_args()
    plan_overrides = {"cache_seq_model": args.cache_seq_model,
                      "decode_batch_shard": not args.no_decode_batch_shard}
    if args.fsdp != "auto":
        plan_overrides["fsdp"] = args.fsdp == "on"
    if args.n_micro:
        plan_overrides["n_micro"] = args.n_micro

    archs = ([args.arch] if args.arch
             else sorted(cfgbase.all_configs().keys()))
    shapes = ([args.shape] if args.shape
              else [s.name for s in cfgbase.SHAPES])
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    results = []
    for a in archs:
        for s in shapes:
            for m in meshes:
                t0 = time.time()
                rec = run_cell(a, s, m, probes=not args.no_probes,
                               out_dir=Path(args.out), force=args.force,
                               tag=args.tag, serve_bf16=args.serve_bf16,
                               moe_scan=args.moe_scan,
                               moe_local=args.moe_local,
                               plan_overrides=plan_overrides)
                dt = time.time() - t0
                st = rec.get("status", "?")
                dom = rec.get("derived", {}).get("dominant", "-")
                print(f"[{st:8s}] {a:28s} {s:12s} {m:6s} dom={dom:10s} "
                      f"({dt:.1f}s)", flush=True)
                if st == "FAILED":
                    print("    " + rec.get("error", ""), flush=True)
                results.append(rec)
    n_ok = sum(r.get("status") == "ok" for r in results)
    n_skip = sum(r.get("status") == "skipped" for r in results)
    n_fail = sum(r.get("status") == "FAILED" for r in results)
    print(f"\ndry-run cells: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
