import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
# NOTE: the two lines above MUST run before any jax import (device count
# locks on first init). Everything below is ordinary code.
"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) pair, lower + compile the step on
the production mesh (16x16 single-pod and 2x16x16 multi-pod), print
memory_analysis() (proves fit) and cost_analysis() (roofline §g), parse
the post-SPMD HLO for collective bytes, and write a JSON artifact under
artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-15b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch import roofline as R
from repro.launch.inputs import (SHAPES, input_specs, make_prefill_step,
                                 make_serve_step, make_train_step,
                                 model_flops_for, shape_config)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding import (batch_shardings, cache_shardings,
                            opt_state_shardings, params_shardings)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# long_500k applicability notes (DESIGN.md §5): who runs it and why.
LONG_OK = {a: "window-8192 variant" for a in ARCH_IDS}
LONG_OK["xlstm-125m"] = "native recurrent state"
LONG_OK["hymba-1.5b"] = "native: SSM state + window-1024 attention"
LONG_OK["whisper-large-v3"] = ("window-8192 variant; out-of-domain for "
                               "whisper's decoder, mechanical support only")


def _struct_with_sharding(struct_tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct_tree, sharding_tree)


def build_lowerable(arch: str, shape: str, mesh, unroll: bool = False,
                    opt_level: int = 0):
    """Returns (fn, args_structs, out_shardings, meta).

    ``unroll=True`` unrolls the layer stack: XLA's cost_analysis counts
    while-loop (scan) bodies ONCE, so scan-based lowerings undercount
    FLOPs/bytes/collectives by ~num_layers. The roofline pass therefore
    compiles the unrolled variant; the scan variant remains the runtime
    path (and is also compiled to prove the production graph).
    """
    cfg = shape_config(get_config(arch), shape)
    if unroll:
        cfg = dataclasses.replace(cfg, unroll_layers=True)
    fn, args, out_sh, donate = _build_from_cfg(cfg, shape, mesh,
                                               opt_level=opt_level)
    return fn, args, out_sh, donate, cfg


def _probe_cfg(cfg, L: int):
    """A structurally identical model with L (unrolled) layers — used to
    measure exact per-layer cost deltas (see build_lowerable docstring)."""
    return dataclasses.replace(
        cfg, num_layers=L,
        encoder_layers=min(L, cfg.encoder_layers) if cfg.encoder_layers else 0,
        block_pattern=cfg.block_pattern[:L] if cfg.block_pattern else (),
        unroll_layers=True)


def _lower_compile(fn, args, out_sh, mesh, donate=()):
    with mesh:
        kw = {"donate_argnums": donate} if donate else {}
        if out_sh is not None:
            kw["out_shardings"] = out_sh
        return jax.jit(fn, **kw).lower(*args).compile()


def _cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``, or ``{}`` when XLA reports none."""
    return compiled.cost_analysis() or {}


def _cost_record(compiled):
    cost = _cost_analysis(compiled)
    coll = R.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0) or 0),
            "bytes_accessed": float(cost.get("bytes accessed", 0) or 0),
            "coll": coll}


def probe_corrected_cost(arch: str, shape: str, mesh, cfg,
                         opt_level: int = 0) -> dict | None:
    """XLA cost_analysis counts scan (while) bodies ONCE, so the scan
    lowering undercounts layer-stack costs by ~num_layers. Correction:
    compile tiny UNROLLED probes at L=1 and L=2; the delta is the exact
    per-layer cost at full batch/seq/mesh, and
        corrected = f(1) + (L_full - 1) · (f(2) - f(1)).
    Heterogeneous stacks (xlstm) already lower unrolled — no correction.
    """
    if not _is_scan_stack(cfg):
        return None
    recs = []
    for L in (1, 2):
        kind = SHAPES[shape][2]
        pcfg = _probe_cfg(cfg, L)
        fn, args, out_sh, donate = _build_from_cfg(pcfg, shape, mesh,
                                                   opt_level=opt_level)
        compiled = _lower_compile(fn, args, out_sh, mesh, donate)
        recs.append(_cost_record(compiled))
    f1, f2 = recs
    Lf = cfg.num_layers
    out = {
        "method": "probe L=1/L=2 unrolled, corrected = f1 + (L-1)(f2-f1)",
        "flops": f1["flops"] + (Lf - 1) * (f2["flops"] - f1["flops"]),
        "bytes_accessed": f1["bytes_accessed"]
        + (Lf - 1) * (f2["bytes_accessed"] - f1["bytes_accessed"]),
    }
    c1 = f1["coll"]["total_bytes"]
    c2 = f2["coll"]["total_bytes"]
    out["coll_total_bytes"] = c1 + (Lf - 1) * (c2 - c1)
    out["coll_per_layer"] = {
        k: f1["coll"]["bytes"][k] + (Lf - 1)
        * (f2["coll"]["bytes"][k] - f1["coll"]["bytes"][k])
        for k in f1["coll"]["bytes"]}
    return out


def _is_scan_stack(cfg) -> bool:
    types = set(cfg.layer_types)
    return len(types) == 1 and not cfg.unroll_layers


def _build_from_cfg(cfg, shape: str, mesh, opt_level: int = 0):
    """build_lowerable body for an explicit cfg (probes).

    opt_level >= 1 (§Perf): KV-cache seq axis sharded over "model" when
    heads don't divide it, and donated buffers (cache / params+opt) so
    updates happen in place instead of round-tripping.
    Returns (fn, args, out_shardings, donate).
    """
    kind = SHAPES[shape][2]
    if opt_level >= 4 and kind == "decode":
        # §Perf: unroll the decode stack — the scan's ys cache double-
        # buffers (in+out copies alive across the loop); unrolled layers
        # let XLA alias each layer's cache update in place.
        cfg = dataclasses.replace(cfg, unroll_layers=True)
    if opt_level >= 2 and cfg.is_moe:
        # §Perf: pad experts up to a multiple of the model axis so expert-
        # parallel sharding applies (function-preserving; DESIGN.md §8)
        from repro.sharding.specs import mesh_axis_size
        tp = mesh_axis_size(mesh, "model")
        if cfg.num_experts % tp:
            cfg = dataclasses.replace(
                cfg, pad_experts_to=-(-cfg.num_experts // tp) * tp)
    params_struct = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    # §Perf opt 3: FSDP-style 2D expert sharding on serving shapes
    expert_2d = opt_level >= 3 and kind != "train"
    p_sh = params_shardings(params_struct, mesh, expert_2d=expert_2d)
    specs = _input_specs_for(cfg, shape)
    b_sh = batch_shardings(specs["batch"], mesh)
    batch_struct = _struct_with_sharding(specs["batch"], b_sh)
    params_in = _struct_with_sharding(params_struct, p_sh)
    donate = ()
    if kind == "train":
        micro = 8 if opt_level >= 2 else 1   # §Perf: grad accumulation
        step, optimizer = make_train_step(cfg, microbatches=micro)
        opt_struct = jax.eval_shape(optimizer.init, params_struct)
        o_sh = opt_state_shardings(params_struct, mesh)
        opt_in = _struct_with_sharding(opt_struct, o_sh)
        if opt_level >= 1:
            donate = (0, 1)            # params, opt_state updated in place
        return step, (params_in, opt_in, batch_struct), (p_sh, o_sh, None), donate
    if kind == "prefill":
        return make_prefill_step(cfg), (params_in, batch_struct), None, donate
    c_sh = cache_shardings(specs["cache"], mesh, batch=SHAPES[shape][1],
                           seq_over_model=opt_level >= 1)
    cache_in = _struct_with_sharding(specs["cache"], c_sh)
    if opt_level >= 1:
        donate = (2,)                  # cache updated in place
    return (make_serve_step(cfg), (params_in, batch_struct, cache_in),
            (None, c_sh), donate)


def _input_specs_for(cfg, shape):
    return input_specs(cfg, shape)


def run_one(arch: str, shape: str, multi_pod: bool = False,
            out_dir: str | None = None, verbose: bool = True,
            unroll: bool = False, probes: bool = True,
            opt_level: int = 0) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "x".join(map(str, mesh.devices.shape))
    if unroll:
        mesh_name += "-unrolled"
    if opt_level:
        mesh_name += f"-opt{opt_level}"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
           "ok": False}
    t0 = time.time()
    try:
        fn, args, out_sh, donate, cfg = build_lowerable(
            arch, shape, mesh, unroll=unroll, opt_level=opt_level)
        with mesh:
            kw = {"donate_argnums": donate} if donate else {}
            if out_sh is not None:
                kw["out_shardings"] = out_sh
            lowered = jax.jit(fn, **kw).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_rec = {}
        if mem is not None:
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                v = getattr(mem, field, None)
                if v is not None:
                    mem_rec[field] = int(v)
        cost = _cost_analysis(compiled)
        coll = R.collective_bytes(compiled.as_text())
        mf = model_flops_for(cfg, shape)

        # scan-body cost correction via unrolled L=1/L=2 probes
        corrected = None
        if probes and not multi_pod:
            try:
                corrected = probe_corrected_cost(arch, shape, mesh, cfg,
                                                 opt_level=opt_level)
            except Exception as e:
                corrected = {"error": f"{type(e).__name__}: {e}"}
        if corrected and "flops" in corrected:
            eff_cost = {"flops": corrected["flops"],
                        "bytes accessed": corrected["bytes_accessed"]}
            eff_coll = {"total_bytes": corrected["coll_total_bytes"],
                        "bytes": corrected["coll_per_layer"],
                        "counts": coll["counts"]}
        else:
            eff_cost, eff_coll = cost, coll
        terms = R.derive_terms(eff_cost, eff_coll, chips, mf)
        rec.update(
            ok=True, lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory=mem_rec,
            cost_raw={"flops": float(cost.get("flops", 0) or 0),
                      "bytes_accessed": float(cost.get("bytes accessed", 0) or 0)},
            cost_corrected=corrected,
            collectives=coll, roofline=terms.as_dict(),
            note=LONG_OK.get(arch, "") if shape == "long_500k" else "")
        if verbose:
            bpd = mem_rec.get("argument_size_in_bytes", 0) + \
                mem_rec.get("temp_size_in_bytes", 0)
            print(f"[OK] {arch:24s} {shape:12s} {mesh_name:8s} "
                  f"compile={t_compile:6.1f}s bytes/dev={bpd/2**30:7.2f}GiB "
                  f"flops/dev={terms.flops:.3e} coll/dev={terms.coll_bytes:.3e} "
                  f"bottleneck={terms.bottleneck}", flush=True)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[FAIL] {arch} {shape} {mesh_name}: {rec['error']}",
                  flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer stack (roofline cost fidelity)")
    ap.add_argument("--opt", type=int, default=0,
                    help="optimization level (1: 2D cache sharding + donation)")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args()

    combos = []
    if args.all:
        combos = [(a, s) for a in ARCH_IDS for s in SHAPES]
    elif args.arch and args.shape:
        combos = [(args.arch, args.shape)]
    else:
        ap.error("need --all or (--arch and --shape)")

    results = [run_one(a, s, multi_pod=args.multi_pod, out_dir=args.out,
                       unroll=args.unroll, opt_level=args.opt)
               for a, s in combos]
    ok = sum(r["ok"] for r in results)
    print(f"\n{ok}/{len(results)} combos compiled OK")
    raise SystemExit(0 if ok == len(results) else 1)


if __name__ == "__main__":
    main()
