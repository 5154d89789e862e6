"""Multi-pod dry run: lower + compile every (architecture x input shape) on
the production meshes, extract memory / cost / collective analyses, and emit
the roofline terms (assignment: MULTI-POD DRY-RUN + ROOFLINE ANALYSIS).

The device-count XLA flag below MUST precede every other import that could
initialize jax — including `from repro...` — since jax locks the device count
on first backend init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.core.hardware import TPU_V5E
from repro.core.perf_model import PerfModel
from repro.launch import hlo_analysis, specs as sp
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.models.config import INPUT_SHAPES, AUDIO, ModelConfig
from repro.models.model import build_model
from repro.sharding import rules
from repro.sharding.ctx import activate, standard_mapping
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import make_train_step

# long_500k skip set (DESIGN.md §4: pure full-attention archs + enc-dec)
LONG_SKIP = {
    "phi-3-vision-4.2b": "pure full attention (no sub-quadratic variant)",
    "tinyllama-1.1b": "pure full attention",
    "granite-moe-3b-a800m": "pure full attention",
    "qwen3-8b": "pure full attention",
    "qwen2.5-32b": "pure full attention",
    "whisper-tiny": "enc-dec with 448-position decoder; no long decode",
}


def skip_reason(arch: str, shape_name: str) -> str | None:
    if shape_name == "long_500k":
        return LONG_SKIP.get(arch)
    return None


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_case(arch: str, shape_name: str, *, multi_pod: bool = False,
               compile_: bool = True, seq_shard: str | None = "model",
               weight_mode: str | None = None, seq_attn: bool | None = None):
    """Lower + compile one (arch, shape, mesh) case; returns a result dict.

    weight_mode overrides the sharding baseline (fsdp_tp); serving shapes
    accept "tp_only"/"replicated". seq_attn forces/disables sequence-sharded
    attention (default: auto for head counts not dividing the model axis).
    (Perf iterations, EXPERIMENTS.md §Perf.)
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    msd = mesh_shape_dict(mesh)
    n_chips = int(np.prod(list(msd.values())))
    dp = int(np.prod([msd[a] for a in rules.dp_axes(multi_pod)]))
    long_ctx = shape_name == "long_500k"
    if shape.kind == "train" and cfg.is_moe:
        # train with the classic 1.25 capacity factor (serving keeps 2.0 for
        # fewer drops); the capacity buffers are the MoE activation peak
        import dataclasses
        cfg = dataclasses.replace(cfg, moe_capacity_factor=1.25)
    model = build_model(cfg, long_context=long_ctx, moe_groups=dp, remat=True)

    params_abs = sp.abstract_params(model)
    wm = weight_mode or "fsdp_tp"
    pspecs = rules.param_specs(params_abs, msd, weight_mode=wm)
    batch_abs = sp.input_specs(cfg, shape)
    bspecs = rules.batch_spec(cfg, shape.kind, shape.global_batch, multi_pod, msd)
    bspecs = {k: bspecs.get(k, P(*([None] * len(v.shape))))
              for k, v in batch_abs.items()}

    t0 = time.perf_counter()
    if shape.kind == "train":
        opt_abs = sp.abstract_opt_state(params_abs)
        ospecs = type(opt_abs)(step=P(), mu=pspecs, nu=jax.tree.map(lambda s: s, pspecs))
        # grad accumulation: scan-saved layer carries scale with
        # depth x per-device microbatch, so deeper stacks get more splits
        micro = 16 if cfg.num_layers >= 56 else 8 if cfg.num_layers >= 32 else 4
        while micro > 1 and shape.global_batch % (micro * dp):
            micro //= 2
        step = make_train_step(model, AdamWConfig(), microbatches=micro)
        jitted = jax.jit(step,
                         in_shardings=(_named(mesh, pspecs), _named(mesh, ospecs),
                                       _named(mesh, bspecs)),
                         out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs),
                                        None),
                         donate_argnums=(0, 1))  # update in place
        args = (params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch, cache_len=shape.seq_len)
        jitted = jax.jit(prefill_step,
                         in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)))
        args = (params_abs, batch_abs)
    else:  # decode
        cache_abs = sp.abstract_cache(model, shape)
        cspecs = rules.cache_specs(cfg, cache_abs, shape.global_batch,
                                   multi_pod, msd, seq_shard=seq_shard)
        b = rules.batch_axis(shape.global_batch, multi_pod, msd)
        tok_spec = P(b)

        def decode_step(params, tokens, cache):
            return model.decode_step(params, tokens, cache)
        jitted = jax.jit(decode_step,
                         in_shardings=(_named(mesh, pspecs),
                                       NamedSharding(mesh, tok_spec),
                                       _named(mesh, cspecs)),
                         out_shardings=(None, _named(mesh, cspecs)),
                         donate_argnums=(2,))  # KV cache updates in place
        args = (params_abs, batch_abs["tokens"], cache_abs)

    b_axes = rules.batch_axis(shape.global_batch, multi_pod, msd)
    mapping = standard_mapping(b_axes)
    if seq_attn is None:
        # auto: serving shapes with head counts not dividing the TP axis.
        # (train keeps baseline sharding: the granite train case trips an
        # XLA SPMD partitioner verifier bug when the seq-attn constraints
        # meet the autodiff gather — see EXPERIMENTS.md §Perf backlog)
        # MoE excluded: seq-sharded activations entering the group-local
        # dispatch force mass resharding (granite prefill regressed 5x).
        # Audio excluded: tiny model, no win (measured 0.9x).
        seq_attn = (shape.kind != "train"
                    and cfg.num_heads % msd["model"] != 0
                    and not cfg.is_moe
                    and cfg.family not in ("ssm", "audio"))
    if seq_attn:
        mapping["attn_q_seq"] = P(b_axes, "model", None, None)
        mapping["attn_kv_rep"] = P(b_axes, None, None, None)
        mapping["attn_q_dec"] = P(b_axes, None, None)
    with mesh, activate(mapping):
        lowered = jitted.lower(*args)
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": n_chips, "lower_s": round(time.perf_counter() - t0, 1),
        }
        if not compile_:
            return result, lowered, None
        t1 = time.perf_counter()
        compiled = lowered.compile()
        result["compile_s"] = round(time.perf_counter() - t1, 1)

    mem = compiled.memory_analysis()
    result["memory"] = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    ca = compiled.cost_analysis() or {}
    result["cost_analysis"] = {"flops": ca.get("flops"),
                               "bytes_accessed": ca.get("bytes accessed")}
    hlo = hlo_analysis.analyze(compiled.as_text())
    result["hlo"] = hlo
    result["roofline"] = roofline_terms(cfg, shape, hlo, n_chips, multi_pod)
    return result, lowered, compiled


def roofline_terms(cfg: ModelConfig, shape, hlo: dict, n_chips: int,
                   multi_pod: bool) -> dict:
    """Three roofline terms (seconds) from the compiled artifact + the
    analytic perf-model cross-check (EXPERIMENTS.md §Roofline)."""
    hw = TPU_V5E
    pm = PerfModel(cfg, hw, tp=1)
    # analytic per-cluster totals from the paper's own operator model
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        est = pm.prefill_estimate([shape.seq_len] * shape.global_batch)
        analytic_flops = 3.0 * est.flops        # fwd + bwd (2x fwd)
        analytic_bytes = 3.0 * est.bytes
        model_flops = 6.0 * cfg.num_active_params() * tokens
    elif shape.kind == "prefill":
        est = pm.prefill_estimate([shape.seq_len] * shape.global_batch)
        analytic_flops, analytic_bytes = est.flops, est.bytes
        model_flops = 2.0 * cfg.num_active_params() * shape.global_batch * shape.seq_len
    else:
        est = pm.decode_estimate([shape.seq_len] * shape.global_batch)
        analytic_flops, analytic_bytes = est.flops, est.bytes
        model_flops = 2.0 * cfg.num_active_params() * shape.global_batch
    dot_flops_dev = hlo["dot_flops_per_device"]
    coll_bytes_dev = hlo["collective_bytes_per_device"]
    compute_t = dot_flops_dev / hw.peak_flops
    memory_t = (analytic_bytes / n_chips) / hw.peak_hbm_bw
    collective_t = coll_bytes_dev / (50e9)
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": collective_t}
    dom = max(terms, key=terms.get)
    return {
        **terms,
        "dominant": dom.replace("_s", ""),
        "hlo_flops_per_device": dot_flops_dev,
        "hlo_flops_cluster": dot_flops_dev * n_chips,
        "analytic_flops_cluster": analytic_flops,
        "analytic_bytes_cluster": analytic_bytes,
        "model_flops": model_flops,
        "useful_fraction": (model_flops / (dot_flops_dev * n_chips)
                            if dot_flops_dev else None),
        "collective_breakdown": hlo["collective_breakdown"],
    }


def serving_weight_mode(cfg: ModelConfig) -> str:
    """Optimized serving layout (§Perf): replicate small models, TP-only
    mid-size, keep FSDP for MoE (expert tensors dominate; TP-only layouts
    inflate dispatch temps 15x with no collective win — measured on
    granite) and for models whose TP-16 shard exceeds ~8 GB/chip."""
    if cfg.is_moe:
        return "fsdp_tp"
    bytes_tp16 = 2 * cfg.num_params() / 16
    if 2 * cfg.num_params() < 6e9:
        return "replicated"
    if bytes_tp16 < 8e9:
        return "tp_only"
    return "fsdp_tp"


def run_all(multi_pod: bool, out_path: str | None, archs=None, shapes=None,
            optimized: bool = False):
    results = []
    for arch in (archs or ASSIGNED):
        for shape_name in (shapes or list(INPUT_SHAPES)):
            reason = skip_reason(arch, shape_name)
            tag = f"{arch} x {shape_name} [{'2x16x16' if multi_pod else '16x16'}]"
            if reason:
                print(f"SKIP {tag}: {reason}", flush=True)
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if multi_pod else "16x16",
                                "skipped": reason})
                continue
            kw = {}
            if optimized and INPUT_SHAPES[shape_name].kind != "train":
                kw["weight_mode"] = serving_weight_mode(get_config(arch))
            if not optimized:
                kw["seq_attn"] = False  # paper-faithful baseline sharding
            try:
                res, _, compiled = lower_case(arch, shape_name,
                                              multi_pod=multi_pod, **kw)
                m = res["memory"]
                print(f"OK   {tag}: compile {res['compile_s']}s  "
                      f"temp/dev {(m['temp_bytes'] or 0)/1e9:.2f} GB  "
                      f"args/dev {(m['argument_bytes'] or 0)/1e9:.2f} GB  "
                      f"dominant={res['roofline']['dominant']}", flush=True)
                results.append(res)
                del compiled
            except Exception as e:  # a failure here is a sharding bug
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if multi_pod else "16x16",
                                "error": f"{type(e).__name__}: {e}"})
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {out_path}")
    n_fail = sum(1 for r in results if "error" in r)
    print(f"\n{len(results)} cases: {n_fail} failures")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--weight-mode", default=None,
                    choices=["fsdp_tp", "tp_only", "replicated"])
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper sharding (seq-attn auto + serving "
                         "weight layouts); default is the recorded baseline")
    args = ap.parse_args()
    if args.all:
        run_all(args.multi_pod, args.out,
                archs=[args.arch] if args.arch else None,
                shapes=[args.shape] if args.shape else None,
                optimized=args.optimized)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    res, lowered, compiled = lower_case(args.arch, args.shape,
                                        multi_pod=args.multi_pod,
                                        weight_mode=args.weight_mode)
    print(compiled.memory_analysis())
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})
    print(json.dumps(res["roofline"], indent=1, default=float))


if __name__ == "__main__":
    main()
