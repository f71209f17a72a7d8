"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

Proves the distribution config is coherent without hardware:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out benchmarks/results

For each combo we record compiled.cost_analysis() (FLOPs / bytes),
memory_analysis() when the backend provides it, an analytic per-device
params/state footprint, and the collective-operand bytes parsed from the
post-optimization HLO — the §Roofline inputs.

NOTE the forced device count MUST precede any jax import (it locks at
first backend init), and only *script execution* may set it: importing
dryrun helpers from tests or the shard engine must not clobber an
already-initialized backend, so the mutation sits under the ``__main__``
guard and goes through ``repro.launch.xla_flags`` (which refuses to touch
XLA_FLAGS once a backend exists).
"""
if __name__ == "__main__":
    from repro.launch.xla_flags import force_host_device_count
    force_host_device_count(512)

import argparse
import json
import os
import re
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import INPUT_SHAPES, FedConfig, get_arch, list_archs
from repro.configs.input_specs import (fed_input_specs, serve_input_specs,
                                       train_input_specs)
from repro.core import make_compressor, make_round_fn, mixing_matrix
from repro.core.fed_state import FedState
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import data_axes, make_production_mesh
from repro.launch import sharding as shd
from repro.models import get_model

SGLD_ETA = 1e-4


# --------------------------------------------------------------------------
# Steps to lower
# --------------------------------------------------------------------------

def build_train_step(model):
    """Paper-faithful SGLD training step (data-parallel baseline)."""

    def train_step(params, batch, key):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch, key)
        knoise = jax.random.fold_in(key, 1)
        leaves, treedef = jax.tree.flatten(grads)
        keys = jax.random.split(knoise, len(leaves))
        noise = [jnp.sqrt(2 * SGLD_ETA) * jax.random.normal(k, g.shape, jnp.float32)
                 for k, g in zip(keys, leaves)]
        noise = jax.tree.unflatten(treedef, noise)
        new_params = jax.tree.map(
            lambda p, g, n: (p.astype(jnp.float32) - SGLD_ETA * g.astype(jnp.float32)
                             + n).astype(p.dtype),
            params, grads, noise)
        return new_params, loss

    return train_step


def build_prefill_step(model):
    def prefill_step(params, batch):
        return model.logits(params, batch)
    return prefill_step


def build_serve_step(model):
    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)
    return serve_step


def build_fed_step(model, fed_cfg, fed_axis: str = "pod"):
    omega = mixing_matrix(fed_cfg.topology, fed_cfg.num_nodes, fed_cfg.mixing)
    comp = make_compressor(fed_cfg)
    round_fn = make_round_fn("cdbfl", model.loss, fed_cfg, omega, comp)

    def fed_step(state, batches, key):
        from repro.models.sharding_hints import reserve_axes
        with reserve_axes(fed_axis):   # keep hints off the node axis
            return round_fn(state, batches, key)

    return fed_step


# --------------------------------------------------------------------------
# Dry-run driver
# --------------------------------------------------------------------------

def _tree_device_bytes(specs, shardings, mesh) -> float:
    """Analytic per-device bytes for a (spec tree, sharding tree)."""
    total = 0.0
    for leaf, shard in zip(jax.tree.leaves(specs), jax.tree.leaves(shardings)):
        n = float(np.prod(leaf.shape)) if leaf.shape else 1.0
        denom = 1.0
        spec = shard.spec
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            denom *= float(np.prod([mesh.shape[a] for a in axes]))
        total += n * jnp.dtype(leaf.dtype).itemsize / denom
    return total


def dryrun_combo(arch_id: str, shape_name: str, *, multi_pod: bool = False,
                 step: str = "auto", fed_nodes: Optional[int] = None,
                 rules: Optional[dict] = None,
                 kv_dtype=jnp.bfloat16,
                 control_dtype: str = "float32",
                 param_dtype: str = "float32",
                 moe_impl: Optional[str] = None,
                 variant: str = "auto") -> Dict[str, Any]:
    """Lower+compile one combo; returns the roofline record."""
    t_start = time.time()
    spec = get_arch(arch_id)
    shape = INPUT_SHAPES[shape_name]
    if shape_name in spec.skips:
        return {"arch": arch_id, "shape": shape_name, "skipped": spec.skips[shape_name]}

    cfg = spec.config
    # sub-quadratic carve-out: dense/moe/vlm archs run long_500k with SWA
    if (variant == "auto" and shape_name == "long_500k"
            and cfg.family in ("dense", "vlm", "moe")
            and cfg.sliding_window == 0 and cfg.kv_lora_rank == 0):
        cfg = cfg.replace(sliding_window=4096)
        variant = "sliding_window_4096"
    elif variant == "auto" and shape_name == "long_500k" and cfg.kv_lora_rank:
        variant = "mla_latent_cache"   # linear-size cache, O(S·rank)/token
    elif variant == "auto":
        variant = "base"

    if moe_impl and cfg.moe.num_experts:
        import dataclasses as _dc
        cfg = cfg.replace(moe=_dc.replace(cfg.moe, impl=moe_impl))
        variant = f"{variant}+moe_{moe_impl}"

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)

    if step == "auto":
        step = {"train": "train", "prefill": "prefill", "decode": "serve"}[shape.kind]

    def _pspecs():
        sp = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        if param_dtype != "float32":
            dt = jnp.dtype(param_dtype)
            sp = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, dt), sp)
        return sp

    with jax.set_mesh(mesh):
        if step == "train":
            pspecs = _pspecs()
            pshard = shd.params_shardings(pspecs, mesh, rules)
            bspecs = train_input_specs(cfg, shape)
            bshard = shd.batch_shardings(bspecs, mesh)
            kspec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            kshard = NamedSharding(mesh, P())
            fn = jax.jit(build_train_step(model),
                         in_shardings=(pshard, bshard, kshard),
                         out_shardings=(pshard, NamedSharding(mesh, P())))
            lowered = fn.lower(pspecs, bspecs, kspec)
            state_bytes = 2 * _tree_device_bytes(pspecs, pshard, mesh)  # θ+grads
            batch_bytes = _tree_device_bytes(
                jax.tree.leaves(bspecs), jax.tree.leaves(bshard), mesh)
        elif step == "prefill":
            pspecs = _pspecs()
            pshard = shd.params_shardings(pspecs, mesh, rules)
            bspecs = train_input_specs(cfg, shape)
            bshard = shd.batch_shardings(bspecs, mesh)
            fn = jax.jit(build_prefill_step(model),
                         in_shardings=(pshard, bshard))
            lowered = fn.lower(pspecs, bspecs)
            state_bytes = _tree_device_bytes(pspecs, pshard, mesh)
            batch_bytes = _tree_device_bytes(
                jax.tree.leaves(bspecs), jax.tree.leaves(bshard), mesh)
        elif step == "serve":
            pspecs = _pspecs()
            pshard = shd.params_shardings(pspecs, mesh, rules)
            step_specs, cache_specs = serve_input_specs(cfg, shape, kv_dtype)
            cshard = shd.cache_shardings(cache_specs, mesh)
            tshard = shd.batch_shardings(step_specs["tokens"], mesh)
            fn = jax.jit(build_serve_step(model),
                         in_shardings=(pshard, cshard, tshard,
                                       NamedSharding(mesh, P())),
                         out_shardings=(cshard, NamedSharding(mesh, P())))
            lowered = fn.lower(pspecs, cache_specs, step_specs["tokens"],
                               step_specs["pos"])
            state_bytes = (_tree_device_bytes(pspecs, pshard, mesh)
                           + _tree_device_bytes(cache_specs, cshard, mesh))
            batch_bytes = 0.0
        elif step == "fed":
            fed_axis = "pod" if multi_pod else "data"
            k = fed_nodes or mesh.shape[fed_axis]
            fed_cfg = FedConfig(num_nodes=k, local_steps=4, topology="ring",
                                compressor="block_topk", compress_ratio=0.01,
                                control_dtype=control_dtype)
            pspecs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            state_specs = jax.eval_shape(
                lambda: FedState(
                    params=jax.tree.map(
                        lambda x: jnp.zeros((k,) + x.shape, x.dtype), pspecs),
                    v=jax.tree.map(
                        lambda x: jnp.zeros((k,) + x.shape,
                                            jnp.dtype(control_dtype)), pspecs),
                    v_bar=jax.tree.map(
                        lambda x: jnp.zeros((k,) + x.shape,
                                            jnp.dtype(control_dtype)), pspecs),
                    opt_state=(),
                    key=jnp.zeros((k, 2), jnp.uint32),
                    round=jnp.zeros((), jnp.int32),
                ))
            fshard = FedState(
                params=shd.params_shardings(state_specs.params, mesh,
                                            rules, fed_axis=fed_axis),
                v=shd.params_shardings(state_specs.v, mesh, rules,
                                       fed_axis=fed_axis),
                v_bar=shd.params_shardings(state_specs.v_bar, mesh, rules,
                                           fed_axis=fed_axis),
                opt_state=(),
                key=NamedSharding(mesh, P(fed_axis)),
                round=NamedSharding(mesh, P()),
            )
            bspecs = fed_input_specs(cfg, shape, fed_cfg)
            bshard = shd.batch_shardings(bspecs, mesh, fed_axis=fed_axis)
            kspec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            fn = jax.jit(build_fed_step(model, fed_cfg, fed_axis),
                         in_shardings=(fshard, bshard, NamedSharding(mesh, P())),
                         out_shardings=(fshard, None))
            lowered = fn.lower(state_specs, bspecs, kspec)
            state_bytes = (_tree_device_bytes(state_specs.params, fshard.params, mesh)
                           + _tree_device_bytes(state_specs.v, fshard.v, mesh)
                           + _tree_device_bytes(state_specs.v_bar, fshard.v_bar, mesh))
            batch_bytes = _tree_device_bytes(
                jax.tree.leaves(bspecs), jax.tree.leaves(bshard), mesh)
        else:
            raise ValueError(step)

        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):   # older jax: one dict per program
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not support it
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    hc = hlo_analyze(hlo, int(np.prod(list(mesh.shape.values()))))

    rec = {
        "arch": arch_id, "shape": shape_name, "step": step,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_devices": int(np.prod(list(mesh.shape.values()))),
        # per-device, trip-count-corrected (repro.launch.hlo_cost)
        "flops_per_device": float(hc["flops"]),
        "hbm_bytes_per_device": float(hc["bytes_hbm"]),
        "hbm_bytes_fused_per_device": float(hc["bytes_hbm_fused"]),
        "collective_bytes_per_device": hc["collective_bytes"],
        "collective_total_per_device": float(hc["collective_total"]),
        "collective_counts": hc["collective_counts"],
        # raw XLA numbers (per-device, while bodies counted once) for reference
        "xla_flops_raw": float(cost.get("flops", 0.0)),
        "xla_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "state_bytes_per_device": float(state_bytes),
        "batch_bytes_per_device": float(batch_bytes),
        "memory_analysis": mem_d,
        "lower_s": t_lower - t_start,
        "compile_s": t_compile - t_lower,
        "hlo_lines": hlo.count("\n"),
    }
    return rec


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--step", default="auto",
                    choices=["auto", "train", "prefill", "serve", "fed"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--rules-preset", default=None,
                    choices=[None, "serve_tp"],
                    help="serve_tp: TP-only params (no FSDP all-gathers in decode)")
    ap.add_argument("--control-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--moe-impl", default=None, choices=[None, "ragged", "gshard"])
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    args = ap.parse_args()

    lm_archs = [a for a in list_archs() if a != "lenet-radar"]
    combos = []
    if args.all:
        for a in lm_archs:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch, shape in combos:
        for mp in meshes:
            tag = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}|{args.step}"
            rules = None
            if args.rules_preset == "serve_tp":
                from repro.launch.sharding import DEFAULT_RULES
                rules = dict(DEFAULT_RULES, embed=None)
            try:
                rec = dryrun_combo(arch, shape, multi_pod=mp, step=args.step,
                                   rules=rules,
                                   control_dtype=args.control_dtype,
                                   param_dtype=args.param_dtype,
                                   moe_impl=args.moe_impl)
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "step": args.step, "error": f"{type(e).__name__}: {e}"}
            if "skipped" in rec:
                print(f"[skip] {tag}: {rec['skipped']}")
            elif "error" in rec:
                print(f"[FAIL] {tag}: {rec['error']}")
            else:
                print(f"[ok]   {tag}: flops/dev={rec['flops_per_device']:.3e} "
                      f"coll/dev={rec['collective_total_per_device']:.3e}B "
                      f"state={rec['state_bytes_per_device']/2**30:.2f}GiB/dev "
                      f"compile={rec['compile_s']:.1f}s")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                fn = (f"{arch}_{shape}_{rec.get('mesh')}_"
                      f"{rec.get('step', args.step)}{args.tag}.json")
                with open(os.path.join(args.out, fn), "w") as f:
                    json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
