import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) cell:
    jax.jit(step, in_shardings=..., out_shardings=...)
       .lower(**ShapeDtypeStruct stand-ins)
       .compile()
then print memory_analysis() (proves the cell fits HBM), run cost_analysis()
+ the HLO collective parser, and emit the three roofline terms as JSON.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun

No real data is allocated: params/optimizer/caches/batches are all abstract.
"""
import argparse
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCHS, LONG_CONTEXT_ARCHS, SHAPES, cells, get_config, input_specs,
)
from repro.dist.sharding import (
    batch_pspecs, cache_pspecs, make_rules_for, param_pspecs, set_axis_sizes,
    use_rules,
)
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.models.model import CausalLM
from repro.optim.adamw import AdamWConfig, init_state
from repro.roofline.analysis import analyze_compiled, model_flops_for
from repro.train.step import make_train_step


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_axis_sizes(mesh)
    chips = mesh_chip_count(mesh)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    kind = shape.kind
    rules = make_rules_for(cfg, mesh, multi_pod=multi_pod, kind=kind)
    model = CausalLM(cfg)

    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init, key)
    params_sh = _named(mesh, param_pspecs(params_shapes, rules))
    batch_shapes = input_specs(cfg, shape)
    batch_sh = _named(mesh, batch_pspecs(cfg, batch_shapes, rules))

    t0 = time.time()
    with use_rules(rules, mesh), mesh:
        if kind == "train":
            opt_shapes = jax.eval_shape(init_state, params_shapes)
            opt_sh = {"m": params_sh, "v": params_sh,
                      "count": NamedSharding(mesh, P())}
            # deep+wide models (qwen1.5-32b: 64L x 5120) and the mamba2
            # hybrid (chunked-SSD intra-chunk tensors scale with b_loc) use
            # gradient accumulation — the saved residual stack / chunk
            # panels are the peak-memory drivers and scale with the
            # microbatch size.
            micro = 4 if (cfg.n_layers * cfg.d_model > 300_000
                          or cfg.family == "hybrid") else 1
            step_fn = make_train_step(model, AdamWConfig(), microbatches=micro)
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_sh, opt_sh, batch_sh,
                              NamedSharding(mesh, P())),
                out_shardings=(params_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shapes, opt_shapes, batch_shapes,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "prefill":
            max_len = shape.seq_len

            def prefill_step(params, batch):
                return model.prefill(params, batch, max_len,
                                     cache_dtype=jnp.bfloat16)

            cache_shapes = jax.eval_shape(
                partial(model.init_cache, shape.global_batch, max_len,
                        jnp.bfloat16))
            cache_out_sh = _named(mesh, cache_pspecs(cfg, cache_shapes, rules))
            jitted = jax.jit(prefill_step,
                             in_shardings=(params_sh, batch_sh),
                             out_shardings=(None, cache_out_sh))
            lowered = jitted.lower(params_shapes, batch_shapes)
        else:  # decode
            max_len = shape.seq_len
            b = shape.global_batch
            cache_dtype = jnp.bfloat16
            cache_shapes = jax.eval_shape(
                partial(model.init_cache, b, max_len, cache_dtype))
            specs = cache_pspecs(cfg, cache_shapes, rules)
            # fp8 KV quantisation when the bf16 cache cannot fit HBM
            # (qwen1.5-32b: MHA kv=40 @ 32k x 128 batch = 5.5 TB global)
            from repro.dist.sharding import _AXIS_SIZES
            per_dev = 0
            for leaf, spec in zip(jax.tree.leaves(cache_shapes),
                                  jax.tree.leaves(specs,
                                                  is_leaf=lambda x: isinstance(x, P))):
                div = 1
                for ax in spec:
                    if ax is None:
                        continue
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        div *= _AXIS_SIZES.get(a, 1)
                per_dev += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // div
            if per_dev > 4 * 2**30:
                cache_dtype = jnp.float8_e4m3fn
                cache_shapes = jax.eval_shape(
                    partial(model.init_cache, b, max_len, cache_dtype))
                specs = cache_pspecs(cfg, cache_shapes, rules)
            cache_sh = _named(mesh, specs)

            def serve_step(params, tokens, cache, index):
                return model.decode_step(params, tokens, cache, index)

            jitted = jax.jit(
                serve_step,
                in_shardings=(params_sh, batch_sh["tokens"], cache_sh,
                              NamedSharding(mesh, P())),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_shapes, batch_shapes["tokens"],
                                   cache_shapes,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mf = model_flops_for(cfg, kind, shape.seq_len, shape.global_batch)
    report = analyze_compiled(compiled, arch=arch, shape=shape_name,
                              mesh_name=mesh_name, chips=chips, model_flops=mf)
    out = report.to_dict()
    # true per-device HBM need: arguments + temps + (outputs - donated alias)
    hbm_need = (float(getattr(mem, "argument_size_in_bytes", 0))
                + float(getattr(mem, "temp_size_in_bytes", 0))
                + float(getattr(mem, "output_size_in_bytes", 0))
                - float(getattr(mem, "alias_size_in_bytes", 0)))
    out.update(kind=kind, lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1), hbm_need=hbm_need, ok=True)
    if verbose:
        hbm_gib = hbm_need / 2**30
        print(f"[{arch} x {shape_name} @ {mesh_name}] OK  "
              f"args={out['argument_bytes']/2**30:.2f}GiB "
              f"need={hbm_gib:.2f} / 16 GiB HBM")
        print(f"  memory_analysis: {mem}")
        print(f"  terms: compute={out['t_compute']*1e3:.2f}ms "
              f"memory={out['t_memory']*1e3:.2f}ms "
              f"collective={out['t_collective']*1e3:.2f}ms "
              f"-> dominant={out['dominant']} "
              f"roofline_frac={out['roofline_fraction']:.3f} "
              f"useful_flops={out['useful_flops_ratio']:.3f}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--metrics-out", default=None, dest="metrics_out",
                    help="emit per-cell roofline terms as obs-style "
                         "JSONL gauges (dryrun.* names, labelled by "
                         "arch/shape/mesh)")
    args = ap.parse_args(argv)
    init_compile_cache()

    todo = []
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        if args.shape == "long_500k" and args.arch not in LONG_CONTEXT_ARCHS:
            print(f"SKIP {args.arch} x long_500k: full-attention arch "
                  "(see DESIGN.md §Arch-applicability)")
            return 0
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    failures = 0
    for arch, shape_name in todo:
        for mp in meshes:
            try:
                results.append(lower_cell(arch, shape_name, mp))
            except Exception as e:  # a dry-run failure is a bug in the system
                failures += 1
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if mp else "16x16",
                                "ok": False, "error": repr(e)})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out} ({len(results)} cells, {failures} failures)")
    if args.metrics_out:
        from repro.obs import MetricRegistry

        reg = MetricRegistry()
        for r in results:
            labels = {"arch": r.get("arch", "?"), "shape": r.get("shape", "?"),
                      "mesh": r.get("mesh", "?")}
            reg.gauge("dryrun.ok", **labels).set(1.0 if r.get("ok") else 0.0)
            for key in ("t_compute", "t_memory", "t_collective",
                        "roofline_fraction", "useful_flops_ratio",
                        "hbm_need"):
                if key in r:
                    reg.gauge(f"dryrun.{key}", **labels).set(float(r[key]))
        print(f"metrics -> {reg.write_jsonl(args.metrics_out)}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
