"""Multi-device prog: mini dry-run (8 devices, smoke configs) — lowers and
compiles train/prefill/decode for a representative arch of each family."""
import jax, jax.numpy as jnp, dataclasses
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import SHAPES, get_smoke, input_specs
from repro.dist.sharding import (batch_pspecs, cache_pspecs, make_rules_for,
                                 param_pspecs, set_axis_sizes, use_rules)
from repro.launch.mesh import make_host_mesh
from repro.models.model import CausalLM
from repro.optim.adamw import AdamWConfig, init_state
from repro.train.step import make_train_step

mesh = make_host_mesh(model_axis=2)            # (4, 2) over 8 devices
set_axis_sizes(mesh)
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                               is_leaf=lambda x: isinstance(x, P))
train = dataclasses.replace(SHAPES["train_4k"], seq_len=128, global_batch=8)
dec = dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=8)
for arch in ["gemma2-2b", "deepseek-moe-16b", "rwkv6-3b", "zamba2-2.7b"]:
    cfg = get_smoke(arch)
    model = CausalLM(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = make_rules_for(cfg, mesh, kind="train")
    psh = named(param_pspecs(params_shapes, rules))
    bs = input_specs(cfg, train)
    bsh = named(batch_pspecs(cfg, bs, rules))
    opt_shapes = jax.eval_shape(init_state, params_shapes)
    osh = {"m": psh, "v": psh, "count": NamedSharding(mesh, P())}
    with use_rules(rules, mesh), mesh:
        jax.jit(make_train_step(model, AdamWConfig()),
                in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())),
                out_shardings=(psh, osh, None), donate_argnums=(0, 1)).lower(
            params_shapes, opt_shapes, bs,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
    rules = make_rules_for(cfg, mesh, kind="decode")
    psh = named(param_pspecs(params_shapes, rules))
    bs = input_specs(cfg, dec)
    bsh = named(batch_pspecs(cfg, bs, rules))
    cache_shapes = jax.eval_shape(partial(model.init_cache, 8, 64, jnp.bfloat16))
    csh = named(cache_pspecs(cfg, cache_shapes, rules))
    with use_rules(rules, mesh), mesh:
        jax.jit(model.decode_step,
                in_shardings=(psh, bsh["tokens"], csh, NamedSharding(mesh, P())),
                out_shardings=(None, csh), donate_argnums=(2,)).lower(
            params_shapes, bs["tokens"], cache_shapes,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
    print(f"{arch} ok")
print("DRYRUN_SMOKE_OK")
