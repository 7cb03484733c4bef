"""Fine-grained Mixture-of-Experts (deepseek-moe-16b, moonshot-v1-16b-a3b).

Shared experts (always on) + routed experts with top-k token-choice routing
and sort-based capacity dispatch: tokens are packed into fixed-size
(E, C, D) expert buffers — the same fixed-bucket idea as the paper's tiles
(DESIGN.md §5): padding waste buys perfectly regular, shardable compute.
Experts are sharded over the "model" mesh axis (EP); the dispatch/combine
scatters become all-to-alls under SPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import MoEConfig
from .layers import param_init, shard
from .mlp import init_mlp, mlp


def init_moe(key, d_model: int, d_ff: int, cfg: MoEConfig, kind: str,
             dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e = cfg.n_experts
    p = {
        "router": param_init(ks[0], (d_model, e), scale=0.006, dtype=dtype),
        "up": param_init(ks[1], (e, d_model, d_ff), dtype=dtype),
        "down": param_init(ks[2], (e, d_ff, d_model), dtype=dtype),
    }
    if kind in ("swiglu", "geglu"):
        p["gate"] = param_init(ks[3], (e, d_model, d_ff), dtype=dtype)
    if cfg.n_shared:
        shared = init_mlp(ks[4], d_model, d_ff * cfg.n_shared, kind, dtype)
        p["shared_up"] = shared["up"]
        p["shared_down"] = shared["down"]
        if "gate" in shared:
            p["shared_gate"] = shared["gate"]
    return p


def _expert_ffn(p, h, kind: str):
    """h: (E, C, D) -> (E, C, D), batched einsum over experts."""
    dt = h.dtype
    up = jnp.einsum("ecd,edf->ecf", h, p["up"].astype(dt))
    if kind == "swiglu":
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["gate"].astype(dt)))
        act = g * up
    elif kind == "geglu":
        g = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", h, p["gate"].astype(dt)), approximate=True
        )
        act = g * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return jnp.einsum("ecf,efd->ecd", act, p["down"].astype(dt))


def moe_ffn(p, x, cfg: MoEConfig, kind: str):
    """x: (B, S, D) -> (out, aux_loss).

    Sort-based dispatch: assignments sorted by expert id, position-in-expert
    computed with a searchsorted trick, overflow beyond capacity dropped
    (GShard semantics).
    """
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    tokens = x.reshape(n, d)

    # --- routing (float32 for numerics) -------------------------------
    rl = (tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(rl, axis=-1)                       # (N, E)
    top_w, top_e = jax.lax.top_k(probs, k)                    # (N, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)    # renormalise

    # load-balancing aux loss (Switch-style).  tokens/expert counted with a
    # scatter-add, NOT a (N, k, E) one-hot — at 1M prefill tokens the one-hot
    # is gigabytes.
    me = jnp.mean(probs, axis=0)                              # (E,)
    counts = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    ce = counts / n                                           # tokens/expert
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce) / k

    # --- sort-based capacity dispatch ----------------------------------
    cap = int(cfg.capacity_factor * n * k / e + 0.999)
    cap = max(8, cap)
    flat_e = top_e.reshape(-1)                                # (N*k,)
    flat_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1).astype(x.dtype)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    seg_start = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(n * k, dtype=jnp.int32) - seg_start
    keep = pos < cap
    dest = jnp.where(keep, se * cap + pos, e * cap)           # overflow slot

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dest].set(jnp.where(keep[:, None], tokens[st], 0.0))
    hidden = shard(buf[:-1].reshape(e, cap, d), "experts", None, None)

    out_buf = _expert_ffn(p, hidden, kind)
    out_buf = shard(out_buf, "experts", None, None).reshape(e * cap, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), x.dtype)], axis=0)

    contrib = out_buf[dest] * sw[:, None]
    routed = jnp.zeros((n, d), x.dtype).at[st].add(contrib)

    out = routed
    if "shared_up" in p:
        sp = {"up": p["shared_up"], "down": p["shared_down"]}
        if "shared_gate" in p:
            sp["gate"] = p["shared_gate"]
        out = out + mlp(sp, tokens[None], kind)[0]
    return out.reshape(b, s, d), aux


# ==========================================================================
# Expert-parallel path: shard_map dispatch with all-to-all over "model"
# ==========================================================================
#
# The GSPMD-global dispatch above is correct but catastrophic at scale: the
# (N*k, d) gather, the (E*C, d) scatter and the global argsort all
# materialise on every device (measured: 330 GiB/device and a 236 s
# collective term for deepseek-moe train_4k — EXPERIMENTS.md §Perf).
#
# The EP path keeps tokens sharded (batch over DP, seq over "model" via SP)
# and experts sharded over "model".  Per device:
#   1. route the LOCAL n_loc tokens (router weights are replicated);
#   2. pack (token, choice) pairs into per-destination-column send buffers
#      of fixed capacity  (tp, C_send, d)  — fixed buckets again: the
#      paper's tile idiom at the transport layer;
#   3. all_to_all over "model"  ->  every column receives the tokens bound
#      for ITS experts;
#   4. local capacity dispatch into (E/tp, C_loc, d), dense expert FFN;
#   5. scatter back into receive order, REVERSE all_to_all, combine with
#      routing weights at the original slots.
# Comm per device = 2 * n_loc * k * d / tp (down from O(N * d)).


def _pack_by(dest, values, n_bins, cap, fill=0.0):
    """Sort-based fixed-capacity packing.

    dest: (M,) int32 bin ids; values: (M, ...) payload.  Returns
    (buf (n_bins, cap, ...), slot (M,) int32 = bin*cap+pos or -1 dropped).
    """
    m = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sd = dest[order]
    seg = jnp.searchsorted(sd, sd, side="left")
    pos = jnp.arange(m, dtype=jnp.int32) - seg
    keep = pos < cap
    slot_sorted = jnp.where(keep, sd * cap + pos, n_bins * cap)
    buf = jnp.full((n_bins * cap + 1,) + values.shape[1:], fill, values.dtype)
    buf = buf.at[slot_sorted].set(jnp.where(
        keep.reshape((-1,) + (1,) * (values.ndim - 1)), values[order], fill))
    # slot per ORIGINAL index
    slot = jnp.full((m,), -1, jnp.int32)
    slot = slot.at[order].set(jnp.where(keep, slot_sorted, -1))
    return buf[:-1].reshape((n_bins, cap) + values.shape[1:]), slot


def moe_ffn_ep(p, x, cfg: MoEConfig, kind: str, mesh, dp_axes, tp_axis="model"):
    """Expert-parallel MoE under shard_map.  x: (B, S, D) -> (out, aux)."""
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes[tp_axis]
    e_loc = e // tp
    all_axes = tuple(mesh.axis_names)

    def body(xb, router, gate, up, down):
        # xb: (b_loc, s_loc, d); router: (d, E) replicated;
        # gate/up/down: (E/tp, ...) local expert shards
        b_loc, s_loc, d = xb.shape
        n_loc = b_loc * s_loc
        toks = xb.reshape(n_loc, d)
        rl = toks.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(rl, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        # aux loss over the GLOBAL batch (pmean across all devices)
        me = jnp.mean(probs, axis=0)
        counts = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
        ce = counts / n_loc
        me = jax.lax.pmean(me, all_axes)
        ce = jax.lax.pmean(ce, all_axes)
        aux = cfg.router_aux_weight * e * jnp.sum(me * ce) / k

        flat_e = top_e.reshape(-1)                      # (n_loc*k,)
        flat_w = top_w.reshape(-1)
        col = flat_e // e_loc                            # destination column
        c_send = max(8, int(cfg.capacity_factor * n_loc * k / tp + 0.999))
        payload = jnp.concatenate([
            jnp.repeat(toks, k, axis=0),
            (flat_e % e_loc).astype(toks.dtype)[:, None],   # local expert id
            jnp.ones((n_loc * k, 1), toks.dtype),            # validity flag
        ], axis=1)
        send, slot = _pack_by(col, payload, tp, c_send)  # (tp, C, d+2)

        recv = jax.lax.all_to_all(send, tp_axis, split_axis=0, concat_axis=0,
                                  tiled=False)            # (tp, C, d+2)
        rtok = recv[..., :d].reshape(tp * c_send, d)
        valid = recv[..., d + 1].reshape(tp * c_send) > 0.5
        rexp = recv[..., d].reshape(tp * c_send).astype(jnp.int32)
        rexp = jnp.where(valid, jnp.clip(rexp, 0, e_loc - 1), e_loc)
        # invalid (padding) rows land in an overflow bin that is sliced off
        c_loc = max(8, int(cfg.capacity_factor * tp * c_send / e_loc + 0.999))
        hidden, hslot = _pack_by(rexp, rtok, e_loc + 1, c_loc)
        hidden = hidden[:e_loc]                           # (E/tp, C_loc, d)

        dt = toks.dtype
        h_up = jnp.einsum("ecd,edf->ecf", hidden, up.astype(dt))
        if kind == "swiglu":
            act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hidden,
                                         gate.astype(dt))) * h_up
        elif kind == "geglu":
            act = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", hidden,
                                         gate.astype(dt)),
                              approximate=True) * h_up
        else:
            act = jax.nn.gelu(h_up, approximate=True)
        h_out = jnp.einsum("ecf,efd->ecd", act, down.astype(dt))

        # back to receive order, then reverse all_to_all.  hslot may point
        # at the overflow bin (>= e_loc*c_loc) — clamp to the zero row.
        flat_out = h_out.reshape(e_loc * c_loc, d)
        flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), dt)], 0)
        hs = jnp.where((hslot >= 0) & (hslot < e_loc * c_loc),
                       hslot, e_loc * c_loc)
        back = flat_out[hs]
        back = back.reshape(tp, c_send, d)
        ret = jax.lax.all_to_all(back, tp_axis, split_axis=0, concat_axis=0,
                                 tiled=False)             # (tp, C, d)
        ret_flat = jnp.concatenate([ret.reshape(tp * c_send, d),
                                    jnp.zeros((1, d), dt)], 0)
        contrib = ret_flat[jnp.where(slot >= 0, slot, tp * c_send)]
        contrib = contrib * flat_w[:, None].astype(dt)
        routed = jnp.zeros((n_loc, d), dt).at[
            jnp.repeat(jnp.arange(n_loc, dtype=jnp.int32), k)].add(contrib)
        return routed.reshape(b_loc, s_loc, d), aux

    dp = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
    x_spec = P(dp, tp_axis, None)
    gate = p.get("gate", p["up"])      # dummy when non-gated (unused)
    routed, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), P(tp_axis, None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], gate, p["up"], p["down"])

    if "shared_up" in p:
        sp = {"up": p["shared_up"], "down": p["shared_down"]}
        if "shared_gate" in p:
            sp["gate"] = p["shared_gate"]
        routed = routed + mlp(sp, x, kind)
    return routed, aux


def moe_ffn_auto(p, x, cfg: MoEConfig, kind: str):
    """EP (shard_map all-to-all) when a mesh is active and shapes divide the
    axes; the GSPMD-global path otherwise (single device, decode s=1,
    oracle tests)."""
    from repro.dist.sharding import _AXIS_SIZES, active_mesh, active_rules

    mesh = active_mesh()
    rules = active_rules() or {}
    if mesh is not None and rules.get("experts") == "model":
        b, s, _ = x.shape
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tp = sizes.get("model", 1)
        dp_axes = rules.get("batch") or ()
        dp = 1
        for a in (dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)):
            dp *= sizes.get(a, 1)
        if (tp > 1 and s % tp == 0 and dp >= 1 and b % max(dp, 1) == 0
                and cfg.n_experts % tp == 0):
            return moe_ffn_ep(p, x, cfg, kind, mesh, dp_axes)
    return moe_ffn(p, x, cfg, kind)
