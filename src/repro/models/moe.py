"""Mixture-of-Experts layer — the in-core mirror of the paper's dispatcher.

Token->expert dispatch is a fork-join scatter/gather, exactly the shape of
Cppless's task->worker dispatch: serialize (pack tokens into capacity
buffers), dispatch (to the expert-parallel `model` mesh axis), execute,
gather (combine weighted by router gates), with *drops* (capacity overflow)
playing the role of load imbalance.

Implementation: `shard_map` over the whole mesh.  Activations enter
replicated across the `model` axis (TP-style), so each model shard already
holds every local token; it packs buffers only for the experts it owns,
runs them, scatters back its partial output, and a psum over `model`
combines — the same collective the dense TP MLP uses, so MoE costs one
psum extra nothing.  Per-shard sort-based packing keeps everything static-
shaped (capacity C per expert) and jit/grad-safe.

On a (1, 1) mesh (CPU smoke tests) every collective degenerates to identity
and the code path is identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init


def moe_init(key, d_model: int, d_ff: int, n_experts: int, act: str, dtype,
             stack: tuple[int, ...] = ()):
    ks = jax.random.split(key, 4)
    pre = stack
    ps = ("layers",) * len(stack)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(
        ks[0], (*pre, d_model, n_experts), (*ps, "embed", None), dtype)
    glu = act in ("swiglu", "geglu")
    p["wi"], s["wi"] = dense_init(
        ks[1], (*pre, n_experts, d_model, d_ff),
        (*ps, "experts", None, "moe_ff"), dtype)
    if glu:
        p["wg"], s["wg"] = dense_init(
            ks[2], (*pre, n_experts, d_model, d_ff),
            (*ps, "experts", None, "moe_ff"), dtype)
    p["wo"], s["wo"] = dense_init(
        ks[3], (*pre, n_experts, d_ff, d_model),
        (*ps, "experts", "moe_ff", None), dtype)
    return p, s


def _expert_mlp(p_local, h, act):
    """p_local: (E_l, d, f) weights; h: (E_l, C, d) packed tokens."""
    up = jnp.einsum("ecd,edf->ecf", h, p_local["wi"])
    if "wg" in p_local:
        g = jnp.einsum("ecd,edf->ecf", h, p_local["wg"])
        g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
        up = up * g
    else:
        up = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", up, p_local["wo"])


def _moe_local_ep(p, x, *, n_experts, top_k, capacity_factor, act,
                  model_axis, token_axes, model_size):
    """Expert-parallel all_to_all body — tokens sharded over `model` too.

    x: (B_local, S_local, d) with S_local = S / model_size.  Dispatch is a
    REAL exchange (two all_to_alls of capacity buffers) instead of the
    replicated-compute + psum combine: wire per layer drops from
    2·T_l·d·(g-1)/g (the psum) to 2·k·T_l/g·d — ~8x for top-2 on a 16-way
    axis — and router/pack work stops being replicated 16x.
    This is the paper's dispatcher in miniature: pack task payloads into
    per-worker capacity buffers, ship, execute, ship back, merge.
    """
    bl, sl, d = x.shape
    t = bl * sl
    e = n_experts
    m = model_size
    e_l = p["wi"].shape[0]
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), 0)
    density_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_prob)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # capacity_factor <= 0 => dropless: capacity covers the worst case
    # (every token lists this expert in its top-k), so routing of one
    # token can never evict another's — the serving path uses this to keep
    # logits batch-composition-invariant (training keeps finite capacity).
    cap = (int(t) if capacity_factor <= 0
           else int(max(top_k, round(t * top_k / e * capacity_factor))))
    ids_flat = ids.reshape(-1)
    order = jnp.argsort(ids_flat)
    sorted_eid = ids_flat[order]
    sorted_tok = order // top_k
    sorted_gate = gates.reshape(-1)[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(e))
    pos = jnp.arange(t * top_k) - starts[sorted_eid]
    keep = pos < cap
    slot = sorted_eid * cap + jnp.where(keep, pos, 0)

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xf[sorted_tok], 0))
    buf = buf.reshape(e, cap, d)

    # ---- ship: (E, C, d) -> (E_l, m*C, d): my experts, everyone's tokens
    recv = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                              tiled=True)
    yrecv = _expert_mlp(p, recv, act)
    # ---- ship back: (E_l, m*C, d) -> (E, C, d) rows for my local tokens
    ybuf = jax.lax.all_to_all(yrecv, model_axis, split_axis=1,
                              concat_axis=0, tiled=True)

    contrib = ybuf.reshape(e * cap, d)[slot] * \
        jnp.where(keep, sorted_gate, 0.0)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[sorted_tok].add(contrib)

    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    red_axes = tuple(token_axes) + (model_axis,)
    aux, zloss, drop_frac = (jax.lax.pmean(v, red_axes)
                             for v in (aux, zloss, drop_frac))
    return y.reshape(bl, sl, d), aux, zloss, drop_frac


def _moe_local(p, x, *, n_experts, top_k, capacity_factor, act,
               model_axis, token_axes):
    """shard_map body.  x: (B_local, S, d) — replicated over `model`."""
    bl, s, d = x.shape
    t = bl * s
    e = n_experts
    xf = x.reshape(t, d)

    # ---- route (replicated compute; every model shard agrees)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)                    # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # aux losses: load balance (Switch) + router z-loss
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), 0)
    density_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_prob)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # ---- pack: sort (token, k) slots by expert id
    # capacity_factor <= 0 => dropless: capacity covers the worst case
    # (every token lists this expert in its top-k), so routing of one
    # token can never evict another's — the serving path uses this to keep
    # logits batch-composition-invariant (training keeps finite capacity).
    cap = (int(t) if capacity_factor <= 0
           else int(max(top_k, round(t * top_k / e * capacity_factor))))
    ids_flat = ids.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(ids_flat)
    sorted_eid = ids_flat[order]
    sorted_tok = order // top_k
    sorted_gate = gates.reshape(-1)[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(e))
    pos = jnp.arange(t * top_k) - starts[sorted_eid]
    keep = pos < cap
    slot = sorted_eid * cap + jnp.where(keep, pos, 0)

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xf[sorted_tok], 0))
    buf = buf.reshape(e, cap, d)

    # ---- execute only the experts this model shard owns
    midx = jax.lax.axis_index(model_axis)
    e_l = p["wi"].shape[0]                       # local expert count
    e0 = midx * e_l
    mybuf = jax.lax.dynamic_slice_in_dim(buf, e0, e_l, axis=0)
    yebuf = _expert_mlp(p, mybuf, act)           # (E_l, C, d)

    # ---- combine: scatter-add my experts' outputs, psum over model
    ybuf = jnp.zeros((e, cap, d), yebuf.dtype)
    ybuf = jax.lax.dynamic_update_slice_in_dim(ybuf, yebuf, e0, axis=0)
    contrib = ybuf.reshape(e * cap, d)[slot] * \
        jnp.where(keep, sorted_gate, 0.0)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[sorted_tok].add(contrib)
    y = jax.lax.psum(y, model_axis)

    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux, zloss, drop_frac = (
        (jax.lax.pmean(m, token_axes) if token_axes else m)
        for m in (aux, zloss, drop_frac))
    return y.reshape(bl, s, d), aux, zloss, drop_frac


def moe_apply(p, x, *, n_experts: int, top_k: int, capacity_factor: float,
              act: str, mesh=None, model_axis: str = "model",
              impl: str = "replicated"):
    """x (B,S,d) -> (y (B,S,d), metrics dict).  Requires a mesh (a (1,1)
    trivial mesh is built for un-meshed CPU smoke runs).

    impl: "replicated" — activations replicated over `model`, psum combine
          (the TP-compatible baseline); "ep_a2a" — tokens seq-sharded over
          `model`, two all_to_alls (the §Perf expert-parallel path).
    """
    if mesh is None:
        from ..sharding import current_rules
        rules = current_rules()
        if rules is not None:
            mesh = rules.mesh
        else:
            import numpy as np
            mesh = jax.sharding.Mesh(
                np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    token_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bdim = token_axes if token_axes else None
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis]

    pspec = {k: P(model_axis, *(None,) * (v.ndim - 1)) for k, v in p.items()
             if k != "router"}
    pspec["router"] = P()

    if impl == "ep_a2a" and x.shape[1] % msize == 0:
        body = functools.partial(
            _moe_local_ep, n_experts=n_experts, top_k=top_k,
            capacity_factor=capacity_factor, act=act,
            model_axis=model_axis, token_axes=token_axes, model_size=msize)
        y, aux, zloss, drop = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(bdim, model_axis, None)),
            out_specs=(P(bdim, model_axis, None), P(), P(), P()),
            check_vma=False,
        )(p, x)
    else:
        body = functools.partial(
            _moe_local, n_experts=n_experts, top_k=top_k,
            capacity_factor=capacity_factor, act=act,
            model_axis=model_axis, token_axes=token_axes)
        y, aux, zloss, drop = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(bdim, None, None)),
            out_specs=(P(bdim, None, None), P(), P(), P()),
            check_vma=False,
        )(p, x)
    metrics = {"moe_aux": aux, "moe_zloss": zloss, "moe_drop": drop}
    return y, metrics
