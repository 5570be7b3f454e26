"""Gated MLP (SwiGLU/GeGLU) and the MoE layer (shared + routed experts,
GShard-style capacity dispatch via one-hot einsums — EP-shardable: the expert
dim maps to the ``experts`` logical axis)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelCfg, MoECfg
from ..parallel.api import shard
from .common import act_fn, ninit


# -- dense gated MLP ----------------------------------------------------------


def init_mlp(key, cfg: ModelCfg, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": ninit(ks[0], (d, f)),
        "w_up": ninit(ks[1], (d, f)),
        "w_down": ninit(ks[2], (f, d), scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }


def specs_mlp():
    return {"w_gate": ("embed_tp", "ff"), "w_up": ("embed_tp", "ff"), "w_down": ("ff", "embed_tp")}


def mlp_forward(p, x, cfg: ModelCfg):
    act = act_fn(cfg.act)
    h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = shard(h, "batch", "seq", "act_ff")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# -- MoE -----------------------------------------------------------------------


def init_moe(key, cfg: ModelCfg):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": ninit(ks[0], (d, m.n_routed), dtype=jnp.float32),
        "w_gate": ninit(ks[1], (m.n_routed, d, m.d_ff_expert)),
        "w_up": ninit(ks[2], (m.n_routed, d, m.d_ff_expert)),
        "w_down": ninit(ks[3], (m.n_routed, m.d_ff_expert, d)),
    }
    if m.n_shared:
        f_sh = m.d_ff_shared or m.n_shared * m.d_ff_expert
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": ninit(kss[0], (d, f_sh)),
            "w_up": ninit(kss[1], (d, f_sh)),
            "w_down": ninit(kss[2], (f_sh, d)),
        }
    return p


def specs_moe(cfg: ModelCfg):
    p = {
        "router": ("embed_tp", None),
        "w_gate": ("experts", "embed_tp", "ff_expert"),
        "w_up": ("experts", "embed_tp", "ff_expert"),
        "w_down": ("experts", "ff_expert", "embed_tp"),
    }
    if cfg.moe.n_shared:
        p["shared"] = {"w_gate": ("embed_tp", "ff"), "w_up": ("embed_tp", "ff"),
                       "w_down": ("ff", "embed_tp")}
    return p


def moe_forward(p, x, cfg: ModelCfg):
    """Returns (y, aux_loss).  Dispatches to the expert-parallel shard_map
    path when the active sharding rules enable it (``_moe_ep``); otherwise
    runs the single-shard sort-based dispatch below."""
    from ..parallel.api import current_mesh, current_rules

    rules = current_rules()
    mesh = current_mesh()
    if rules is not None and mesh is not None and rules.rules.get("_moe_ep"):
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_model = mesh.shape.get("model", 1)
        mdl_ok = ("model" not in mesh.axis_names
                  or cfg.moe.n_routed % n_model == 0
                  or cfg.moe.d_ff_expert % n_model == 0)
        if dp and mdl_ok and x.shape[0] % _prod(mesh.shape[a] for a in dp) == 0:
            return moe_forward_ep(p, x, cfg, mesh, dp)
    return moe_forward_local(p, x, cfg)


def _prod(xs):
    out = 1
    for v in xs:
        out *= v
    return out


def _is_spec_leaf(t):
    return isinstance(t, tuple) and all(e is None or isinstance(e, str) for e in t)


def moe_forward_ep(p, x, cfg: ModelCfg, mesh, dp_axes):
    """Expert-parallel MoE under a *full-manual* shard_map:

      * tokens stay sharded over the dp axes — each shard routes only its
        local tokens, so the global argsort/scatter collectives of the
        GSPMD lowering disappear entirely;
      * experts shard over ``model`` (E % model == 0: each shard dispatches
        into its own expert range and the per-token outputs combine with one
        psum); otherwise the expert FF dim shards over ``model`` (TP inside
        every expert, same single psum);
      * FSDP weight gathering is explicit (all_gather over ``data``;
        backward reduce-scatters — identical traffic to any FSDP layer).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.api import current_rules

    m = cfg.moe
    rules = current_rules()
    manual = set(mesh.axis_names)
    mdl = "model" if "model" in mesh.axis_names else None
    n_model = mesh.shape.get("model", 1)
    shard_experts = mdl is not None and m.n_routed % n_model == 0
    shard_ff = mdl is not None and not shard_experts and m.d_ff_expert % n_model == 0

    specs = specs_moe(cfg)
    if not shard_experts:
        # expert dim replicates; TP moves inside each expert (ff_expert)
        def retarget(t):
            return tuple((None if ax == "experts" else ax) for ax in t)
        specs = jax.tree.map(retarget, specs, is_leaf=_is_spec_leaf)

    def resolve_manual(t):
        axes = []
        for ax in t:
            mm = rules.rules.get(ax) if ax else None
            if ax == "experts" and shard_experts:
                mm = mdl
            if ax == "ff_expert" and shard_ff:
                mm = mdl
            if isinstance(mm, str):
                mm = (mm,)
            keep = tuple(a for a in (mm or ()) if a in manual)
            axes.append(keep if len(keep) > 1 else (keep[0] if keep else None))
        return P(*axes)

    p_specs = jax.tree.map(resolve_manual, specs, is_leaf=_is_spec_leaf)

    def gather_fsdp(w, t):
        for dim, ax in enumerate(t):
            mm = rules.rules.get(ax) if ax else None
            if isinstance(mm, str):
                mm = (mm,)
            for a in (mm or ()):
                if a in ("data", "pod"):
                    w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
        return w

    def body(p_sh, xs):
        pl = jax.tree.map(gather_fsdp, p_sh, specs, is_leaf=_is_spec_leaf)
        if shard_experts:
            e_local = m.n_routed // n_model
            e_off = jax.lax.axis_index(mdl) * e_local
            y, aux = _moe_compute(pl, xs, cfg, e_off=e_off, e_local=e_local,
                                  ff_psum_axis=None)
        else:
            y, aux = _moe_compute(pl, xs, cfg, e_off=0, e_local=m.n_routed,
                                  ff_psum_axis=mdl if shard_ff else None)
        if mdl is not None and (shard_experts or shard_ff):
            y = jax.lax.psum(y, mdl)
        for a in dp_axes:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    fm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, P(dp_axes)),
        out_specs=(P(dp_axes), P()),
        axis_names=manual,
        check_vma=False,
    )
    return fm(p, x)


def _moe_compute(p, x, cfg: ModelCfg, *, e_off, e_local: int, ff_psum_axis):
    """Sort-based dispatch restricted to the local expert range
    [e_off, e_off + e_local); expert weights ``p`` hold only that range
    (or an ff-slice of all experts when ``ff_psum_axis`` combines TP
    partials).  Shared experts are ff-sharded alongside.  The caller psums
    the result over the model axis."""
    import jax

    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    K = m.top_k
    E = m.n_routed
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(-(-T * K * m.capacity_factor // E)))

    e_flat = idx.reshape(T * K)
    tok_flat = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    same = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            (e_sorted[1:] == e_sorted[:-1]).astype(jnp.int32)])
    seg_pos = jax.lax.associative_scan(
        lambda a, b: (a[0] * b[0], b[1] + b[0] * a[1]),
        (same, jnp.ones_like(same)),
    )[1] - 1
    e_rel = e_sorted - e_off
    keep = (seg_pos < cap) & (e_rel >= 0) & (e_rel < e_local)
    slot = jnp.where(keep, e_rel * cap + seg_pos, e_local * cap)

    buf = jnp.zeros((e_local * cap + 1, D), xt.dtype).at[slot].set(xt[tok_sorted])
    xe = buf[:-1].reshape(e_local, cap, D)

    act = act_fn(cfg.act)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(e_local * cap, D)
    ye = jnp.concatenate([ye, jnp.zeros((1, D), ye.dtype)], axis=0)

    out_sorted = ye[slot]
    gates_sorted = (gate_vals.reshape(T * K)[order] * keep).astype(jnp.float32)
    contrib = out_sorted.astype(jnp.float32) * gates_sorted[:, None]
    y = jnp.zeros((T, D), jnp.float32).at[tok_sorted].add(contrib).astype(x.dtype)

    if m.n_shared:
        # shared experts arrive ff-sharded over ``model`` (gated MLP is
        # elementwise in ff; w_down contracts the local slice), so their
        # contribution is a partial sum — the caller's psum makes it exact.
        sh = p["shared"]
        hs = act(jnp.einsum("td,df->tf", xt, sh["w_gate"])) * \
            jnp.einsum("td,df->tf", xt, sh["w_up"])
        y = y + jnp.einsum("tf,fd->td", hs, sh["w_down"])

    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0 / (T * K))
    aux = m.router_aux_coef * E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux


def moe_forward_local(p, x, cfg: ModelCfg):
    """Sort-based dispatch (Megablocks-style, O(T·k) gathers + an (E,C,D)
    buffer — no (T,E,C) one-hot tensor): token-slots are sorted by expert id,
    each expert keeps its first C arrivals (capacity ``cf·T·k/E``), dropped
    slots fall through on the residual path.  The expert dim maps to the
    ``experts`` logical axis for expert parallelism."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    K = m.top_k
    E = m.n_routed
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)                       # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(-(-T * K * m.capacity_factor // E)))

    e_flat = idx.reshape(T * K)                                     # expert of each slot
    tok_flat = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    # position within the expert's queue
    same = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            (e_sorted[1:] == e_sorted[:-1]).astype(jnp.int32)])
    seg_pos = jax.lax.associative_scan(
        lambda a, b: (a[0] * b[0], b[1] + b[0] * a[1]),
        (same, jnp.ones_like(same)),
    )[1] - 1
    keep = seg_pos < cap
    slot = jnp.where(keep, e_sorted * cap + seg_pos, E * cap)       # overflow -> dump row

    # scatter tokens into the expert buffer
    buf = jnp.zeros((E * cap + 1, D), xt.dtype).at[slot].set(xt[tok_sorted])
    xe = buf[:-1].reshape(E, cap, D)
    xe = shard(xe, "experts", None, None)

    act = act_fn(cfg.act)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = shard(h, "experts", None, "ff_expert")
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * cap, D)
    ye = jnp.concatenate([ye, jnp.zeros((1, D), ye.dtype)], axis=0)

    # combine: gather each slot's output back to its token, gate-weighted
    out_sorted = ye[slot]                                           # (T*K, D)
    gates_sorted = (gate_vals.reshape(T * K)[order] * keep).astype(jnp.float32)
    contrib = out_sorted.astype(jnp.float32) * gates_sorted[:, None]
    y = jnp.zeros((T, D), jnp.float32).at[tok_sorted].add(contrib).astype(x.dtype)

    if m.n_shared:
        sh = p["shared"]
        hs = act(jnp.einsum("td,df->tf", xt, sh["w_gate"])) * jnp.einsum("td,df->tf", xt, sh["w_up"])
        y = y + jnp.einsum("tf,fd->td", hs, sh["w_down"])

    # load-balancing aux loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0 / (T * K))
    aux = m.router_aux_coef * E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux
