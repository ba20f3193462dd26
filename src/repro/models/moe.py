"""Mixture-of-Experts with sort-based, capacity-bounded dispatch.

The dispatch is the same machinery as the paper's conflict resolution
(DESIGN.md §5): tokens are *sorted by expert id* (linearization ordering),
per-expert runs become segments, and each expert processes a fixed-capacity
contiguous slab. FLOPs scale with E x C x d x ff = active-expert FLOPs x
capacity_factor — so the roofline "useful compute" ratio stays honest (a
dense-dispatch einsum would inflate HLO FLOPs by num_experts/top_k).

Experts shard over the ``model`` mesh axis (expert parallelism); the dispatch
gather/scatter lowers to all-to-all-free intra-shard ops because the slab dim
is sharded with the experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .modules import linear_init, Rng


def moe_init(rng: Rng, cfg, dtype):
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    scale_in = d ** -0.5
    scale_out = ff ** -0.5 / (2 * cfg.num_layers) ** 0.5
    p = {
        "router": linear_init(rng, d, e, dtype=dtype),
        # stacked expert weights: (E, d, ff) / (E, ff, d)
        "wi": {"w": _expert_w(rng, e, d, ff, dtype, scale_in)},
        "wg": {"w": _expert_w(rng, e, d, ff, dtype, scale_in)},
        "wo": {"w": _expert_w(rng, e, ff, d, dtype, scale_out)},
    }
    if cfg.num_shared_experts:
        sff = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared_wi"] = linear_init(rng, d, sff, dtype=dtype)
        p["shared_wg"] = linear_init(rng, d, sff, dtype=dtype)
        p["shared_wo"] = linear_init(rng, sff, d, dtype=dtype, scale=scale_out)
    return p


def _expert_w(rng: Rng, e, a, b, dtype, scale):
    from .modules import normal
    return normal(rng, (e, a, b), dtype, scale)


def moe_apply(p, cfg, x):
    """x: (B,S,D) -> (B,S,D). Top-k routing, capacity-bounded sort dispatch."""
    compute_dtype = x.dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)                  # (T,k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch (the BLCO sort+segment pattern) ----------------
    flat_e = top_e.reshape(-1)                              # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_g = top_g.reshape(-1)
    order = jnp.argsort(flat_e)                             # group by expert
    se, stok, sg = flat_e[order], flat_tok[order], flat_g[order]

    cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
    # position of each routed token within its expert's slab
    ones = jnp.ones_like(se)
    pos_in_e = jnp.cumsum(ones) - 1
    first_of_e = jnp.full((e,), t * k, pos_in_e.dtype).at[se].min(pos_in_e)
    slot = pos_in_e - first_of_e[se]
    keep = slot < cap                                       # overflow drops

    # dispatch: (E, C, D) slabs
    slabs = jnp.zeros((e, cap, d), compute_dtype)
    safe_slot = jnp.where(keep, slot, cap - 1)
    slabs = slabs.at[se, safe_slot].add(
        jnp.where(keep[:, None], xt[stok].astype(compute_dtype), 0))

    # expert FFN (swiglu) on slabs: E x C x d x ff
    wi = p["wi"]["w"].astype(compute_dtype)
    wg = p["wg"]["w"].astype(compute_dtype)
    wo = p["wo"]["w"].astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", slabs, wg)) * \
        jnp.einsum("ecd,edf->ecf", slabs, wi)
    out_slabs = jnp.einsum("ecf,efd->ecd", h, wo)

    # combine: gather back + gate weight, one scatter-add per routed token
    gathered = out_slabs[se, safe_slot]
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = jnp.zeros((t, d), compute_dtype).at[stok].add(
        gathered * sg[:, None].astype(compute_dtype))

    if cfg.num_shared_experts:
        from .modules import linear
        sh = jax.nn.silu(linear(p["shared_wg"], xt)) * linear(p["shared_wi"], xt)
        combined = combined + linear(p["shared_wo"], sh)

    # router z-loss / aux load-balancing loss (returned for the trainer)
    me = gates.mean(axis=0)                                  # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
    aux_loss = e * jnp.sum(me * ce)
    return combined.reshape(b, s, d).astype(x.dtype), aux_loss


# ------------------------------------------------------- SPMD (shard_map) path
def moe_apply_sharded(p, cfg, x, mesh):
    """Expert-parallel MoE for the production mesh (DESIGN.md §4).

    Layout: tokens manual over (pod, data) (batch dim); experts OWNED along
    ``model`` (each model shard holds E/tp experts, full d x ff each — no TP
    inside an expert). Activations entering the block are replicated across
    the model axis (post-TP-all-reduce), so each model shard can locally
    gate + select the tokens routed to *its* experts, run them, and the
    per-token combine is a single psum over ``model`` — no all-to-all at all.
    Expert weights stay ZeRO-sharded over data outside; GSPMD all-gathers
    them at entry (that is the FSDP all-gather, visible in the dry-run).
    """
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    tp_size = mesh.shape["model"]
    e, k = cfg.num_experts, cfg.top_k
    assert e % tp_size == 0, (e, tp_size)
    e_local = e // tp_size

    xspec = P(dp, None, None)                  # batch manual over data axes
    wspec_in = P("model", None, None)          # experts owned along model
    wspec_out = P("model", None, None)
    rspec = P()                                # router replicated

    def block(xl, router_w, wi, wg, wo):
        # xl: (B_local, S, D); wi/wg/wo: (E_local, ., .)
        bl, s, d = xl.shape
        t = bl * s
        xt = xl.reshape(t, d)
        my_col = jax.lax.axis_index("model")

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_e = jax.lax.top_k(gates, k)
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

        cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
        flat_e = top_e.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        flat_g = top_g.reshape(-1)
        # tokens routed to experts owned by this model column
        local_e = flat_e - my_col * e_local
        mine = (local_e >= 0) & (local_e < e_local)
        order = jnp.argsort(jnp.where(mine, local_e, e_local))
        se = jnp.where(mine, local_e, e_local)[order]
        stok = flat_tok[order]
        sg = flat_g[order]
        pos = jnp.cumsum(jnp.ones_like(se)) - 1
        first = jnp.full((e_local + 1,), t * k, pos.dtype).at[se].min(pos)
        slot = pos - first[se]
        keep = (slot < cap) & (se < e_local)
        safe_e = jnp.minimum(se, e_local - 1)
        safe_slot = jnp.where(keep, slot, cap - 1)

        slabs = jnp.zeros((e_local, cap, d), xl.dtype)
        slabs = slabs.at[safe_e, safe_slot].add(
            jnp.where(keep[:, None], xt[stok], 0))

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", slabs,
                                   wg.astype(xl.dtype))) * \
            jnp.einsum("ecd,edf->ecf", slabs, wi.astype(xl.dtype))
        out_slabs = jnp.einsum("ecf,efd->ecd", h, wo.astype(xl.dtype))

        gathered = jnp.where(keep[:, None], out_slabs[safe_e, safe_slot], 0)
        combined = jnp.zeros((t, d), xl.dtype).at[stok].add(
            gathered * sg[:, None].astype(xl.dtype))
        combined = jax.lax.psum(combined, "model")   # one collective

        me_ = gates.mean(axis=0)
        ce_ = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
        aux = e * jnp.sum(me_ * ce_)
        aux = jax.lax.pmean(aux, dp)                 # replicate for out_spec
        return combined.reshape(bl, s, d), aux

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(xspec, rspec, wspec_in, wspec_in, wspec_out),
        out_specs=(xspec, P()),
        check_vma=False)
    out, aux = fn(x, p["router"]["w"], p["wi"]["w"], p["wg"]["w"], p["wo"]["w"])

    if cfg.num_shared_experts:
        from .modules import linear
        sh = jax.nn.silu(linear(p["shared_wg"], x)) * linear(p["shared_wi"], x)
        out = out + linear(p["shared_wo"], sh)
    return out.astype(x.dtype), aux.astype(jnp.float32)
