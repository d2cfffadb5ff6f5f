"""DeepSeekMoE: shared experts + routed top-k with sort-based grouped matmul.

Dispatch is capacity-free and exact: token copies are sorted by expert id and
the expert FFNs run as `jax.lax.ragged_dot` grouped matmuls (the TPU
MegaBlocks analogue). Expert weights are stacked (E, ...) so expert
parallelism is a plain 'model'-axis sharding of the leading dim; the sort is
the same divide-stage responsible-key partitioning as the paper's pipeline
filters (tokens stream to the expert responsible for them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models.layers import activation, mlp_apply, mlp_init


def moe_init(key, cfg: LMConfig, dtype) -> dict:
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff_expert, mo.n_routed
    ks = jax.random.split(key, 5)
    s_in, s_out = d**-0.5, f**-0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    if mo.n_shared:
        p["shared"] = mlp_init(ks[4], d, mo.n_shared * f, cfg.act, dtype)
    return p


def moe_apply(p: dict, cfg: LMConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (T, D) flattened tokens → (y: (T, D), aux_loss: scalar)."""
    mo = cfg.moe
    t, d = x.shape
    e, k = mo.n_routed, mo.top_k
    act = activation(cfg.act)

    scores = jax.nn.softmax((x.astype(jnp.float32) @ p["router"]), axis=-1)  # (T, E)
    top_w, top_i = jax.lax.top_k(scores, k)  # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)  # DeepSeek renorm

    # ---- sort-based dispatch (responsible-key partitioning) ----
    flat_e = top_i.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e)  # stable
    tok_of_slot = jnp.arange(t * k, dtype=jnp.int32) // k
    xs = x[tok_of_slot[order]]  # (T*k, D) sorted by expert
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    g = act(jax.lax.ragged_dot(xs, p["w_gate"], group_sizes))
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    y_sorted = jax.lax.ragged_dot((g * u).astype(xs.dtype), p["w_down"], group_sizes)

    # ---- unsort + weighted combine over the k copies ----
    y_slots = jnp.zeros_like(y_sorted).at[order].set(y_sorted)  # (T*k, D)
    y = jnp.sum(
        y_slots.reshape(t, k, d) * top_w[..., None].astype(y_sorted.dtype), axis=1
    )

    if mo.n_shared:
        y = y + mlp_apply(p["shared"], x, cfg.act)

    # load-balance aux loss (switch-style): E * Σ_e f_e · P_e
    density = jnp.mean(
        jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(axis=1), axis=0
    )  # fraction routed to e
    prob = jnp.mean(scores, axis=0)
    aux = e * jnp.sum(density * prob)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map over 'model'): GShard-style capacity dispatch
# ---------------------------------------------------------------------------
def moe_apply_ep(p: dict, cfg: LMConfig, x: jax.Array, *, mesh,
                 capacity_factor: float = 1.25) -> tuple[jax.Array, jax.Array]:
    """Distributed MoE: experts sharded over 'model', tokens over the data
    axes. Within a dp row, x is replicated across the model axis, every model
    shard routes identically, computes ONLY its resident experts' FFNs into a
    capacity-bounded (E_loc, C, D) dispatch buffer, and the combine is a psum
    over 'model' — the paper's divide-stage responsible-key partition, with
    the capacity bound as the straggler guard (tokens beyond capacity drop,
    GShard semantics). Static shapes throughout; exact when capacity_factor
    is generous (tests verify against moe_apply)."""
    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    e, k = mo.n_routed, mo.top_k
    act = activation(cfg.act)
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dpa = dp if len(dp) > 1 else dp[0]
    n_model = mesh.shape["model"]
    e_loc = e // n_model

    def body(router, w_gate, w_up, w_down, x_loc):
        t_loc, d = x_loc.shape
        cap = max(1, int(t_loc * k / e * capacity_factor))
        scores = jax.nn.softmax(x_loc.astype(jnp.float32) @ router, axis=-1)
        top_w, top_i = jax.lax.top_k(scores, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        midx = jax.lax.axis_index("model")
        e0 = midx * e_loc
        flat_e = top_i.reshape(-1)
        local = flat_e - e0
        in_range = (local >= 0) & (local < e_loc)
        local_c = jnp.where(in_range, local, 0)
        # position of each slot within its expert (only counting local slots)
        oh = jax.nn.one_hot(jnp.where(in_range, local, e_loc), e_loc + 1, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) - oh  # exclusive prefix count per expert
        pos = jnp.take_along_axis(pos, jnp.where(in_range, local, e_loc)[:, None], axis=1)[:, 0]
        keep = in_range & (pos < cap)
        pos_c = jnp.where(keep, pos, 0)
        tok = jnp.arange(t_loc * k, dtype=jnp.int32) // k
        x_slot = x_loc[tok] * keep[:, None].astype(x_loc.dtype)
        dispatch = jnp.zeros((e_loc, cap, d), x_loc.dtype).at[local_c, pos_c].add(x_slot)
        g = act(jnp.einsum("ecd,edf->ecf", dispatch, w_gate))
        u = jnp.einsum("ecd,edf->ecf", dispatch, w_up)
        y = jnp.einsum("ecf,efd->ecd", (g * u).astype(x_loc.dtype), w_down)
        y_slot = y[local_c, pos_c] * keep[:, None].astype(y.dtype)
        w_slot = top_w.reshape(-1)[:, None].astype(x_loc.dtype)  # keep combine in param dtype
        out = jax.ops.segment_sum(y_slot * w_slot, tok, num_segments=t_loc)
        # NOTE (§Perf C2, refuted): reduce-scattering this combine onto a
        # ("dp","model")-joint token sharding doubled total wire bytes — the
        # SPMD partitioner falls back to "involuntary full rematerialization"
        # when un-transposing the joint sharding in the backward pass.
        out = jax.lax.psum(out, "model")
        # aux loss terms (identical on every model shard; psum-avg over dp)
        density = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(axis=1), axis=0)
        prob = jnp.mean(scores, axis=0)
        aux = e * jnp.sum(density * prob)
        return out, aux

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(dpa, None)),
        out_specs=(P(dpa, None), P()),
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)

    if mo.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    return out.astype(x.dtype), aux
