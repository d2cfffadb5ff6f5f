"""Distributed full-graph message passing (GSPMD-native engine).

GSPMD's auto-partitioner replicates the (E, d) message tensors of full-graph
GNNs at ogb_products scale (measured: 15.5 TiB/device for GraphCast), and
shard_map blocks rematerialization through its boundary (measured: remat had
zero effect, 168 GiB/device). This engine expresses the dynamic-pipeline
partitioning (DESIGN.md §4) in shapes GSPMD partitions trivially:

- node states h: (N, d), row-sharded over the flattened mesh — each device
  owns a responsible-node range (N/devs rows);
- edges: (n_dev, e_loc, 2), pre-partitioned host-side BY DESTINATION shard
  (``partition_edges_by_dst``), so the scatter step is a *vmapped local*
  segment-sum over the leading device axis — its output (n_dev, n_loc, d)
  has exactly h's shard layout and needs no collective;
- the only collective is the h all-gather feeding the edge gather (XLA
  inserts it for jnp.take on the row-sharded h) — the streamed counterpart
  of the paper's edge stream;
- jax.checkpoint per layer works (plain-jit remat), so the peak is one
  layer's working set plus the (h, e) carries.

Correctness is differential-tested against the plain single-device models on
8 forced host devices.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.models.gnn import common as C


def _flat_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def partition_edges_by_dst(edges, n_nodes_pad: int, n_devices: int):
    """Host-side: bucket (global-id) edges by dst row range. Returns
    ((n_devices * e_loc, 2) int32 padded with n_nodes_pad, e_loc)."""
    import numpy as np

    edges = np.asarray(edges)
    rows = n_nodes_pad // n_devices
    shard = np.minimum(edges[:, 1] // rows, n_devices - 1)
    shard = np.where(edges[:, 1] >= n_nodes_pad, -1, shard)
    counts = np.bincount(shard[shard >= 0], minlength=n_devices)
    e_loc = max(int(counts.max()), 1)
    e_loc = -(-e_loc // 8) * 8
    out = np.full((n_devices * e_loc, 2), n_nodes_pad, dtype=np.int32)
    for s in range(n_devices):
        rows_s = edges[shard == s]
        out[s * e_loc : s * e_loc + len(rows_s)] = rows_s
    return out, e_loc


def _cst(x: jax.Array, mesh: Mesh | None) -> jax.Array:
    """Constrain leading dim over the full flat mesh (edge/node shard layout).
    Without this GSPMD replicates gather outputs (measured 247 GiB/device)."""
    if mesh is None:
        return x
    axes = tuple(mesh.axis_names)
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _cst_axis1(x: jax.Array, mesh: Mesh | None) -> jax.Array:
    """Constrain dim 1 over the full flat mesh (chunked (K, n_dev, ...) layout)."""
    if mesh is None:
        return x
    axes = tuple(mesh.axis_names)
    spec = P(None, axes, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def gather_rows(h: jax.Array, idx: jax.Array, mesh: Mesh | None = None) -> jax.Array:
    """h: (N, d) row-sharded; idx: any shape of global ids (phantom = N).
    Returns rows with phantom rows zeroed. GSPMD all-gathers h once; the
    output is constrained to idx's shard layout."""
    n = h.shape[0]
    rows = jnp.take(h, jnp.minimum(idx, n - 1).reshape(-1), axis=0)
    rows = rows * (idx.reshape(-1) < n)[:, None].astype(h.dtype)
    return _cst(rows.reshape(*idx.shape, h.shape[-1]), mesh)


def multi_axis_index(axes) -> jax.Array:
    """Linear device index over a tuple of mesh axes (row-major)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def local_scatter_sum(msg: jax.Array, dst: jax.Array, n_loc: int,
                      mesh: Mesh | None = None) -> jax.Array:
    """msg: (n_dev, e_loc, d); dst: (n_dev, e_loc) GLOBAL ids, guaranteed in
    shard i's row range [i*n_loc, (i+1)*n_loc) (or phantom). Returns
    (n_dev, n_loc, d) — the exact shard layout of h, no collective.

    With a mesh this runs as a THIN shard_map (GSPMD replicates batched
    scatters — measured 129 GiB/device on MACE); the shard_map contains only
    the segment_sum, so remat outside it is unaffected."""
    n_dev = msg.shape[0]
    n_glob = n_dev * n_loc
    if mesh is None:
        row0 = (jnp.arange(n_dev, dtype=dst.dtype) * n_loc)[:, None]
        local = jnp.clip(dst - row0, 0, n_loc)
        local = jnp.where(dst >= n_glob, n_loc, local)

        def one(m, l):
            return jax.ops.segment_sum(m, l, num_segments=n_loc + 1)[:n_loc]

        return jax.vmap(one)(msg, local)

    axes = tuple(mesh.axis_names)

    def body(m, d_):
        me = multi_axis_index(axes)
        local = jnp.clip(d_[0] - me * n_loc, 0, n_loc)
        local = jnp.where(d_[0] >= n_glob, n_loc, local)
        out = jax.ops.segment_sum(m[0], local, num_segments=n_loc + 1)[:n_loc]
        return out[None]

    return jax.shard_map(body, mesh=mesh, check_vma=False,
                         in_specs=(P(axes, None, None), P(axes, None)),
                         out_specs=P(axes, None, None))(msg, dst)


def local_take(arr: jax.Array, idx: jax.Array, mesh: Mesh | None = None) -> jax.Array:
    """Batched within-shard gather: arr (n_dev, E[, d]); idx (n_dev, T) LOCAL
    slot ids → (n_dev, T[, d]). Thin shard_map for the same GSPMD reason."""
    if arr.ndim == 2:
        return local_take(arr[..., None], idx, mesh)[..., 0]
    if mesh is None:
        return jax.vmap(lambda a, i: a[i])(arr, idx)

    axes = tuple(mesh.axis_names)

    def body(a, i):
        return a[0][i[0]][None]

    return jax.shard_map(body, mesh=mesh, check_vma=False,
                         in_specs=(P(axes, None, None), P(axes, None)),
                         out_specs=P(axes, None, None))(arr, idx)


def local_segment_sum(vals: jax.Array, ids: jax.Array, num: int,
                      mesh: Mesh | None = None) -> jax.Array:
    """Batched within-shard segment_sum: vals (n_dev, T, d); ids (n_dev, T)
    LOCAL segment ids in [0, num) → (n_dev, num, d)."""
    if mesh is None:
        return jax.vmap(lambda v, i: jax.ops.segment_sum(v, i, num_segments=num))(vals, ids)

    axes = tuple(mesh.axis_names)

    def body(v, i):
        return jax.ops.segment_sum(v[0], i[0], num_segments=num)[None]

    return jax.shard_map(body, mesh=mesh, check_vma=False,
                         in_specs=(P(axes, None, None), P(axes, None)),
                         out_specs=P(axes, None, None))(vals, ids)


def _reshape_edges(edges: jax.Array, n_dev: int) -> jax.Array:
    return edges.reshape(n_dev, -1, 2)


def replicate_rows(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Explicit all-gather of a row-sharded (N, d) array via a thin shard_map.
    Unlike a replicated with_sharding_constraint, this cannot leak a
    'replicated' sharding choice back into the producer (measured: the layer
    scan's h carry stack became a replicated 21 GiB/device buffer)."""

    axes = tuple(mesh.axis_names)

    def body(xl):
        return jax.lax.all_gather(xl, axes, axis=0, tiled=True)

    return jax.shard_map(body, mesh=mesh, check_vma=False,
                         in_specs=P(axes, None), out_specs=P(None, None))(x)


# ---------------------------------------------------------------------------
# family instances
# ---------------------------------------------------------------------------
def gin_distributed_loss(params, cfg: GNNConfig, mesh: Mesh):
    n_dev = mesh.devices.size

    def loss(p, batch):
        edges = _reshape_edges(batch["edges"], n_dev)
        h = batch["x"]
        n = h.shape[0]
        n_loc = n // n_dev
        for layer in p["layers"]:
            def one_layer(layer, h):
                msg = gather_rows(h, edges[..., 0], mesh)
                agg = local_scatter_sum(msg, edges[..., 1], n_loc, mesh).reshape(n, -1)
                return _cst(C.mlp_apply(layer["mlp"], (1.0 + layer["eps"]) * h + agg,
                                        act=jax.nn.relu, final_act=True), mesh)
            h = jax.checkpoint(one_layer)(layer, h)
        logits = C.mlp_apply(p["readout"], h)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], axis=1))

    return loss


def _stack_layers(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def graphcast_distributed_loss(params, cfg: GNNConfig, mesh: Mesh, *, remat: bool = True,
                               compute_dtype=None):
    """lax.scan over stacked layers: the while-loop body gets ONE reusable
    buffer allocation (python-loop layers made XLA:CPU's non-memory-aware
    scheduler keep every layer's working set live — 247 GiB/device;
    scan+remat: 35 GiB f32, ~18 GiB bf16 at ogb_products scale)."""
    n_dev = mesh.devices.size

    def loss(p, batch):
        edges = _reshape_edges(batch["edges"], n_dev)
        x, target = batch["x"], batch["target"]
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        n = x.shape[0]
        n_loc = n // n_dev
        e_loc = edges.shape[1]
        if compute_dtype is not None:
            p = jax.tree.map(lambda w: w.astype(compute_dtype), p)
        h = _cst(C.mlp_apply(p["encoder"], x), mesh)
        d = h.shape[-1]
        e = _cst(C.mlp_apply(p["edge_embed"], jnp.zeros((n_dev, e_loc, 4), h.dtype)), mesh)
        stacked = _stack_layers(p["layers"])

        def body(carry, layer):
            h, e = carry
            h_src = gather_rows(h, edges[..., 0], mesh)
            h_dst = gather_rows(h, edges[..., 1], mesh)
            e = _cst(e + C.mlp_apply(layer["edge_mlp"], jnp.concatenate([h_src, h_dst, e], -1)), mesh)
            agg = local_scatter_sum(e, edges[..., 1], n_loc, mesh).reshape(n, d)
            h = _cst(h + C.layer_norm(C.mlp_apply(layer["node_mlp"], jnp.concatenate([h, agg], -1))), mesh)
            return (h, e), None

        if remat:
            body = jax.checkpoint(body)
        (h, e), _ = jax.lax.scan(body, (h, e), stacked)
        pred = C.mlp_apply(p["decoder"], h)
        return jnp.mean(jnp.square(pred.astype(jnp.float32) - target.astype(jnp.float32)))

    return loss


def mace_distributed_loss(params, cfg: GNNConfig, mesh: Mesh, *, compute_dtype=None):
    """Flattened-irrep node states, CG-path edge math, local scatter."""
    from repro.models.gnn.cg import sh_l
    from repro.models.gnn.dimenet import radial_basis
    from repro.models.gnn.mace import _cg_contract, _paths

    n_dev = mesh.devices.size
    lm, c = cfg.l_max, cfg.d_hidden
    paths = _paths(lm)
    dims = [2 * l + 1 for l in range(lm + 1)]
    off = [0]
    for d in dims:
        off.append(off[-1] + d * c)

    def split(hf):
        return {l: hf[..., off[l]:off[l + 1]].reshape(*hf.shape[:-1], dims[l], c)
                for l in range(lm + 1)}

    def loss(p, batch, n_chunks: int = 8):
        edges = _reshape_edges(batch["edges"], n_dev)
        z, pos, target = batch["z"], batch["pos"], batch["target"]
        if compute_dtype is not None:
            p = jax.tree.map(lambda w: w.astype(compute_dtype), p)
            pos = pos.astype(compute_dtype)
        n = z.shape[0]
        n_loc = n // n_dev
        e_loc = edges.shape[1]
        k = n_chunks if e_loc % n_chunks == 0 else 1
        ck = e_loc // k
        # chunk layout (K, n_dev, ck, ...): scanning the chunk axis bounds the
        # per-path edge tensors at 1/K — the 13-path python loop otherwise
        # keeps every path's (e_loc, ·) tensors live (measured 128 GiB/device)
        chunked = lambda x: _cst_axis1(
            jnp.moveaxis(x.reshape(n_dev, k, ck, *x.shape[2:]), 1, 0), mesh)
        src_c = chunked(edges[..., 0])
        dst_c = chunked(edges[..., 1])

        src, dst = edges[..., 0], edges[..., 1]
        p_src = gather_rows(pos, src, mesh)
        p_dst = gather_rows(pos, dst, mesh)
        valid = (src < n)[..., None].astype(pos.dtype)
        vec = (p_dst - p_src) * valid
        dist = jnp.linalg.norm(vec + 1e-9, axis=-1)
        unit = vec / jnp.maximum(dist, 1e-9)[..., None]
        sh_c = {l: chunked((sh_l(unit, l) * valid).astype(pos.dtype)) for l in range(lm + 1)}
        rbf_c = chunked((radial_basis(dist, cfg.n_rbf, 5.0) * valid).astype(pos.dtype))

        h0 = jnp.take(p["species"], jnp.minimum(z, p["species"].shape[0] - 1), axis=0)
        h_flat = jnp.concatenate(
            [h0] + [jnp.zeros((n, dims[l] * c), h0.dtype) for l in range(1, lm + 1)], axis=-1)

        def one_layer(layer, h_flat):
            # replicate node states once per layer (the explicit all-gather);
            # per-chunk gathers below are then collective-free local takes
            h_full = replicate_rows(h_flat, mesh)
            hs_full = split(h_full)

            @jax.checkpoint
            def chunk_body(a_carry, chunk):
                s_c, d_c, shc, rc = chunk
                w = C.mlp_apply(layer["radial"], rc).reshape(n_dev, ck, len(paths), c)
                # §Perf iter: ONE source gather per distinct l1 (3 gathers)
                # instead of one per path (13) — the gather is the dominant
                # HBM traffic of the atomic-basis stage
                hj_by_l1 = {}
                for l1 in range(lm + 1):
                    hj = jnp.take(hs_full[l1].reshape(n, dims[l1] * c),
                                  jnp.minimum(s_c, n - 1).reshape(-1), axis=0)
                    hj = hj * (s_c.reshape(-1) < n)[:, None].astype(hj.dtype)
                    hj_by_l1[l1] = hj.reshape(-1, dims[l1], c)
                for pi, (l1, l2, l3) in enumerate(paths):
                    msg = _cg_contract(hj_by_l1[l1], shc[l2].reshape(-1, dims[l2]), l1, l2, l3)
                    msg = msg * w[..., pi, :].reshape(-1, 1, c)
                    agg = local_scatter_sum(
                        msg.reshape(n_dev, ck, dims[l3] * c), d_c, n_loc, mesh
                    ).reshape(n, dims[l3], c)
                    a_carry[l3] = a_carry[l3] + agg
                return a_carry, None

            a0 = {l: _cst(jnp.zeros((n, dims[l], c), h0.dtype), mesh) for l in range(lm + 1)}
            a_parts, _ = jax.lax.scan(chunk_body, a0, (src_c, dst_c, sh_c, rbf_c))
            hs = split(h_flat)
            b2 = {l: jnp.zeros_like(a_parts[l]) for l in range(lm + 1)}
            b3 = {l: jnp.zeros_like(a_parts[l]) for l in range(lm + 1)}
            for l1, l2, l3 in paths:
                b2[l3] = b2[l3] + _cg_contract(a_parts[l1], a_parts[l2], l1, l2, l3)
            for l1, l2, l3 in paths:
                b3[l3] = b3[l3] + _cg_contract(b2[l1], a_parts[l2], l1, l2, l3)
            newh = {}
            for l in range(lm + 1):
                newh[l] = (a_parts[l] @ layer["mix_a"][str(l)]
                           + b2[l] @ layer["mix_b2"][str(l)]
                           + b3[l] @ layer["mix_b3"][str(l)]
                           + hs[l] @ layer["res"][str(l)])
            h_new = _cst(jnp.concatenate([newh[l].reshape(n, dims[l] * c) for l in range(lm + 1)], -1), mesh)
            e_site = C.mlp_apply(layer["readout"], newh[0][:, 0, :])[:, 0].astype(jnp.float32)
            return h_new, e_site

        stacked = _stack_layers(p["layers"])

        @jax.checkpoint
        def body(carry, layer):
            h_flat, energy = carry
            h_new, e_site = one_layer(layer, h_flat)
            return (h_new, energy + e_site), None

        energy0 = jnp.zeros((n,), jnp.float32)
        (h_flat, energy), _ = jax.lax.scan(body, (h_flat, energy0), stacked)
        e_tot = jnp.sum(energy)
        return jnp.mean(jnp.square(e_tot - target[0]))

    return loss


def dimenet_distributed_loss(params, cfg: GNNConfig, mesh: Mesh):
    """Edge-centric: edge messages m live with dst-node shards (responsible
    node j of message m_ji). Triplets are LOCAL by construction (both e_kj
    and e_ji share middle node j — same shard), so the triplet gather is a
    vmapped within-shard take, never an all-gather of m."""
    from repro.models.gnn.dimenet import radial_basis, spherical_basis

    n_dev = mesh.devices.size

    def loss(p, batch):
        edges = _reshape_edges(batch["edges"], n_dev)
        trip = batch["triplets"].reshape(n_dev, -1, 2)
        z, pos, target = batch["z"], batch["pos"], batch["target"]
        n = z.shape[0]
        n_loc = n // n_dev
        e_loc = edges.shape[1]
        src, dst = edges[..., 0], edges[..., 1]
        valid_e = (src < n)[..., None].astype(pos.dtype)
        vec = _cst((gather_rows(pos, dst, mesh) - gather_rows(pos, src, mesh)) * valid_e, mesh)
        dist = jnp.linalg.norm(vec + 1e-9, axis=-1)
        rbf = radial_basis(dist, cfg.n_radial, 5.0) * valid_e

        # LOCAL triplet slots (edge ids are global; subtract shard base)
        e_row0 = (jnp.arange(n_dev, dtype=trip.dtype) * e_loc)[:, None]
        t_kj = jnp.clip(trip[..., 0] - e_row0, 0, e_loc - 1)
        t_ji = jnp.clip(trip[..., 1] - e_row0, 0, e_loc - 1)
        in_shard = ((trip[..., 0] - e_row0 >= 0) & (trip[..., 0] - e_row0 < e_loc)
                    & (trip[..., 1] - e_row0 >= 0) & (trip[..., 1] - e_row0 < e_loc))
        valid_t = in_shard[..., None].astype(pos.dtype)

        take_e = lambda arr, idx: local_take(arr, idx, mesh)
        v1 = -take_e(vec, t_kj)
        v2 = take_e(vec, t_ji)
        cosang = jnp.sum(v1 * v2, -1) / jnp.maximum(
            jnp.linalg.norm(v1, axis=-1) * jnp.linalg.norm(v2, axis=-1), 1e-9)
        angle = jnp.arccos(jnp.clip(cosang, -1 + 1e-6, 1 - 1e-6))
        sbf = spherical_basis(
            take_e(dist, t_kj).reshape(-1), angle.reshape(-1),
            cfg.n_spherical, cfg.n_radial, 5.0
        ).reshape(n_dev, -1, cfg.n_spherical * cfg.n_radial) * valid_t

        h = jnp.take(p["species"], jnp.minimum(z, p["species"].shape[0] - 1), axis=0)
        h_src = gather_rows(h, src, mesh)
        h_dst = gather_rows(h, dst, mesh)
        m = _cst(C.mlp_apply(p["embed_mlp"], jnp.concatenate(
            [h_src, h_dst, C.mlp_apply(p["rbf_proj"], rbf)], -1)), mesh)  # (n_dev, e_loc, d)

        def one_block(blk, m):
            t_msg = take_e(C.mlp_apply(blk["mlp_src"], m), t_kj) * valid_t
            sb = sbf @ blk["w_sbf"]
            from repro.models.gnn.dimenet import bilinear_apply
            tri = bilinear_apply(sb, blk["w_bil"], t_msg)
            agg = local_segment_sum(tri, t_ji, e_loc, mesh)
            m = _cst(m + C.mlp_apply(blk["mlp_out"], m + agg), mesh)
            gated = m * C.mlp_apply(blk["out_rbf"], rbf)
            node = local_scatter_sum(gated, dst, n_loc, mesh).reshape(n, -1)
            e_site = C.mlp_apply(blk["out_mlp"], node)[:, 0].astype(jnp.float32)
            return m, e_site

        stacked = _stack_layers(p["blocks"])

        @jax.checkpoint
        def body(carry, blk):
            m, energy = carry
            m, e_site = one_block(blk, m)
            return (m, energy + e_site), None

        (m, energy), _ = jax.lax.scan(body, (m, jnp.zeros((n,), jnp.float32)), stacked)
        e_tot = jnp.sum(energy)
        return jnp.mean(jnp.square(e_tot - target[0]))

    return loss


def make_distributed_gnn_train_step(cfg: GNNConfig, mesh: Mesh, opt_cfg=None,
                                    compute_dtype=None):
    from repro.train import optimizer as opt

    opt_cfg = opt_cfg or opt.AdamWConfig(weight_decay=0.0)
    builders = {
        "gin": gin_distributed_loss,
        "graphcast": graphcast_distributed_loss,
        "mace": mace_distributed_loss,
        "dimenet": dimenet_distributed_loss,
    }
    loss_builder = builders[cfg.family]
    kw = {}
    if compute_dtype is not None and cfg.family in ("mace", "graphcast"):
        kw["compute_dtype"] = compute_dtype

    def step(params, opt_state, batch):
        loss = loss_builder(params, cfg, mesh, **kw)
        l, grads = jax.value_and_grad(lambda p: loss(p, batch))(params)
        params, opt_state = opt.update(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": l}

    return step
