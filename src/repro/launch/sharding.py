"""Sharding rules: params (FSDP x TP x EP), decode states and batches.

Strategy (DESIGN.md §8):
  * params: one matmul dim on "model" (TP) — expert dim for MoE weights,
    head dim for attention, d_ff for FFNs — and one dim on "data" (FSDP,
    all-gathered just in time). Scan-stacked leaves skip the leading G dim.
  * batches: batch on (pod, data); long_500k (batch=1) shards the sequence.
  * decode KV caches: batch on (pod, data) when divisible, sequence on
    "model"; recurrent states shard their widest divisible dims.

Everything is divisibility-guarded, so the same rules serve the 16x16 and
2x16x16 production meshes and the 8-device test mesh.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.launch.mesh import data_axes


def _axis_size(mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name]


def _fits(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0 and dim >= n


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_spec(path: str, shape, cfg: ModelConfig, mesh) -> P:
    """PartitionSpec for one parameter leaf."""
    nd = len(shape)
    dp = data_axes(mesh)
    n_model = _axis_size(mesh, "model")
    n_data = _axis_size(mesh, dp)
    spec = [None] * nd
    i0 = 1 if "/scan/" in f"/{path}/" or path.startswith("scan/") else 0
    dims = list(range(i0, nd))
    if not dims:
        return P()

    used = set()

    def assign(i, ax):
        spec[i] = ax
        used.add(i)

    # ---- model (TP / EP) dim ------------------------------------------
    model_dim = None
    if cfg.moe is not None and ("moe/w_gate" in path or "moe/w_up" in path
                                or "moe/w_down" in path):
        for i in dims:                     # expert dim -> expert parallel
            if shape[i] == cfg.moe.num_experts and _fits(shape[i], n_model):
                model_dim = i
                break
    if model_dim is None and ("attn/" in path or "self_attn" in path
                              or "cross_attn" in path):
        for i in dims:                     # head dim -> tensor parallel
            if shape[i] in (cfg.num_heads, cfg.num_kv_heads) \
                    and _fits(shape[i], n_model):
                model_dim = i
    if model_dim is None and "tok_emb" in path:
        if _fits(shape[0], n_model):
            model_dim = 0                  # vocab on model
    if model_dim is None:
        # largest trailing dim divisible by model (prefer last)
        for i in reversed(dims):
            if _fits(shape[i], n_model) and shape[i] >= 2 * n_model:
                model_dim = i
                break
    if model_dim is not None:
        assign(model_dim, "model")

    # ---- data (FSDP) dim ----------------------------------------------
    for i in dims:
        if i not in used and _fits(shape[i], n_data):
            assign(i, dp)
            break

    return P(*spec)


def shard_params(cfg: ModelConfig, abstract_params, mesh) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    out = [NamedSharding(mesh, param_spec(_path_str(p), leaf.shape, cfg,
                                          mesh))
           for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_spec(cfg: ModelConfig, shape_name: str, mesh) -> Any:
    """Shardings for the input batch dict."""
    shp = INPUT_SHAPES[shape_name]
    dp = data_axes(mesh)
    n_data = _axis_size(mesh, dp)
    bdim = dp if _fits(shp.global_batch, n_data) else None

    def leaf_spec(leaf_shape):
        spec = [bdim] + [None] * (len(leaf_shape) - 1)
        if bdim is None and len(leaf_shape) > 1 \
                and _fits(leaf_shape[1], n_data):
            spec[1] = dp                  # batch=1: shard sequence instead
        return P(*spec)

    def to_sharding(leaf):
        return NamedSharding(mesh, leaf_spec(leaf.shape))

    return to_sharding


def _state_leaf_spec(path: str, shape, cfg, mesh) -> P:
    dp = data_axes(mesh)
    n_data = _axis_size(mesh, dp)
    n_model = _axis_size(mesh, "model")
    nd = len(shape)
    if nd == 0:
        return P()
    # stacked leading layer/group dim for scanned caches & encdec memory
    i0 = 1 if ("scan/" in path or "memory/" in path
               or (cfg.encdec is not None and "caches/" in path)) else 0
    spec = [None] * nd
    dims = list(range(i0, nd))
    if not dims:
        return P()
    b_i = dims[0]
    if _fits(shape[b_i], n_data):
        spec[b_i] = dp
        rest = dims[1:]
    else:
        rest = dims[1:]
    # sequence dim (largest) on model; fall back to any divisible dim
    if rest:
        cand = max(rest, key=lambda i: shape[i])
        if shape[cand] >= 4 * n_model and _fits(shape[cand], n_model):
            spec[cand] = "model"
        elif spec[b_i] is None and _fits(shape[cand], n_data):
            spec[cand] = dp
    return P(*spec)


def shard_decode_state(cfg: ModelConfig, abstract_state, mesh) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    out = []
    for p, leaf in flat:
        path = _path_str(p)
        out.append(NamedSharding(mesh,
                                 _state_leaf_spec(path, leaf.shape, cfg,
                                                  mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


def expert_dispatch_ffn(mesh, wg, wu, wd, x_send, eid_send):
    """Expert-parallel compute dispatch: ship ``(tokens, expert_id)``
    groups across a 1-D ``("expert",)`` mesh (launch/mesh.make_expert_mesh)
    with a real ``lax.all_to_all``, compute each token's expert FFN on the
    shard that *owns* the expert, and return the outputs to the sender —
    the multi-device ground truth of the serving engines' modeled ship
    path (``TierConfig.dispatch``), runnable on CPU under
    ``--xla_force_host_platform_device_count``.

    wg/wu: (E, D, F); wd: (E, F, D) — expert-sharded, ``E`` divisible by
    the mesh's ``S`` shards, shard ``s`` owning global experts
    ``[s*E/S, (s+1)*E/S)``. ``x_send``: (S, S, C, D) send buffers —
    ``x_send[s, d, c]`` is source shard ``s``'s c-th token for destination
    shard ``d``; ``eid_send``: (S, S, C) int32 global expert ids aligned
    with it, ``-1`` marking padding slots (their outputs are zeroed).
    Every non-padding ``eid_send[s, d]`` entry must name an expert homed
    on shard ``d``. Returns (S, S, C, D): ``out[s, d, c]`` is the expert
    output for ``x_send[s, d, c]``, back on the source shard, unweighted
    (the caller applies the router's combine weights, exactly like
    :func:`repro.models.moe.expert_group_ffn`). f32 accumulation, output
    in ``x_send.dtype``.
    """
    from jax import lax

    s_mesh = _axis_size(mesh, "expert")
    e_local = wg.shape[0] // s_mesh
    assert wg.shape[0] % s_mesh == 0, \
        f"num_experts {wg.shape[0]} not divisible by {s_mesh} shards"
    assert x_send.shape[0] == s_mesh and x_send.shape[1] == s_mesh

    def body(wg_l, wu_l, wd_l, xs, es):
        # wg_l/wu_l: (E/S, D, F); wd_l: (E/S, F, D) — this shard's experts
        # xs: (1, S, C, D); es: (1, S, C) — this shard's send buffers
        xs, es = xs[0], es[0]
        # dispatch: row d of the send buffer goes to shard d; afterwards
        # row j holds what shard j sent HERE
        xr = lax.all_to_all(xs, "expert", split_axis=0, concat_axis=0,
                            tiled=True)
        er = lax.all_to_all(es, "expert", split_axis=0, concat_axis=0,
                            tiled=True)
        shard = lax.axis_index("expert")
        le = jnp.clip(er - shard * e_local, 0, e_local - 1)   # (S, C)
        g_sel = jnp.take(wg_l, le, axis=0).astype(jnp.float32)
        u_sel = jnp.take(wu_l, le, axis=0).astype(jnp.float32)
        d_sel = jnp.take(wd_l, le, axis=0).astype(jnp.float32)
        xf = xr.astype(jnp.float32)
        g = jnp.einsum("scd,scdf->scf", xf, g_sel)
        u = jnp.einsum("scd,scdf->scf", xf, u_sel)
        y = jnp.einsum("scf,scfd->scd", jax.nn.silu(g) * u, d_sel)
        y = jnp.where((er >= 0)[..., None], y, 0.0).astype(xs.dtype)
        # return trip: row j goes back to source shard j
        yr = lax.all_to_all(y, "expert", split_axis=0, concat_axis=0,
                            tiled=True)
        return yr[None]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert"),
                  P("expert"), P("expert")),
        out_specs=P("expert"))(wg, wu, wd, x_send, eid_send)


def act_sharding(cfg: ModelConfig, shape_name: str, mesh):
    """Between-layer activation constraint (B, T, D): batch on data,
    sequence on model (Megatron-style sequence parallelism)."""
    shp = INPUT_SHAPES[shape_name]
    dp = data_axes(mesh)
    n_data = _axis_size(mesh, dp)
    bdim = dp if _fits(shp.global_batch, n_data) else None
    if shp.mode == "decode":
        return NamedSharding(mesh, P(bdim, None, None))
    n_model = _axis_size(mesh, "model")
    sdim = "model" if shp.seq_len % n_model == 0 else None
    return NamedSharding(mesh, P(bdim, sdim, None))
