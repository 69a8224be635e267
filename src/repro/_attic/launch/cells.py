"""Cell builder: (arch × shape × mesh) → a lowerable, shard-annotated step.

This is the hub the dry-run, the roofline pass, and the real launchers all
share.  ``build_cell`` returns the jit-able function, abstract input
ShapeDtypeStructs, and in/out PartitionSpecs for the given mesh — 40 cells
total across the 10 assigned architectures.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_arch, shapes_for
from ..configs.shapes import (GNNShape, LMShape, RecsysShape, pad_to,
                              sampled_sizes)
from ..models import gnn as G
from ..models import recsys as R
from ..models import transformer as T
from repro.train import optimizer as O
from repro.train.train_loop import make_train_step
from repro.launch.mesh import dp_axes, dp_size


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_id: str
    kind: str
    fn: Callable
    args: Tuple[Any, ...]          # ShapeDtypeStruct pytrees
    in_specs: Tuple[Any, ...]      # PartitionSpec pytrees (same structure)
    out_specs: Any
    meta: Dict[str, Any]
    donate: Tuple[int, ...] = ()   # arg indices aliased into outputs


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _replicated_like(tree):
    return jax.tree.map(lambda _: P(), tree)


def shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# -- LM cells -------------------------------------------------------------------

def _lm_cfg_for(cfg: T.LMConfig, n_groups: int) -> T.LMConfig:
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_groups=n_groups))


def _lm_train_cell(arch_id, cfg: T.LMConfig, shape: LMShape, mesh) -> Cell:
    dp = dp_axes(mesh)
    dpn = dp_size(mesh)
    b, s = shape.global_batch, shape.seq_len
    per_chip = max(1, b // dpn)
    # §Perf iteration 3: the d-sharded residual stream shrinks remat
    # carries 16×, so larger microbatches fit — fewer FSDP weight
    # re-gathers (collective term scales with accum)
    accum = max(1, per_chip // 4)
    cfg = _lm_cfg_for(cfg, dpn)

    opt = O.adafactor(peak_lr=1e-4) if cfg.moe is not None \
        else O.adamw(peak_lr=3e-4)
    param_shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    opt_shapes = jax.eval_shape(opt.init, param_shapes)
    batch = {"tokens": _sds((b, s), jnp.int32),
             "labels": _sds((b, s), jnp.int32)}
    batch_specs = {"tokens": P(dp, None), "labels": P(dp, None)}

    param_specs = T.param_specs(cfg)
    state_specs = opt.state_specs(param_specs)
    q_block = 1024 if s >= 4096 else None
    # giant-MoE grads don't fit as f32 scan carry → accumulate in bf16
    accum_dtype = jnp.bfloat16 if (cfg.moe is not None
                                   and cfg.n_params() > 1e11) else jnp.float32
    step = make_train_step(
        lambda p, mb: T.loss_fn(p, mb, cfg, q_block=q_block), opt,
        accum=accum, accum_dtype=accum_dtype)
    tokens = b * s
    return Cell(
        arch_id, shape.shape_id, "train", step,
        (param_shapes, opt_shapes, batch),
        (param_specs, state_specs, batch_specs),
        (param_specs, state_specs, None),
        {"family": "lm", "tokens": tokens, "accum": accum,
         "n_params": cfg.n_params(), "n_active": cfg.n_active_params(),
         "model_flops": 6.0 * cfg.n_active_params() * tokens},
        donate=(0, 1))


def _lm_prefill_cell(arch_id, cfg: T.LMConfig, shape: LMShape, mesh) -> Cell:
    dp = dp_axes(mesh)
    cfg = _lm_cfg_for(cfg, dp_size(mesh))
    b, s = shape.global_batch, shape.seq_len
    param_shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    param_specs = T.param_specs(cfg)
    tokens = _sds((b, s), jnp.int32)

    def fn(params, toks):
        return T.prefill_step(params, toks, cfg, q_block=2048)

    cache_out = T.cache_specs(cfg, batch_ax=dp,
                              model_size=mesh.shape["model"])
    return Cell(
        arch_id, shape.shape_id, "prefill", fn,
        (param_shapes, tokens),
        (param_specs, P(dp, None)),
        (P(dp, "model"), cache_out),
        {"family": "lm", "tokens": b * s, "n_params": cfg.n_params(),
         "n_active": cfg.n_active_params(),
         "model_flops": 2.0 * cfg.n_active_params() * b * s})


def _lm_decode_cell(arch_id, cfg: T.LMConfig, shape: LMShape, mesh) -> Cell:
    dp = dp_axes(mesh)
    b, s = shape.global_batch, shape.seq_len
    long_ctx = b == 1
    # decode token counts are tiny: one routing group, d sharded over data
    # in the dispatch buffer (§Perf deepseek decode iteration 2)
    cfg = _lm_cfg_for(cfg, 1)
    param_shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    param_specs = T.param_specs(cfg)
    cache_shapes = jax.eval_shape(lambda: T.make_cache(cfg, b, s))
    msize = mesh.shape["model"]
    cache_specs = T.cache_specs(cfg, batch_ax=None if long_ctx else dp,
                                model_size=msize)
    toks = _sds((b, 1), jnp.int32)
    tok_spec = P(None, None) if long_ctx else P(dp, None)

    def fn(params, cache, t):
        return T.decode_step(params, cache, t, cfg)

    return Cell(
        arch_id, shape.shape_id, "decode", fn,
        (param_shapes, cache_shapes, toks),
        (param_specs, cache_specs, tok_spec),
        (None, cache_specs),
        {"family": "lm", "tokens": b, "kv_len": s,
         "n_params": cfg.n_params(), "n_active": cfg.n_active_params(),
         "model_flops": 2.0 * cfg.n_active_params() * b},
        donate=(1,))


# -- GNN cells -------------------------------------------------------------------

_GNN_LOSS = {
    "graphsage-reddit": lambda p, b, cfg, ng: G.sage_loss(p, b, cfg),
    "meshgraphnet": lambda p, b, cfg, ng: G.mgn_loss(p, b, cfg),
    "schnet": lambda p, b, cfg, ng: G.schnet_loss(p, b, cfg, ng),
    "equiformer-v2": lambda p, b, cfg, ng: G.eqv2_loss(p, b, cfg, ng),
}

_GNN_INIT = {
    "graphsage-reddit": G.sage_init,
    "meshgraphnet": G.mgn_init,
    "schnet": G.schnet_init,
    "equiformer-v2": G.eqv2_init,
}


def _gnn_batch_shapes(arch_id, n_pad, e_pad, d_feat, n_graphs):
    base = {"src": _sds((e_pad,), jnp.int32),
            "dst": _sds((e_pad,), jnp.int32),
            "node_mask": _sds((n_pad,), jnp.bool_)}
    if arch_id == "graphsage-reddit":
        base |= {"feat": _sds((n_pad, d_feat), jnp.float32),
                 "labels": _sds((n_pad,), jnp.int32)}
    elif arch_id == "meshgraphnet":
        base |= {"feat": _sds((n_pad, d_feat), jnp.float32),
                 "pos": _sds((n_pad, 3), jnp.float32),
                 "targets": _sds((n_pad, 2), jnp.float32)}
    else:  # schnet / equiformer: geometric, species-driven
        base |= {"species": _sds((n_pad,), jnp.int32),
                 "pos": _sds((n_pad, 3), jnp.float32),
                 "graph_id": _sds((n_pad,), jnp.int32),
                 "energy": _sds((n_graphs,), jnp.float32)}
    return base


def _gnn_cell(arch_id, cfg, shape: GNNShape, mesh,
              local_sampled: bool = True) -> Cell:
    all_ax = tuple(mesh.axis_names)
    if shape.kind == "sampled":
        n, e = sampled_sizes(shape)
        n_graphs = 1
    elif shape.kind == "batched":
        n, e = shape.n_nodes * shape.n_graphs, shape.n_edges * shape.n_graphs
        n_graphs = shape.n_graphs
    else:
        n, e = shape.n_nodes, shape.n_edges
        n_graphs = 1
    n_pad, e_pad = pad_to(n, 1024), pad_to(e, 1024)

    if arch_id == "graphsage-reddit":
        cfg = dataclasses.replace(cfg, d_in=shape.d_feat)
    elif arch_id == "meshgraphnet":
        cfg = dataclasses.replace(cfg, d_node_in=shape.d_feat)
    # NOTE equiformer-v2 × ogb_products: the edge-chunked two-pass layer
    # (EqV2Config.edge_chunk, exactness-tested) bounds *forward* edge
    # buffers, but reverse-mode through the chunk scan stores the (n, M, C)
    # carry per chunk — full-batch TRAINING at 61.8M edges needs a
    # flash-attention-style custom VJP (two extra edge passes from the
    # softmax statistics). Documented in EXPERIMENTS.md §F; the cell lowers
    # unchunked (compiles; does not fit 16 GiB).

    init = _GNN_INIT[arch_id]
    loss = _GNN_LOSS[arch_id]
    param_shapes = jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg))
    param_specs = _replicated_like(param_shapes)
    opt = O.adamw(peak_lr=1e-3)
    opt_shapes = jax.eval_shape(opt.init, param_shapes)
    state_specs = opt.state_specs(param_specs)

    batch = _gnn_batch_shapes(arch_id, n_pad, e_pad, shape.d_feat, n_graphs)
    batch_specs = {k: P(all_ax, *([None] * (v.ndim - 1)))
                   if v.shape and v.shape[0] in (n_pad, e_pad) else P()
                   for k, v in batch.items()}

    if shape.kind == "sampled" and local_sampled:
        # §Perf iteration 2: sampled-subgraph training is data-parallel
        # over seed minibatches.  Each device holds self-contained
        # subgraphs with LOCAL node ids (data/graphs.sampled_batch emits
        # per-shard-local blocks), so the whole GNN step runs inside
        # shard_map with zero cross-device traffic except the (tiny)
        # parameter-gradient psum.  Baseline (GSPMD over one flat graph)
        # paid an all-gather of node states per message-passing layer.
        def loss_fn(params, mb):
            def local(params, mbl):
                return jax.lax.pmean(loss(params, mbl, cfg, n_graphs),
                                     all_ax)
            in_specs = (_replicated_like(param_shapes),
                        {k: P(all_ax, *([None] * (v.ndim - 1)))
                         if v.shape and v.shape[0] in (n_pad, e_pad)
                         else P() for k, v in batch.items()})
            return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=P())(params, mb)
    else:
        loss_fn = lambda p, mb: loss(p, mb, cfg, n_graphs)
    step = make_train_step(loss_fn, opt)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(param_shapes))
    return Cell(
        arch_id, shape.shape_id, "train", step,
        (param_shapes, opt_shapes, batch),
        (param_specs, state_specs, batch_specs),
        (param_specs, state_specs, None),
        {"family": "gnn", "tokens": n, "edges": e, "n_params": n_params,
         "n_active": n_params,
         "model_flops": 6.0 * n_params * n},
        donate=(0, 1))


# -- recsys cells -----------------------------------------------------------------

def _dien_batch_shapes(cfg: R.DIENConfig, b: int, train: bool):
    t = cfg.seq_len
    base = {
        "hist_items": _sds((b, t), jnp.int32),
        "hist_cats": _sds((b, t), jnp.int32),
        "hist_mask": _sds((b, t), jnp.float32),
        "target_item": _sds((b,), jnp.int32),
        "target_cat": _sds((b,), jnp.int32),
        "profile": _sds((b, cfg.profile_bags, cfg.bag_len), jnp.int32),
    }
    if train:
        base |= {"neg_items": _sds((b, t), jnp.int32),
                 "neg_cats": _sds((b, t), jnp.int32),
                 "label": _sds((b,), jnp.int32)}
    return base


def _recsys_cell(arch_id, cfg: R.DIENConfig, shape: RecsysShape,
                 mesh) -> Cell:
    dp = dp_axes(mesh)
    param_shapes = jax.eval_shape(
        lambda: R.dien_init(jax.random.PRNGKey(0), cfg))
    param_specs = R.dien_param_specs(cfg)
    b = shape.batch
    flat = jax.tree_util.tree_flatten_with_path(param_shapes)[0]
    n_params = sum(x.size for _, x in flat)
    table_params = sum(x.size for kp, x in flat
                       if "table" in jax.tree_util.keystr(kp))
    # active params per example: dense scorer + touched embedding rows
    touched_rows = 2 * cfg.seq_len + 2 + cfg.profile_bags * cfg.bag_len
    n_active = (n_params - table_params) + touched_rows * cfg.embed_dim
    meta = {"family": "recsys", "tokens": b, "n_params": n_params,
            "n_active": n_active, "model_flops": 6.0 * n_active * b}

    if shape.kind == "train":
        opt = O.adamw(peak_lr=1e-3)
        opt_shapes = jax.eval_shape(opt.init, param_shapes)
        state_specs = opt.state_specs(param_specs)
        batch = _dien_batch_shapes(cfg, b, train=True)
        batch_specs = {k: P(dp, *([None] * (v.ndim - 1)))
                       for k, v in batch.items()}
        step = make_train_step(lambda p, mb: R.dien_loss(p, mb, cfg), opt)
        return Cell(arch_id, shape.shape_id, "train", step,
                    (param_shapes, opt_shapes, batch),
                    (param_specs, state_specs, batch_specs),
                    (param_specs, state_specs, None), meta, donate=(0, 1))

    batch = _dien_batch_shapes(cfg, b, train=False)
    if shape.kind == "serve":
        batch_specs = {k: P(dp, *([None] * (v.ndim - 1)))
                       for k, v in batch.items()}

        def fn(params, mb):
            return R.dien_forward(params, mb, cfg)[0]

        return Cell(arch_id, shape.shape_id, "serve", fn,
                    (param_shapes, batch),
                    (param_specs, batch_specs), P(dp),
                    dict(meta, model_flops=2.0 * n_active * b))

    # retrieval: one user vs 1e6 candidates — single batched matmul
    cands = _sds((shape.n_candidates,), jnp.int32)

    def fn(params, mb, cand_ids):
        uv = R.dien_user_vector(params, mb, cfg)
        return R.retrieval_scores(params, uv, cand_ids)

    batch_specs = {k: P(*([None] * v.ndim)) for k, v in batch.items()}
    meta = dict(meta, model_flops=2.0 * shape.n_candidates * cfg.embed_dim
                + 2.0 * n_active)
    return Cell(arch_id, shape.shape_id, "retrieval", fn,
                (param_shapes, batch, cands),
                (param_specs, batch_specs, P(dp)),
                P(None, dp), meta)


# -- entry point -------------------------------------------------------------------

def build_cell(arch_id: str, shape_id: str, mesh) -> Cell:
    family, cfg = get_arch(arch_id)
    shape = shapes_for(arch_id)[shape_id]
    if family == "lm":
        if shape.kind == "train":
            return _lm_train_cell(arch_id, cfg, shape, mesh)
        if shape.kind == "prefill":
            return _lm_prefill_cell(arch_id, cfg, shape, mesh)
        return _lm_decode_cell(arch_id, cfg, shape, mesh)
    if family == "gnn":
        return _gnn_cell(arch_id, cfg, shape, mesh)
    return _recsys_cell(arch_id, cfg, shape, mesh)


def jit_cell(cell: Cell, mesh):
    """jit with explicit shardings, ready for .lower(*args)."""
    return jax.jit(
        cell.fn,
        in_shardings=shardings(mesh, cell.in_specs),
        out_shardings=shardings(mesh, cell.out_specs),
        donate_argnums=cell.donate)
