"""Step bundles: one (jit-able fn, abstract inputs, sharding specs)
triple per (architecture x input shape) cell.

Used by BOTH the CPU smoke tests (tiny real arrays through the same
builders) and the multi-pod dry-run (full-size ShapeDtypeStructs +
``.lower().compile()``), so what we smoke-test is what we ship.

``model_flops`` is the *useful-work* term for the roofline's
MODEL_FLOPS / HLO_FLOPS ratio:
  LM      6 * N_active * tokens  (+ 12 * L * H * dh * T^2 * B attention)
  GNN     documented per-family op counts
  recsys  dominated by GRU/AUGRU matmuls: 2 * 6 * H * (D + H) * T * B
  dspc    op-count proxy (label-merge ops); flagged in the table
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get as get_arch
from repro.configs.common import ArchSpec, ShapeSpec
from repro.models import dien as dien_mod
from repro.models import transformer as tf
from repro.models.gnn import egnn as egnn_mod
from repro.models.gnn import equiformer_v2 as eqv2_mod
from repro.models.gnn import nequip as nequip_mod
from repro.models.gnn import pna as pna_mod
from repro.models.gnn.graph import GraphBatch
from repro.models.gnn.sampler import sample_block_caps
from repro.train import optimizer as opt
from repro.train.loop import make_train_step_fn

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class StepBundle:
    name: str
    fn: Optional[Callable]            # mesh-independent step
    mesh_fn: Optional[Callable]       # mesh -> step (shard_map paths)
    abstract_args: tuple              # pytrees of ShapeDtypeStruct
    arg_specs: tuple                  # logical sharding spec pytrees
    model_flops: float
    static_kwargs: dict = dataclasses.field(default_factory=dict)
    notes: str = ""

    def get_fn(self, mesh=None, rules=None):
        if self.mesh_fn is not None:
            assert mesh is not None, f"{self.name} needs a mesh"
            return self.mesh_fn(mesh)
        if mesh is not None and rules is not None:
            from repro.sharding import wrap_with_activation_sharding
            return wrap_with_activation_sharding(self.fn, rules, mesh)
        return self.fn


_OPT = opt.AdamWConfig()


def _abstract(tree):
    return jax.tree.map(lambda x: SDS(x.shape, x.dtype), tree)


def _replicated_like(tree):
    return jax.tree.map(lambda _: (), tree,
                        is_leaf=lambda x: isinstance(x, SDS))


# ==========================================================================
# LM family
# ==========================================================================
def _lm_flops(cfg: tf.TransformerConfig, tokens: int, seq: int,
              train: bool) -> float:
    mult = 6 if train else 2
    dense = mult * cfg.active_param_count() * tokens
    attn = mult * 2 * cfg.n_layers * cfg.n_heads * cfg.d_head * seq * tokens
    return float(dense + attn)


def _lm_batch_struct(b, t):
    return {"tokens": SDS((b, t), jnp.int32), "labels": SDS((b, t), jnp.int32)}


def _lm_batch_spec():
    return {"tokens": ("batch", None), "labels": ("batch", None)}


def lm_bundle(spec: ArchSpec, shape: ShapeSpec, smoke: bool) -> StepBundle:
    cfg: tf.TransformerConfig = spec.smoke if smoke else spec.config
    dims = dict(shape.dims)
    if smoke:
        dims["seq_len"] = 16
        dims["global_batch"] = 2
    b, t = dims["global_batch"], dims["seq_len"]
    params_a = jax.eval_shape(lambda: tf.init_params(cfg))
    p_specs = tf.param_specs(cfg)

    if shape.kind == "train":
        loss_fn = tf.make_train_loss(cfg)
        step = make_train_step_fn(loss_fn, _OPT)
        opt_a = jax.eval_shape(lambda: opt.init(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_a),
            _OPT))
        o_specs = opt.state_specs(p_specs)
        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=step, mesh_fn=None,
            abstract_args=(params_a, opt_a, _lm_batch_struct(b, t)),
            arg_specs=(p_specs, o_specs, _lm_batch_spec()),
            model_flops=_lm_flops(cfg, b * t, t, train=True))

    if shape.kind == "prefill":
        s_max = t

        def prefill(params, tokens):
            return tf.prefill(params, tokens, cfg, s_max)

        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=prefill, mesh_fn=None,
            abstract_args=(params_a, SDS((b, t), jnp.int32)),
            arg_specs=(p_specs, ("batch", None)),
            model_flops=_lm_flops(cfg, b * t, t, train=False))

    if shape.kind == "decode":
        s_max = t
        cache_a = tf.abstract_cache(cfg, b, s_max)
        c_specs = tf.cache_specs(cfg)

        def decode(params, cache, token):
            return tf.decode_step(params, cache, token, cfg)

        # one token per sequence; cache attention reads the whole window
        flops = (2 * cfg.active_param_count() * b
                 + 2 * 2 * cfg.n_layers * cfg.n_heads * cfg.d_head * t * b)
        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=decode, mesh_fn=None,
            abstract_args=(params_a, cache_a, SDS((b,), jnp.int32)),
            arg_specs=(p_specs, c_specs, ("batch",)),
            model_flops=float(flops))

    raise ValueError(shape.kind)


def lm_host_args(spec: ArchSpec, shape: ShapeSpec, seed: int = 0):
    """Tiny real arrays for the smoke path (same structure as abstract)."""
    cfg: tf.TransformerConfig = spec.smoke
    rng = np.random.default_rng(seed)
    b, t = 2, 16
    params = tf.init_params(cfg, jax.random.PRNGKey(seed))
    if shape.kind == "train":
        state = opt.init(params, _OPT)
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, (b, t)), jnp.int32),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab, (b, t)), jnp.int32)}
        return (params, state, batch)
    if shape.kind == "prefill":
        return (params, jnp.asarray(
            rng.integers(0, cfg.vocab, (b, t)), jnp.int32))
    if shape.kind == "decode":
        cache = tf.init_cache(cfg, b, t)
        cache["lengths"] = jnp.full((b,), t // 2, jnp.int32)
        return (params, cache,
                jnp.asarray(rng.integers(0, cfg.vocab, (b,)), jnp.int32))
    raise ValueError(shape.kind)


# ==========================================================================
# GNN family
# ==========================================================================
_GNN_MODS = {
    "egnn": egnn_mod, "pna": pna_mod, "nequip": nequip_mod,
    "equiformer-v2": eqv2_mod,
}


def _gnn_needs_pos(arch_id: str) -> bool:
    return arch_id != "pna"


def _gnn_adapt(cfg, d_feat: int, n_out: int):
    return dataclasses.replace(cfg, d_in=d_feat, n_out=n_out)


def _gnn_flops(arch_id, cfg, n_edges, n_nodes) -> float:
    """Useful-op estimates (messages + updates), documented per family."""
    if arch_id == "egnn":
        per_edge = 2 * (2 * cfg.d_hidden + 1) * cfg.d_hidden * 2
        per_node = 2 * 2 * cfg.d_hidden * cfg.d_hidden * 2
    elif arch_id == "pna":
        per_edge = 2 * 2 * cfg.d_hidden * cfg.d_hidden
        per_node = 2 * 13 * cfg.d_hidden * cfg.d_hidden
    elif arch_id == "nequip":
        n_paths = len(cfg.paths)
        per_edge = (2 * cfg.n_rbf * cfg.radial_hidden
                    + 2 * cfg.radial_hidden * n_paths * cfg.d_hidden
                    + n_paths * cfg.d_hidden * 27 * 2)
        per_node = 2 * (cfg.l_max + 1) * cfg.d_hidden ** 2 * 9
    else:  # equiformer-v2
        c, lmax = cfg.d_hidden, cfg.l_max
        n_m0 = (lmax + 1) * c
        so2 = 2 * (2 * n_m0 + cfg.n_rbf) * n_m0
        for m in range(1, cfg.m_max + 1):
            nm = cfg.n_l(m) * c
            so2 += 2 * 4 * (2 * nm) * nm
        wig = sum((2 * l + 1) ** 2 for l in range(lmax + 1)) * c * 2 * 2
        per_edge = so2 + wig
        per_node = 2 * (lmax + 1) * c * c * 2
    layers = cfg.n_layers
    return float(layers * (per_edge * n_edges + per_node * n_nodes))


def _gnn_batch_struct(arch_id, n_node, n_edge, d_feat, n_graph=1):
    from repro.models.gnn.graph import batch_spec
    return batch_spec(n_node, n_edge, d_feat,
                      with_pos=_gnn_needs_pos(arch_id), n_graph=n_graph)


def _gnn_batch_specs(batch_a: GraphBatch) -> GraphBatch:
    return GraphBatch(
        nodes=(), senders=("edges",), receivers=("edges",),
        pos=None if batch_a.pos is None else (),
        graph_id=(), n_node=batch_a.n_node, n_graph=batch_a.n_graph)


def gnn_bundle(spec: ArchSpec, shape: ShapeSpec, smoke: bool) -> StepBundle:
    mod = _GNN_MODS[spec.arch_id]
    dims = dict(shape.dims)
    if smoke:
        # reduced instances of the same kind
        if shape.kind == "sampled":
            dims.update(n_nodes=500, batch_nodes=8, fanout=(3, 2),
                        d_feat=12, n_classes=5)
        elif shape.kind == "molecule":
            dims.update(n_nodes=6, n_edges=10, batch=3, d_feat=4)
        else:
            dims.update(n_nodes=40, n_edges=120, d_feat=12, n_classes=5)
    cfg = spec.smoke if smoke else spec.config

    if shape.kind in ("full_graph", "sampled"):
        n_classes = dims["n_classes"]
        cfg = _gnn_adapt(cfg, dims["d_feat"], n_classes)
        if shape.kind == "sampled":
            n_node, n_edge = sample_block_caps(dims["batch_nodes"],
                                               dims["fanout"])
            n_tgt = dims["batch_nodes"]
        else:
            n_node, n_edge = dims["n_nodes"], dims["n_edges"]
            n_tgt = None
        # pad the edge capacity so it divides any production mesh axis
        # combination (padded slots relax into the dump row)
        n_edge = -(-n_edge // 512) * 512
        batch_a = _gnn_batch_struct(spec.arch_id, n_node, n_edge,
                                    dims["d_feat"])

        def loss_fn(params, batch_and_labels):
            batch, labels = batch_and_labels
            if spec.arch_id == "pna":
                logits = pna_mod.forward(params, batch, cfg)
            else:
                logits = mod.node_forward(params, batch, cfg)
            if n_tgt is not None:
                logits = logits[:n_tgt]
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - ll)

        labels_a = SDS((n_tgt if n_tgt else n_node,), jnp.int32)
        labels_spec = ("batch",) if n_tgt else ()
    elif shape.kind == "molecule":
        cfg = _gnn_adapt(cfg, dims["d_feat"], 1)
        g = dims["batch"]
        n_node = dims["n_nodes"] * g
        n_edge = dims["n_edges"] * g
        batch_a = _gnn_batch_struct(spec.arch_id, n_node, n_edge,
                                    dims["d_feat"], n_graph=g)
        loss_fn = mod.make_loss(cfg) if spec.arch_id != "pna" else (
            lambda params, bt: jnp.mean(
                (pna_mod.forward(params, dataclasses.replace(
                    bt[0]), dataclasses.replace(cfg, node_level=False))
                 - bt[1]) ** 2))
        labels_a = SDS((g, 1), jnp.float32)
        labels_spec = ("batch", None)
    else:
        raise ValueError(shape.kind)

    params_a = jax.eval_shape(lambda: mod.init_params(cfg))
    p_specs = _replicated_like(params_a)
    step = make_train_step_fn(loss_fn, _OPT)
    opt_a = jax.eval_shape(lambda: opt.init(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_a), _OPT))
    o_specs = opt.state_specs(p_specs)
    return StepBundle(
        name=f"{spec.arch_id}/{shape.name}", fn=step, mesh_fn=None,
        abstract_args=(params_a, opt_a, (batch_a, labels_a)),
        arg_specs=(p_specs, o_specs, (_gnn_batch_specs(batch_a),
                                      labels_spec)),
        model_flops=_gnn_flops(spec.arch_id, cfg, n_edge, n_node))


def gnn_host_args(spec: ArchSpec, shape: ShapeSpec, seed: int = 0):
    """Small real graphs for the smoke path."""
    from repro.models.gnn.graph import from_numpy
    mod = _GNN_MODS[spec.arch_id]
    bundle = gnn_bundle(spec, shape, smoke=True)
    params_a, opt_a, (batch_a, labels_a) = bundle.abstract_args
    rng = np.random.default_rng(seed)
    n, e = batch_a.n_node, batch_a.senders.shape[0]
    d_feat = batch_a.nodes.shape[1]
    n_real_e = max(e // 2, 1)
    senders = rng.integers(0, n, n_real_e).astype(np.int32)
    receivers = rng.integers(0, n, n_real_e).astype(np.int32)
    keep = senders != receivers
    gid = None
    if batch_a.n_graph > 1:
        per = n // batch_a.n_graph
        gid = np.minimum(np.arange(n) // per, batch_a.n_graph - 1)
        gid = gid.astype(np.int32)
        # keep edges within one graph
        keep &= gid[senders] == gid[receivers]
    batch = from_numpy(
        rng.normal(size=(n, d_feat)).astype(np.float32),
        senders[keep], receivers[keep],
        pos=(rng.normal(size=(n, 3)).astype(np.float32)
             if batch_a.pos is not None else None),
        graph_id=gid, n_graph=batch_a.n_graph, e_cap=e)
    if labels_a.dtype == jnp.int32:
        labels = jnp.asarray(
            rng.integers(0, 5, labels_a.shape), jnp.int32)
    else:
        labels = jnp.asarray(
            rng.normal(size=labels_a.shape), jnp.float32)
    # cfg used inside loss is bound in the bundle; rebuild params to match
    dims = dict(shape.dims)
    cfg = spec.smoke
    if shape.kind == "molecule":
        cfg = _gnn_adapt(cfg, 4, 1)
    elif shape.kind == "sampled":
        cfg = _gnn_adapt(cfg, 12, 5)
    else:
        cfg = _gnn_adapt(cfg, 12, 5)
    params = mod.init_params(cfg, jax.random.PRNGKey(seed))
    state = opt.init(params, _OPT)
    return (params, state, (batch, labels))


# ==========================================================================
# RecSys family (DIEN)
# ==========================================================================
def _dien_batch_struct(cfg: dien_mod.DIENConfig, b: int, with_train: bool):
    t = cfg.seq_len
    d = {
        "hist_items": SDS((b, t), jnp.int32),
        "hist_cates": SDS((b, t), jnp.int32),
        "hist_mask": SDS((b, t), jnp.bool_),
        "target_item": SDS((b,), jnp.int32),
        "target_cate": SDS((b,), jnp.int32),
        "profile": SDS((b, cfg.profile_bags, cfg.bag_size), jnp.int32),
    }
    if with_train:
        d.update({
            "neg_items": SDS((b, t), jnp.int32),
            "neg_cates": SDS((b, t), jnp.int32),
            "label": SDS((b,), jnp.int32),
        })
    return d


def _dien_batch_spec(struct):
    return {k: ("batch",) + (None,) * (len(v.shape) - 1)
            for k, v in struct.items()}


def _dien_flops(cfg: dien_mod.DIENConfig, b: int, train: bool) -> float:
    d, h, t = cfg.beh_dim, cfg.gru_dim, cfg.seq_len
    gru = 2 * 3 * h * (d + h) * t * 2          # GRU + AUGRU
    mlp_in = h + d + cfg.profile_bags * cfg.embed_dim
    mlp = 2 * (mlp_in * cfg.mlp[0] + cfg.mlp[0] * cfg.mlp[1] + cfg.mlp[1])
    aux = 2 * (h + d) * 100 * t * 2 if train else 0
    total = (gru + mlp + aux) * b
    return float(total * (3 if train else 1))


def dien_bundle(spec: ArchSpec, shape: ShapeSpec, smoke: bool) -> StepBundle:
    cfg: dien_mod.DIENConfig = spec.smoke if smoke else spec.config
    dims = dict(shape.dims)
    if smoke:
        dims["batch"] = 4
        dims["n_candidates"] = 64
    b = dims["batch"]
    params_a = jax.eval_shape(lambda: dien_mod.init_params(cfg))
    p_specs = dien_mod.param_specs(cfg)

    if shape.kind == "recsys_train":
        loss_fn = dien_mod.make_train_loss(cfg)
        step = make_train_step_fn(loss_fn, _OPT)
        opt_a = jax.eval_shape(lambda: opt.init(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_a),
            _OPT))
        batch_a = _dien_batch_struct(cfg, b, with_train=True)
        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=step, mesh_fn=None,
            abstract_args=(params_a, opt_a, batch_a),
            arg_specs=(p_specs, opt.state_specs(p_specs),
                       _dien_batch_spec(batch_a)),
            model_flops=_dien_flops(cfg, b, train=True))

    if shape.kind == "recsys_serve":
        batch_a = _dien_batch_struct(cfg, b, with_train=False)

        def serve(params, batch):
            return dien_mod.forward(params, batch, cfg)

        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=serve, mesh_fn=None,
            abstract_args=(params_a, batch_a),
            arg_specs=(p_specs, _dien_batch_spec(batch_a)),
            model_flops=_dien_flops(cfg, b, train=False))

    if shape.kind == "retrieval":
        n_cand = dims["n_candidates"]
        batch_a = _dien_batch_struct(cfg, b, with_train=False)
        cand_a = {"item": SDS((n_cand,), jnp.int32),
                  "cate": SDS((n_cand,), jnp.int32)}

        def retrieve(params, batch, cand):
            return dien_mod.retrieval_scores(params, batch, cand, cfg)

        flops = (_dien_flops(cfg, b, train=False)
                 + 2.0 * b * cfg.beh_dim * n_cand)
        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=retrieve, mesh_fn=None,
            abstract_args=(params_a, batch_a, cand_a),
            arg_specs=(p_specs, _dien_batch_spec(batch_a),
                       {"item": ("qbatch",), "cate": ("qbatch",)}),
            model_flops=float(flops))

    raise ValueError(shape.kind)


def dien_host_args(spec: ArchSpec, shape: ShapeSpec, seed: int = 0):
    from repro.data import dien_batch
    cfg: dien_mod.DIENConfig = spec.smoke
    params = dien_mod.init_params(cfg, jax.random.PRNGKey(seed))
    b = 4
    full = dien_batch(0, b, cfg.seq_len, cfg.n_items, cfg.n_cates,
                      cfg.n_profile_vocab, cfg.profile_bags, cfg.bag_size,
                      seed=seed)
    full = {k: jnp.asarray(v) for k, v in full.items()}
    if shape.kind == "recsys_train":
        return (params, opt.init(params, _OPT), full)
    serve_batch = {k: full[k] for k in
                   ("hist_items", "hist_cates", "hist_mask", "target_item",
                    "target_cate", "profile")}
    if shape.kind == "recsys_serve":
        return (params, serve_batch)
    rng = np.random.default_rng(seed)
    cand = {"item": jnp.asarray(rng.integers(0, cfg.n_items, (64,)),
                                jnp.int32),
            "cate": jnp.asarray(rng.integers(0, cfg.n_cates, (64,)),
                                jnp.int32)}
    return (params, serve_batch, cand)


# ==========================================================================
# DSPC family (the paper's workload)
# ==========================================================================
def dspc_bundle(spec: ArchSpec, shape: ShapeSpec, smoke: bool) -> StepBundle:
    from repro.core import distributed as dist
    from repro.core.decremental import dec_spc
    from repro.core.graph import Graph
    from repro.core.incremental import inc_spc
    from repro.core.labels import SPCIndex

    cfg = spec.smoke if smoke else spec.config
    dims = dict(shape.dims)
    if smoke:
        dims.update(n=cfg.n, m=cfg.m, l_cap=cfg.l_cap, batch=cfg.query_batch)
    n, m, l_cap = dims["n"], dims["m"], dims["l_cap"]
    cap_e = 1 << (2 * m + m).bit_length()        # 2m doubled + headroom
    graph_a = Graph(src=SDS((cap_e,), jnp.int32),
                    dst=SDS((cap_e,), jnp.int32),
                    m2=SDS((), jnp.int32), n=n)
    graph_spec = Graph(src=("edges",), dst=("edges",), m2=(), n=n)
    index_a = SPCIndex(hub=SDS((n + 1, l_cap), jnp.int32),
                       dist=SDS((n + 1, l_cap), jnp.int32),
                       cnt=SDS((n + 1, l_cap), jnp.int64),
                       size=SDS((n + 1,), jnp.int32),
                       overflow=SDS((), jnp.int32), n=n)
    index_spec = SPCIndex(hub=(), dist=(), cnt=(), size=(), overflow=(),
                          n=n)
    # op-count proxy: per hub ~ one BFS over m edges + nL label merge
    build_ops = float(n) * (2.0 * m + 2.0 * n * l_cap) / 50.0
    update_ops = 2.0 * m + 4.0 * (n + 1) * l_cap

    if shape.kind == "dspc_build":
        def mesh_fn(mesh):
            return functools.partial(
                dist.make_distributed_builder(mesh, "model"), l_cap=l_cap)
        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=None, mesh_fn=mesh_fn,
            abstract_args=(graph_a,), arg_specs=(graph_spec,),
            model_flops=build_ops,
            notes="op-count proxy, not FLOPs")

    if shape.kind in ("dspc_inc", "dspc_dec"):
        fn = inc_spc if shape.kind == "dspc_inc" else dec_spc

        def wrapped(g, idx, a, b):
            return fn(g, idx, a, b)

        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=wrapped, mesh_fn=None,
            abstract_args=(graph_a, index_a, SDS((), jnp.int32),
                           SDS((), jnp.int32)),
            arg_specs=(graph_spec, index_spec, (), ()),
            model_flops=update_ops, notes="op-count proxy, not FLOPs")

    if shape.kind == "dspc_query":
        batch = dims["batch"]

        def mesh_fn(mesh):
            axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.axis_names)
            return dist.make_sharded_query(mesh, axes)

        return StepBundle(
            name=f"{spec.arch_id}/{shape.name}", fn=None, mesh_fn=mesh_fn,
            abstract_args=(index_a, SDS((batch,), jnp.int32),
                           SDS((batch,), jnp.int32)),
            arg_specs=(index_spec, ("qbatch",), ("qbatch",)),
            model_flops=4.0 * batch * l_cap * l_cap,
            notes="op-count proxy, not FLOPs")

    raise ValueError(shape.kind)


def dspc_host_args(spec: ArchSpec, shape: ShapeSpec, seed: int = 0):
    from repro.core import build_index, from_edges
    from repro.data import random_graph_edges
    cfg = spec.smoke
    edges = random_graph_edges(cfg.n, cfg.m, seed=seed)
    cap_e = 1 << (2 * cfg.m + cfg.m).bit_length()
    g = from_edges(cfg.n, edges, cap_e=cap_e)
    if shape.kind == "dspc_build":
        return (g,)
    idx = build_index(g, l_cap=cfg.l_cap)
    if shape.kind == "dspc_inc":
        present = set(edges)
        rng = np.random.default_rng(seed)
        while True:
            a, b = rng.integers(0, cfg.n, 2)
            if a != b and (min(a, b), max(a, b)) not in present:
                break
        return (g, idx, jnp.int32(int(a)), jnp.int32(int(b)))
    if shape.kind == "dspc_dec":
        a, b = edges[len(edges) // 2]
        return (g, idx, jnp.int32(a), jnp.int32(b))
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.integers(0, cfg.n, cfg.query_batch), jnp.int32)
    t = jnp.asarray(rng.integers(0, cfg.n, cfg.query_batch), jnp.int32)
    return (idx, s, t)


# ==========================================================================
# Ring variant (SPerf cell-B): node-sharded Equiformer-v2 for the
# full-batch-large shapes.
# ==========================================================================
def equiformer_ring_bundle(spec: ArchSpec, shape: ShapeSpec,
                           p_data: int = 16,
                           p_model: int = 16) -> StepBundle:
    from repro.models.gnn import equiformer_v2 as E2
    from repro.models.gnn import ring

    dims = dict(shape.dims)
    cfg = _gnn_adapt(spec.config, dims["d_feat"], dims["n_classes"])
    n = dims["n_nodes"]
    src_a, dst_a, n_loc = ring.bucket_specs(n, dims["n_edges"], p_data,
                                            p_model)
    n_pad = p_data * (n_loc + 1)
    nodes_a = SDS((n_pad, dims["d_feat"]), jnp.float32)
    pos_a = SDS((n_pad, 3), jnp.float32)
    labels_a = SDS((n_pad,), jnp.int32)          # -1 on pad rows
    params_a = jax.eval_shape(lambda: E2.init_params(cfg))
    p_specs = _replicated_like(params_a)
    opt_a = jax.eval_shape(lambda: opt.init(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_a),
        _OPT))

    def mesh_fn(mesh):
        def loss_fn(params, batch):
            nodes, pos, sb, db, labels = batch
            x = ring.forward_ring(params, nodes, pos, sb, db, cfg, mesh,
                                  p_data)
            logits = E2._lin(params["head"], x[..., 0]).astype(jnp.float32)
            mask = labels >= 0
            logz = jax.nn.logsumexp(logits, axis=-1)
            hit = (jnp.maximum(labels, 0)[:, None]
                   == jax.lax.broadcasted_iota(
                       jnp.int32, logits.shape[-1:], 0))
            ll = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
            per = jnp.where(mask, logz - ll, 0.0)
            return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1)

        return make_train_step_fn(loss_fn, _OPT)

    node_spec = ("ring_nodes",)
    return StepBundle(
        name=f"{spec.arch_id}/{shape.name}@ring", fn=None, mesh_fn=mesh_fn,
        abstract_args=(params_a, opt_a,
                       (nodes_a, pos_a, src_a, dst_a, labels_a)),
        arg_specs=(p_specs, opt.state_specs(p_specs),
                   (node_spec + (None,), node_spec + (None,),
                    ("ring_nodes", "ring_cols", None, None),
                    ("ring_nodes", "ring_cols", None, None), node_spec)),
        model_flops=_gnn_flops(spec.arch_id, cfg, dims["n_edges"], n) * 3,
        notes="ring-partitioned (SPerf cell-B)")


# ==========================================================================
# Dispatch
# ==========================================================================
_BUNDLERS = {"lm": lm_bundle, "gnn": gnn_bundle, "recsys": dien_bundle,
             "dspc": dspc_bundle}
_HOST_ARGS = {"lm": lm_host_args, "gnn": gnn_host_args,
              "recsys": dien_host_args, "dspc": dspc_host_args}


def make_bundle(arch_id: str, shape_name: str, *, smoke: bool = False,
                unroll: bool = False, variant: str = "") -> StepBundle:
    spec = get_arch(arch_id)
    shape = spec.shapes[shape_name]
    if variant == "ring":
        assert arch_id == "equiformer-v2" and shape.kind == "full_graph", \
            "ring variant is the equiformer-v2 full-graph optimization"
        return equiformer_ring_bundle(spec, shape)
    if variant:
        raise ValueError(f"unknown variant {variant!r}")
    if unroll and spec.family in ("lm", "recsys"):
        # roofline-measurement mode: scans unrolled so cost_analysis
        # counts every iteration (GNN models have no scans; DSPC loops
        # are data-dependent -> op-count proxies, see dspc_bundle)
        spec = dataclasses.replace(
            spec,
            config=dataclasses.replace(spec.config, unroll_scans=True),
            smoke=dataclasses.replace(spec.smoke, unroll_scans=True))
    return _BUNDLERS[spec.family](spec, shape, smoke)


def make_host_args(arch_id: str, shape_name: str, seed: int = 0):
    spec = get_arch(arch_id)
    shape = spec.shapes[shape_name]
    return _HOST_ARGS[spec.family](spec, shape, seed)


def all_cells(include_dspc: bool = True):
    from repro.configs import ARCH_IDS, ASSIGNED_ARCH_IDS
    ids = ARCH_IDS if include_dspc else ASSIGNED_ARCH_IDS
    out = []
    for a in ids:
        for s in get_arch(a).shapes:
            out.append((a, s))
    return out
