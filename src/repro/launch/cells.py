"""Dry-run cell construction: (arch × shape × mesh) → lowerable step.

A Cell bundles the step function, ShapeDtypeStruct input stand-ins (never
allocated), and in/out shardings for the production mesh. Training cells
lower the *full* train step (loss + grad + Adam update); serve cells lower
the model's serving computation — decode steps for ``decode_*``/``long_*``
(one token against a KV cache), packed-table scoring for recsys serving.

Shape cells follow the assignment exactly:
  LM:     train_4k (256×4096) · prefill_32k (32×32768) · decode_32k
          (128 @ 32768 KV) · long_500k (1 @ 524288 KV)
  GNN:    full_graph_sm · minibatch_lg (fanout 15-10 sampler shapes) ·
          ogb_products · molecule
  recsys: train_batch (65536) · serve_p99 (512) · serve_bulk (262144) ·
          retrieval_cand (1 × 1,048,576)
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.configs.gin_tu import GRAPH_CELLS
from repro.core.inference import packed_specs
from repro.core.mpe import MPEConfig
from repro.data.graphs import NeighborSampler
from repro.dist.sharding import (dp_axes, lm_batch_pspecs, lm_kv_cache_pspecs,
                                 lm_logits_pspecs, lm_param_pspecs,
                                 packed_serve_pspecs, recsys_table_pspecs,
                                 replicate_like)
from repro.models.bst import BST
from repro.models.dlrm import DLRM
from repro.models.gnn import GIN
from repro.models.lm import LM
from repro.models.sasrec import SASRec
from repro.models.two_tower import TwoTower
from repro.models.wide_deep import WideDeep
from repro.serve.cells import score_step
from repro.train.optimizer import adam, apply_updates

PACKED_HIST = (0.0, 0.30, 0.20, 0.20, 0.10, 0.10, 0.10)  # widths 0..6 (b>0 rows)
MPE_BITS = (0, 1, 2, 3, 4, 5, 6)


class Cell(NamedTuple):
    name: str
    step_fn: Callable
    input_specs: tuple
    in_pspecs: tuple
    out_pspecs: Any
    meta: dict


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

LM_SHAPE_DEFS = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1, long=True),
}


def apply_overrides(cfg, overrides):
    """NamedTuple config overrides ('moe.x' targets the nested MoEConfig)."""
    if not overrides:
        return cfg
    direct = {k: v for k, v in overrides.items()
              if "." not in k and k in cfg._fields}
    cfg = cfg._replace(**direct)
    moe_over = {k.split(".", 1)[1]: v for k, v in overrides.items()
                if k.startswith("moe.")}
    if moe_over and getattr(cfg, "moe", None) is not None:
        cfg = cfg._replace(moe=cfg.moe._replace(**moe_over))
    return cfg


def build_lm_cell(arch_id: str, shape: str, multi_pod: bool,
                  overrides=None) -> Cell:
    spec = get_arch(arch_id)
    cfg = apply_overrides(spec.make_config(False), overrides)
    sd = LM_SHAPE_DEFS[shape]
    dp = dp_axes(multi_pod)
    buffers = {"embedding": {}}  # plain vocab table: no buffer state

    params_sds = jax.eval_shape(
        lambda k: LM.init(k, cfg)[0], sds((2,), jnp.uint32))
    p_pspecs = lm_param_pspecs(params_sds, cfg)

    if sd["kind"] == "train":
        opt = adam(1e-3)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_pspecs = {"step": P(), "mu": p_pspecs, "nu": p_pspecs}
        batch_sds = {"tokens": sds((sd["batch"], sd["seq"]), jnp.int32),
                     "labels": sds((sd["batch"], sd["seq"]), jnp.int32)}
        b_pspecs = lm_batch_pspecs(multi_pod)

        def train_step(params, opt_state, batch):
            (loss, _), grads = jax.value_and_grad(
                lambda p: LM.loss_fn(p, buffers, batch, cfg), has_aux=True
            )(params)
            updates, new_opt = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), new_opt, loss

        return Cell(
            name=f"{arch_id}/{shape}", step_fn=train_step,
            input_specs=(params_sds, opt_sds, batch_sds),
            in_pspecs=(p_pspecs, opt_pspecs, b_pspecs),
            out_pspecs=(p_pspecs, opt_pspecs, P()),
            meta={"kind": "train", "tokens": sd["batch"] * sd["seq"],
                  "family": "lm"},
        )

    cache_shape = (cfg.n_layers, sd["batch"], sd["seq"], cfg.n_kv_heads,
                   cfg.head_dim)
    kv_dtype = jnp.int8 if (overrides or {}).get("kv_int8") else jnp.bfloat16
    cache_ps = lm_kv_cache_pspecs(quantized=kv_dtype == jnp.int8,
                                  long_context=sd.get("long", False),
                                  multi_pod=multi_pod)
    caches_sds = {"k": sds(cache_shape, kv_dtype),
                  "v": sds(cache_shape, kv_dtype),
                  "len": sds((), jnp.int32)}
    if kv_dtype == jnp.int8:
        sshape = (cfg.n_layers, sd["batch"], 1, cfg.n_kv_heads, 1)
        caches_sds["k_scale"] = sds(sshape, jnp.float32)
        caches_sds["v_scale"] = sds(sshape, jnp.float32)

    if sd["kind"] == "prefill":
        tokens_sds = sds((sd["batch"], sd["seq"]), jnp.int32)

        def prefill_step(params, tokens):
            return LM.prefill(params, buffers, tokens, cfg, max_len=sd["seq"])

        return Cell(
            name=f"{arch_id}/{shape}", step_fn=prefill_step,
            input_specs=(params_sds, tokens_sds),
            in_pspecs=(p_pspecs, P(dp, None)),
            out_pspecs=(lm_logits_pspecs(sd["batch"], vocab_sharded=True,
                                         dp=dp), cache_ps),
            meta={"kind": "prefill", "tokens": sd["batch"] * sd["seq"],
                  "family": "lm"},
        )

    # decode: one new token against the KV cache
    tok_batch_ps = P(dp, None) if sd["batch"] > 1 else P(None, None)
    tokens_sds = sds((sd["batch"], 1), jnp.int32)

    def decode_step(params, tokens, caches):
        return LM.decode_step(params, buffers, tokens, caches, cfg)

    return Cell(
        name=f"{arch_id}/{shape}", step_fn=decode_step,
        input_specs=(params_sds, tokens_sds, caches_sds),
        in_pspecs=(p_pspecs, tok_batch_ps, cache_ps),
        out_pspecs=(lm_logits_pspecs(sd["batch"], dp=dp), cache_ps),
        meta={"kind": "decode", "tokens": sd["batch"], "family": "lm",
              "kv_len": sd["seq"]},
    )


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def build_gnn_cell(arch_id: str, shape: str, multi_pod: bool) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.make_config(False, shape=shape)
    cell = GRAPH_CELLS[shape]
    dp = dp_axes(multi_pod)
    edge_ax = (*dp, "model")
    opt = adam(1e-3)

    if shape == "minibatch_lg":
        n_nodes, n_edges = NeighborSampler.output_sizes(cell.batch_nodes,
                                                        cell.fanout)
    elif shape == "molecule":
        n_nodes = cell.n_graphs * cell.n_nodes
        n_edges = cell.n_graphs * cell.n_edges
    else:
        n_nodes, n_edges = cell.n_nodes, cell.n_edges
    # pad the edge list to the full mesh size (512 covers both meshes) so the
    # edge shards are even; padded edges carry edge_mask = False
    n_edges = -(-n_edges // 512) * 512

    graph_sds = {
        "edge_src": sds((n_edges,), jnp.int32),
        "edge_dst": sds((n_edges,), jnp.int32),
        "edge_mask": sds((n_edges,), jnp.bool_),
        "labels": sds((cell.n_graphs if cfg.readout == "graph" else n_nodes,),
                      jnp.int32),
    }
    graph_ps = {"edge_src": P(edge_ax), "edge_dst": P(edge_ax),
                "edge_mask": P(edge_ax), "labels": P(None)}
    if cfg.input_mode == "categorical":
        graph_sds["atom_ids"] = sds((n_nodes,), jnp.int32)
        graph_sds["graph_ids"] = sds((n_nodes,), jnp.int32)
        graph_ps["atom_ids"] = P(None)
        graph_ps["graph_ids"] = P(None)
        n_graphs = cell.n_graphs
    else:
        graph_sds["x"] = sds((n_nodes, cell.d_feat), jnp.float32)
        graph_ps["x"] = P(None, None)
        n_graphs = 0
    if shape == "minibatch_lg":
        graph_sds["label_mask"] = sds((n_nodes,), jnp.float32)
        graph_ps["label_mask"] = P(None)

    init_fn = lambda k: GIN.init(k, cfg)
    params_sds, buffers_sds = jax.eval_shape(init_fn, sds((2,), jnp.uint32))
    p_pspecs = replicate_like(params_sds)
    bufs_pspecs = replicate_like(buffers_sds)
    opt_sds = jax.eval_shape(opt.init, params_sds)
    opt_pspecs = {"step": P(), "mu": p_pspecs, "nu": p_pspecs}

    def train_step(params, opt_state, buffers, graph):
        if n_graphs:
            graph = dict(graph, n_graphs=n_graphs)
        (loss, _), grads = jax.value_and_grad(
            lambda p: GIN.loss_fn(p, buffers, graph, cfg, lam=1e-5),
            has_aux=True)(params)
        updates, new_opt = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), new_opt, loss

    return Cell(
        name=f"{arch_id}/{shape}", step_fn=train_step,
        input_specs=(params_sds, opt_sds, buffers_sds, graph_sds),
        in_pspecs=(p_pspecs, opt_pspecs, bufs_pspecs, graph_ps),
        out_pspecs=(p_pspecs, opt_pspecs, P()),
        meta={"kind": "train", "family": "gnn", "n_edges": n_edges,
              "n_nodes": n_nodes},
    )


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

RECSYS_BATCH = {"train_batch": 65536, "serve_p99": 512, "serve_bulk": 262144,
                "retrieval_cand": 1}
N_CANDIDATES = 1_048_576
SERVE_CANDS = 1000  # candidate set for sasrec online scoring


def _mpe_comp_cfg():
    return MPEConfig()._asdict()


def _packed_cfg(n, d):
    return {"bits": MPE_BITS, "d": d, "n": n}


def _mpe_buffer_specs(n: int, group_size: int = 128):
    g = -(-n // group_size)
    return {"group_of_feature": sds((n,), jnp.int32),
            "freq_sum": sds((g,), jnp.float32)}


def _mpe_param_specs(n: int, d: int, m: int = 7, group_size: int = 128):
    g = -(-n // group_size)
    return {"emb": sds((n, d), jnp.float32), "gamma": sds((g, m), jnp.float32),
            "alpha": sds((m,), jnp.float32), "beta": sds((d,), jnp.float32)}


def _mpe_emb_pspecs(rows_axes):
    return recsys_table_pspecs(rows_axes)


def _packed_param_specs(n, d):
    return packed_specs(n, d, MPEConfig(), PACKED_HIST)


def build_recsys_cell(arch_id: str, shape: str, multi_pod: bool,
                      overrides=None) -> Cell:
    spec = get_arch(arch_id)
    dp = dp_axes(multi_pod)
    rows_axes = (*dp, "model")
    batch = RECSYS_BATCH[shape]
    train = shape == "train_batch"
    builder = {
        "wide-deep": _wide_deep_cell, "dlrm-criteo": _dlrm_cell,
        "two-tower-retrieval": _two_tower_cell, "bst": _bst_cell,
        "sasrec": _sasrec_cell,
    }[arch_id]
    global _RECSYS_OVERRIDES
    _RECSYS_OVERRIDES = overrides or {}
    if _RECSYS_OVERRIDES.get("table_model_only"):
        rows_axes = ("model",)
    return builder(spec, shape, batch, train, dp, rows_axes, multi_pod)


_RECSYS_OVERRIDES: dict = {}


def _train_cell(name, model_loss, params_sds, buffers_sds, state_sds,
                p_pspecs, bufs_pspecs, st_pspecs, batch_sds, batch_ps, meta):
    import jax.numpy as _jnp
    moment_dtype = (_jnp.bfloat16 if _RECSYS_OVERRIDES.get("bf16_moments")
                    else None)
    opt = adam(1e-3, moment_dtype=moment_dtype)
    opt_sds = jax.eval_shape(opt.init, params_sds)
    opt_pspecs = {"step": P(), "mu": p_pspecs, "nu": p_pspecs}

    def train_step(params, opt_state, state, buffers, batch):
        (loss, (new_state, _)), grads = jax.value_and_grad(
            lambda p: model_loss(p, buffers, state, batch), has_aux=True)(params)
        updates, new_opt = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), new_opt, new_state, loss

    return Cell(
        name=name, step_fn=train_step,
        input_specs=(params_sds, opt_sds, state_sds, buffers_sds, batch_sds),
        in_pspecs=(p_pspecs, opt_pspecs, st_pspecs, bufs_pspecs, batch_ps),
        out_pspecs=(p_pspecs, opt_pspecs, st_pspecs, P()),
        meta=meta,
    )


def _serve_cell(name, serve_fn, inputs_sds, inputs_ps, out_ps, meta):
    return Cell(name=name, step_fn=serve_fn, input_specs=inputs_sds,
                in_pspecs=inputs_ps, out_pspecs=out_ps, meta=meta)


# -- wide-deep / dlrm (flat multi-field CTR) --------------------------------

def _flat_ctr_cell(spec, shape, batch, train, dp, rows_axes, multi_pod, *,
                   model, n_fields_attr="fields"):
    if train:
        cfg = spec.make_config(False)
        fields = cfg.fields
        n = int(sum(f.vocab for f in fields))
        d = cfg.d_embed
        init_fn = lambda k: model.init(k, cfg)
        params_sds, buffers_sds, state_sds = jax.eval_shape(
            init_fn, sds((2,), jnp.uint32))
        p_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), params_sds)
        p_pspecs["embedding"] = _mpe_emb_pspecs(rows_axes)
        if "wide" in params_sds:
            p_pspecs["wide"] = P(rows_axes)
        if "fm_linear" in params_sds:
            p_pspecs["fm_linear"] = P(rows_axes)
        bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
        bufs_pspecs["embedding"] = {"group_of_feature": P(rows_axes),
                                    "freq_sum": P(None)}
        st_pspecs = replicate_like(state_sds)
        batch_sds = {"ids": sds((batch, len(fields)), jnp.int32),
                     "label": sds((batch,), jnp.int32)}
        batch_ps = {"ids": P(dp, None), "label": P(dp)}

        def loss(p, bu, st, b):
            return model.loss_fn(p, bu, st, b, cfg, lam=1e-5, train=True,
                                 step=None)

        return _train_cell(f"{spec.arch_id}/{shape}", loss, params_sds,
                           buffers_sds, state_sds, p_pspecs, bufs_pspecs,
                           st_pspecs, batch_sds, batch_ps,
                           {"kind": "train", "family": "recsys", "rows": n,
                            "batch": batch})

    # serving on the packed table
    cfg = spec.make_config(False)._replace(compressor="packed")
    fields = cfg.fields
    n = int(sum(f.vocab for f in fields))
    d = cfg.d_embed
    cfg = cfg._replace(comp_cfg=_packed_cfg(n, d))
    n_eff = N_CANDIDATES if shape == "retrieval_cand" else batch

    plain_cfg = spec.make_config(False)  # structure donor for non-emb params
    params_sds, buffers_sds, state_sds = jax.eval_shape(
        lambda k: model.init(k, plain_cfg._replace(compressor="plain")),
        sds((2,), jnp.uint32))
    params_sds = dict(params_sds)
    params_sds["embedding"] = _packed_param_specs(n, d)
    p_pspecs = packed_serve_pspecs(params_sds, rows_axes=rows_axes)
    buffers_sds = dict(buffers_sds, embedding={})
    bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
    st_pspecs = replicate_like(state_sds)
    ids_sds = sds((n_eff, len(fields)), jnp.int32)
    ids_ps = P(rows_axes if shape == "retrieval_cand" else dp, None)

    serve_step = score_step(
        model, cfg, top_k=100 if shape == "retrieval_cand" else None)

    return _serve_cell(
        f"{spec.arch_id}/{shape}", serve_step,
        (params_sds, state_sds, buffers_sds, ids_sds),
        (p_pspecs, st_pspecs, bufs_pspecs, ids_ps),
        (P(None), P(None)) if shape == "retrieval_cand" else P(dp),
        {"kind": "serve", "family": "recsys", "rows": n, "batch": n_eff},
    )


def _wide_deep_cell(spec, shape, batch, train, dp, rows_axes, multi_pod):
    return _flat_ctr_cell(spec, shape, batch, train, dp, rows_axes, multi_pod,
                          model=WideDeep)


def _dlrm_cell(spec, shape, batch, train, dp, rows_axes, multi_pod):
    return _flat_ctr_cell(spec, shape, batch, train, dp, rows_axes, multi_pod,
                          model=DLRM)


# -- two-tower ---------------------------------------------------------------

def _two_tower_cell(spec, shape, batch, train, dp, rows_axes, multi_pod):
    cfg = spec.make_config(False)
    fields = (*cfg.user_fields, *cfg.item_fields)
    n = int(sum(f.vocab for f in fields))
    d = cfg.d_embed
    fu, fi = len(cfg.user_fields), len(cfg.item_fields)

    if train:
        params_sds, buffers_sds, state_sds = jax.eval_shape(
            lambda k: TwoTower.init(k, cfg), sds((2,), jnp.uint32))
        p_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), params_sds)
        p_pspecs["embedding"] = _mpe_emb_pspecs(rows_axes)
        bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
        bufs_pspecs["embedding"] = {"group_of_feature": P(rows_axes),
                                    "freq_sum": P(None)}
        st_pspecs = replicate_like(state_sds)
        batch_sds = {"user_ids": sds((batch, fu), jnp.int32),
                     "item_ids": sds((batch, fi), jnp.int32),
                     "item_logq": sds((batch,), jnp.float32)}
        batch_ps = {"user_ids": P(dp, None), "item_ids": P(dp, None),
                    "item_logq": P(dp)}

        def loss(p, bu, st, b):
            return TwoTower.loss_fn(p, bu, st, b, cfg, lam=1e-5, train=True)

        return _train_cell(f"{spec.arch_id}/{shape}", loss, params_sds,
                           buffers_sds, state_sds, p_pspecs, bufs_pspecs,
                           st_pspecs, batch_sds, batch_ps,
                           {"kind": "train", "family": "recsys", "rows": n,
                            "batch": batch})

    scfg = cfg._replace(compressor="packed", comp_cfg=_packed_cfg(n, d))
    params_sds, buffers_sds, state_sds = jax.eval_shape(
        lambda k: TwoTower.init(k, cfg), sds((2,), jnp.uint32))
    params_sds = dict(params_sds, embedding=_packed_param_specs(n, d))
    p_pspecs = packed_serve_pspecs(params_sds, rows_axes=rows_axes)
    buffers_sds = dict(buffers_sds, embedding={})
    bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
    st_pspecs = replicate_like(state_sds)

    if shape == "retrieval_cand":
        u_sds = sds((1, fu), jnp.int32)
        c_sds = sds((N_CANDIDATES, fi), jnp.int32)

        def serve_step(params, state, buffers, user_ids, cand_ids):
            return TwoTower.retrieval_score(params, buffers, state, user_ids,
                                            cand_ids, scfg, top_k=100)

        return _serve_cell(
            f"{spec.arch_id}/{shape}", serve_step,
            (params_sds, state_sds, buffers_sds, u_sds, c_sds),
            (p_pspecs, st_pspecs, bufs_pspecs, P(None, None),
             P(rows_axes, None)),
            (P(None), P(None)),
            {"kind": "serve", "family": "recsys", "rows": n,
             "batch": N_CANDIDATES})

    u_sds = sds((batch, fu), jnp.int32)
    i_sds = sds((batch, fi), jnp.int32)

    def serve_step(params, state, buffers, user_ids, item_ids):
        u, _ = TwoTower.user_tower(params, buffers, state, user_ids, scfg)
        v, _ = TwoTower.item_tower(params, buffers, state, item_ids, scfg)
        return jnp.sum(u * v, axis=-1)

    return _serve_cell(
        f"{spec.arch_id}/{shape}", serve_step,
        (params_sds, state_sds, buffers_sds, u_sds, i_sds),
        (p_pspecs, st_pspecs, bufs_pspecs, P(dp, None), P(dp, None)),
        P(dp),
        {"kind": "serve", "family": "recsys", "rows": n, "batch": batch})


# -- bst ----------------------------------------------------------------------

def _bst_cell(spec, shape, batch, train, dp, rows_axes, multi_pod):
    cfg = spec.make_config(False)
    n = cfg.item_vocab + sum(f.vocab for f in cfg.ctx_fields)
    d = cfg.d_embed
    fc = len(cfg.ctx_fields)
    s = cfg.seq_len

    if train:
        params_sds, buffers_sds, state_sds = jax.eval_shape(
            lambda k: BST.init(k, cfg), sds((2,), jnp.uint32))
        p_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), params_sds)
        p_pspecs["embedding"] = _mpe_emb_pspecs(rows_axes)
        bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
        bufs_pspecs["embedding"] = {"group_of_feature": P(rows_axes),
                                    "freq_sum": P(None)}
        st_pspecs = replicate_like(state_sds)
        batch_sds = {"seq_ids": sds((batch, s), jnp.int32),
                     "target_id": sds((batch,), jnp.int32),
                     "ctx_ids": sds((batch, fc), jnp.int32),
                     "label": sds((batch,), jnp.int32)}
        batch_ps = {"seq_ids": P(dp, None), "target_id": P(dp),
                    "ctx_ids": P(dp, None), "label": P(dp)}

        def loss(p, bu, st, b):
            return BST.loss_fn(p, bu, st, b, cfg, lam=1e-5, train=True)

        return _train_cell(f"{spec.arch_id}/{shape}", loss, params_sds,
                           buffers_sds, state_sds, p_pspecs, bufs_pspecs,
                           st_pspecs, batch_sds, batch_ps,
                           {"kind": "train", "family": "recsys", "rows": n,
                            "batch": batch})

    scfg = cfg._replace(compressor="packed", comp_cfg=_packed_cfg(n, d))
    params_sds, buffers_sds, state_sds = jax.eval_shape(
        lambda k: BST.init(k, cfg), sds((2,), jnp.uint32))
    params_sds = dict(params_sds, embedding=_packed_param_specs(n, d))
    p_pspecs = packed_serve_pspecs(params_sds, rows_axes=rows_axes)
    buffers_sds = dict(buffers_sds, embedding={})
    bufs_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), buffers_sds)
    st_pspecs = replicate_like(state_sds)

    n_eff = N_CANDIDATES if shape == "retrieval_cand" else batch
    row_ax = rows_axes if shape == "retrieval_cand" else dp
    batch_sds = {"seq_ids": sds((n_eff, s), jnp.int32),
                 "target_id": sds((n_eff,), jnp.int32),
                 "ctx_ids": sds((n_eff, fc), jnp.int32),
                 "label": sds((n_eff,), jnp.int32)}
    batch_ps = {"seq_ids": P(row_ax, None), "target_id": P(row_ax),
                "ctx_ids": P(row_ax, None), "label": P(row_ax)}

    def serve_step(params, state, buffers, batch_in):
        logits, _, _ = BST.apply(params, buffers, state, batch_in, scfg,
                                 train=False)
        if shape == "retrieval_cand":
            return tuple(jax.lax.top_k(logits, 100))
        return logits

    return _serve_cell(
        f"{spec.arch_id}/{shape}", serve_step,
        (params_sds, state_sds, buffers_sds, batch_sds),
        (p_pspecs, st_pspecs, bufs_pspecs, batch_ps),
        (P(None), P(None)) if shape == "retrieval_cand" else P(row_ax),
        {"kind": "serve", "family": "recsys", "rows": n, "batch": n_eff})


# -- sasrec -------------------------------------------------------------------

def _sasrec_cell(spec, shape, batch, train, dp, rows_axes, multi_pod):
    cfg = spec.make_config(False)
    n, d, s = cfg.item_vocab, cfg.d_embed, cfg.seq_len

    if train:
        params_sds, buffers_sds, _ = jax.eval_shape(
            lambda k: SASRec.init(k, cfg), sds((2,), jnp.uint32))
        p_pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), params_sds)
        p_pspecs["embedding"] = _mpe_emb_pspecs(rows_axes)
        bufs_pspecs = {"embedding": {"group_of_feature": P(rows_axes),
                                     "freq_sum": P(None)}}
        batch_sds = {k: sds((batch, s), jnp.int32)
                     for k in ("seq_ids", "pos_ids", "neg_ids")}
        batch_sds["mask"] = sds((batch, s), jnp.float32)
        batch_ps = {k: P(dp, None)
                    for k in ("seq_ids", "pos_ids", "neg_ids", "mask")}

        def loss(p, bu, st, b):
            return SASRec.loss_fn(p, bu, st, b, cfg, lam=1e-5, train=True)

        return _train_cell(f"{spec.arch_id}/{shape}", loss, params_sds,
                           buffers_sds, {}, p_pspecs, bufs_pspecs, {},
                           batch_sds, batch_ps,
                           {"kind": "train", "family": "recsys", "rows": n,
                            "batch": batch})

    scfg = cfg._replace(compressor="packed", comp_cfg=_packed_cfg(n, d))
    params_sds, _, _ = jax.eval_shape(lambda k: SASRec.init(k, cfg),
                                      sds((2,), jnp.uint32))
    params_sds = dict(params_sds, embedding=_packed_param_specs(n, d))
    p_pspecs = packed_serve_pspecs(params_sds, rows_axes=rows_axes)
    buffers_sds = {"embedding": {}}
    bufs_pspecs = {"embedding": {}}

    if shape == "retrieval_cand":
        seq_sds = sds((1, s), jnp.int32)
        cand_sds = sds((N_CANDIDATES,), jnp.int32)

        def serve_step(params, buffers, seq_ids, cand_ids):
            return SASRec.score_candidates(params, buffers, seq_ids, cand_ids,
                                           scfg, top_k=100)

        return _serve_cell(
            f"{spec.arch_id}/{shape}", serve_step,
            (params_sds, buffers_sds, seq_sds, cand_sds),
            (p_pspecs, bufs_pspecs, P(None, None), P(rows_axes)),
            (P(None, None), P(None, None)),
            {"kind": "serve", "family": "recsys", "rows": n,
             "batch": N_CANDIDATES})

    seq_sds = sds((batch, s), jnp.int32)
    cand_sds = sds((SERVE_CANDS,), jnp.int32)

    def serve_step(params, buffers, seq_ids, cand_ids):
        return SASRec.score_candidates(params, buffers, seq_ids, cand_ids,
                                       scfg, top_k=100)

    return _serve_cell(
        f"{spec.arch_id}/{shape}", serve_step,
        (params_sds, buffers_sds, seq_sds, cand_sds),
        (p_pspecs, bufs_pspecs, P(dp, None), P(None)),
        (P(dp, None), P(dp, None)),
        {"kind": "serve", "family": "recsys", "rows": n, "batch": batch})


# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape: str, multi_pod: bool = False,
               overrides=None) -> Cell:
    spec = get_arch(arch_id)
    if spec.family == "lm":
        return build_lm_cell(arch_id, shape, multi_pod, overrides)
    if spec.family == "gnn":
        return build_gnn_cell(arch_id, shape, multi_pod)
    return build_recsys_cell(arch_id, shape, multi_pod, overrides)
