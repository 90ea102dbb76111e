"""The paper's four DLRM backbones: DNN, DCN, DeepFM, IPNN (§5.1.2), and
MLPerf's DLRM-DCNv2.

All share: a global embedding table over all feature fields (compressed by a
pluggable compressor — MPE or any baseline), a 1024-512-256 MLP with
BatchNorm (§5.1.5), and a sigmoid CTR head. They differ only in the
interaction branch. ``dcnv2`` runs low-rank cross layers over the features
and the MLP over the cross output (MLPerf's recommendation benchmark runs it
without batch norm).

batch = {"ids": (B, ΣL_f) int32 per-field local ids, "label": (B,)} and,
with ``dense_in``, "dense": (B, dense_in) float32. A field of
``multiplicity`` L_f holds L_f ids side by side, in field order; their
looked-up rows are summed into one (a fixed-size multi-hot bag). Dense
features go through a bottom MLP (ReLU on every layer) whose output is
concatenated in front of the flattened field embeddings.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import get_compressor
from repro.embeddings.bag import pool_fields
from repro.embeddings.table import slot_offsets, total_vocab
from repro.models.interactions import (CrossNetwork, LowRankCrossNet,
                                       fm_second_order, inner_products)
from repro.nn import init as initializers
from repro.nn.mlp import MLP


class DLRMConfig(NamedTuple):
    fields: tuple                      # tuple[FieldSpec]
    d_embed: int = 16                  # paper §5.1.5
    mlp_hidden: tuple = (1024, 512, 256)
    backbone: str = "dnn"              # dnn | dcn | dcnv2 | deepfm | ipnn
    n_cross_layers: int = 3
    compressor: str = "plain"
    comp_cfg: dict | None = None
    use_batchnorm: bool = True
    dense_in: int = 0                  # dense features a sample (0: none)
    bottom_hidden: tuple = ()          # the bottom MLP over them
    cross_rank: int = 512              # dcnv2 (MLPerf DLRM-DCNv2)


def _bag_sizes(cfg: DLRMConfig):
    """Each field's slot count, or None where every field is one-hot."""
    sizes = tuple(f.multiplicity for f in cfg.fields)
    return sizes if any(n != 1 for n in sizes) else None


class DLRM:
    @staticmethod
    def init(key, cfg: DLRMConfig, freqs=None):
        n = total_vocab(cfg.fields)
        f = len(cfg.fields)
        d_in = f * cfg.d_embed
        if cfg.dense_in:
            d_in += cfg.bottom_hidden[-1]
        keys = jax.random.split(key, 5)
        comp = get_compressor(cfg.compressor)
        if freqs is None:
            freqs = np.ones((n,), np.float64)
        emb_params, emb_buffers = comp.init(keys[0], n, cfg.d_embed, freqs, cfg.comp_cfg)

        if cfg.backbone == "ipnn":
            mlp_in = d_in + f * (f - 1) // 2
        else:
            mlp_in = d_in
        params = {
            "embedding": emb_params,
            "mlp": MLP.init(keys[1], mlp_in, cfg.mlp_hidden, d_out=1,
                            use_batchnorm=cfg.use_batchnorm),
        }
        if cfg.dense_in:
            params["bottom"] = MLP.init(keys[4], cfg.dense_in,
                                        cfg.bottom_hidden, use_batchnorm=False)
        if cfg.backbone == "dcn":
            params["cross"] = CrossNetwork.init(keys[2], d_in, cfg.n_cross_layers)
            params["cross_head"] = initializers.normal(keys[3], (d_in,), std=0.01)
        if cfg.backbone == "dcnv2":
            params["cross"] = LowRankCrossNet.init(keys[2], d_in, cfg.cross_rank,
                                                   cfg.n_cross_layers)
        if cfg.backbone == "deepfm":
            # first-order per-feature weights (the FM linear term)
            params["fm_linear"] = jnp.zeros((n,), jnp.float32)
            params["fm_bias"] = jnp.zeros((), jnp.float32)

        buffers = {
            "embedding": emb_buffers,
            "offsets": jnp.asarray(slot_offsets(cfg.fields)),
        }
        state = {"mlp": MLP.init_state(cfg.mlp_hidden, use_batchnorm=cfg.use_batchnorm)}
        return params, buffers, state

    @staticmethod
    def interact(params, state, emb, gids, cfg: DLRMConfig, *,
                 train: bool = False, dense=None):
        """The post-lookup half of ``apply``: interaction branch + MLP head
        over pre-gathered (pooled) embeddings ``emb (B, F, d)`` and, with
        ``cfg.dense_in``, the dense features ``dense (B, dense_in)``. Split
        out so serving paths that gather embeddings elsewhere (the tiered
        hot/cold store in ``repro.cache``) reuse the exact compute graph.
        ``gids`` are the globalized ids (only the DeepFM first-order term
        reads them). The bottom MLP runs under the ``bottom`` named scope,
        the low-rank cross layers under ``cross``. Returns
        (logits (B,), new_state)."""
        b, f, d = emb.shape
        flat = emb.reshape(b, f * d)
        if cfg.dense_in:
            with jax.named_scope("bottom"):
                bottom, _ = MLP.apply(params["bottom"], {}, dense)
            flat = jnp.concatenate([bottom, flat], axis=-1)

        if cfg.backbone == "ipnn":
            mlp_in = jnp.concatenate([flat, inner_products(emb)], axis=-1)
        elif cfg.backbone == "dcnv2":
            with jax.named_scope("cross"):
                mlp_in = LowRankCrossNet.apply(params["cross"], flat)
        else:
            mlp_in = flat
        deep, new_mlp_state = MLP.apply(params["mlp"], state["mlp"], mlp_in, train=train)
        logit = deep[:, 0]

        if cfg.backbone == "dcn":
            cross = CrossNetwork.apply(params["cross"], flat)
            logit = logit + cross @ params["cross_head"]
        elif cfg.backbone == "deepfm":
            first = jnp.sum(jnp.take(params["fm_linear"], gids, axis=0), axis=1)
            logit = logit + first + fm_second_order(emb) + params["fm_bias"]
        return logit, {"mlp": new_mlp_state}

    @staticmethod
    def apply(params, buffers, state, batch, cfg: DLRMConfig, *,
              train: bool = False, step=None):
        """Returns (logits (B,), new_state, reg_loss). The interaction
        branch and the MLP head run under the ``tower`` named scope; the
        compressor looks every id slot up at once and multi-hot fields are
        summed under ``embed_gather/bag_pool``."""
        comp = get_compressor(cfg.compressor)
        gids = batch["ids"] + buffers["offsets"][None, :]
        emb = comp.lookup(params["embedding"], buffers["embedding"], gids,
                          cfg.comp_cfg, train=train, step=step)  # (B, ΣL, d)
        sizes = _bag_sizes(cfg)
        if sizes is not None:
            with jax.named_scope("embed_gather"), jax.named_scope("bag_pool"):
                emb = pool_fields(emb, sizes)                    # (B, F, d)
        with jax.named_scope("tower"):
            logit, new_state = DLRM.interact(params, state, emb, gids, cfg,
                                             train=train,
                                             dense=batch.get("dense"))
        reg = comp.reg_loss(params["embedding"], buffers["embedding"], cfg.comp_cfg)
        return logit, new_state, reg

    @staticmethod
    def loss_fn(params, buffers, state, batch, cfg: DLRMConfig, *,
                lam: float = 0.0, train: bool = True, step=None):
        logits, new_state, reg = DLRM.apply(params, buffers, state, batch, cfg,
                                            train=train, step=step)
        with jax.named_scope("tower"):
            labels = batch["label"].astype(jnp.float32)
            ce = jnp.mean(jnp.maximum(logits, 0) - logits * labels
                          + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return ce + lam * reg, (new_state, ce)
