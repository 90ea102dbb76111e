"""End-to-end MPE pipeline: search → sample → retrain → packed export (§3.4).

Model-agnostic: every model in the zoo stores its compressor state under
``params["embedding"]`` / ``buffers["embedding"]``, so phase transitions are
key swaps. The pipeline implements the paper's three retraining variants
(Table 4):

  - "none": quantize the searched embeddings at the sampled widths directly;
  - "lth":  Lottery-Ticket reset — *all* params back to their initial values;
  - "mpe":  the paper's scheme — embeddings reset to the search-phase init,
            step sizes α, offsets β and the interaction network W warm-started
            from the search phase.

The model is supplied as a builder: build(key, compressor, comp_cfg) ->
{"params", "buffers", "state", "loss_fn", "eval_fn"} where loss_fn follows the
Trainer signature.
"""
from __future__ import annotations

from typing import Callable

import jax
import numpy as np

from repro.core.inference import build_packed_table, packed_storage_bytes
from repro.core.mpe import MPEConfig
from repro.core.sampling import (MPERetrainEmbedding, average_bits,
                                 feature_bits, sample_group_bits,
                                 storage_ratio)
from repro.train.loop import Trainer


def jnp_array(x):
    import jax.numpy as jnp
    return jnp.asarray(x)


def _free_device_arrays(tree):
    """Release the device buffers of every live ``jax.Array`` in ``tree``."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def run_mpe_pipeline(build: Callable, data_fn: Callable, *, key,
                     mpe_cfg: MPEConfig, optimizer, search_steps: int,
                     retrain_steps: int, retrain_mode: str = "mpe",
                     eval_fn: Callable | None = None, log_fn=print,
                     ckpt_dir: str | None = None, prefetch: bool = False,
                     mesh=None, log_every: int = 100) -> dict:
    """Search → sample → retrain → pack. ``log_every`` sets how often each
    phase syncs to log its loss; the logged points come back per phase in
    ``result["history"]`` (``Trainer.history``)."""
    comp_cfg = mpe_cfg._asdict()

    # ---------------- phase 1: precision search ----------------
    bundle = build(key, "mpe_search", comp_cfg)
    init_snapshot = jax.tree.map(np.asarray, bundle["params"])  # host copy
    trainer = Trainer(bundle["loss_fn"], bundle["params"], bundle["buffers"],
                      bundle["state"], optimizer, mesh=mesh,
                      ckpt_dir=None if ckpt_dir is None else f"{ckpt_dir}/search")
    trainer.restore()
    log_fn(f"[mpe] search phase: {search_steps} steps")
    trainer.run(data_fn, search_steps, log_fn=log_fn, prefetch=prefetch,
                log_every=log_every)
    # host snapshots: the trainers donate their carries, so later phases must
    # not alias live device arrays from this one.
    search_params = jax.tree.map(np.asarray, trainer.params)
    search_state = jax.tree.map(np.asarray, trainer.state)
    history = {"search": trainer.history}
    # free the search phase's device state (table, optimizer moments) before
    # the retrain phase allocates its own: at Criteo width each phase's step
    # needs ~9 GB, two of them do not fit one 16 GB chip
    _free_device_arrays(trainer.carry)
    del trainer, bundle["params"]

    # ---------------- phase 2: precision sampling (Eq. 11) ----------------
    group_bits = sample_group_bits(search_params["embedding"], mpe_cfg)
    gof = bundle["buffers"]["embedding"]["group_of_feature"]
    fbits = feature_bits(group_bits, gof)
    avg_b = average_bits(fbits, mpe_cfg)
    ratio = storage_ratio(fbits, mpe_cfg)
    log_fn(f"[mpe] sampled avg bits={avg_b:.3f} ratio={ratio:.4f}")

    # ---------------- phase 3: retraining ----------------
    searched_alpha = search_params["embedding"]["alpha"]
    searched_beta = search_params["embedding"]["beta"]
    if retrain_mode == "none":
        emb_src = search_params["embedding"]["emb"]
        base = search_params
        steps = 0
    elif retrain_mode == "lth":
        base = jax.tree.map(jax.numpy.asarray, init_snapshot)
        emb_src = base["embedding"]["emb"]
        searched_alpha = base["embedding"]["alpha"]
        searched_beta = base["embedding"]["beta"]
        steps = retrain_steps
    elif retrain_mode == "mpe":
        base = search_params                         # warm-start W (paper §3.4)
        emb_src = jax.numpy.asarray(init_snapshot["embedding"]["emb"])
        steps = retrain_steps
    else:
        raise ValueError(retrain_mode)

    emb_params, emb_buffers = MPERetrainEmbedding.init(
        emb_src, searched_alpha, searched_beta, fbits)
    retrain_params = {k: v for k, v in base.items() if k != "embedding"}
    retrain_params["embedding"] = emb_params
    retrain_buffers = {k: v for k, v in bundle["buffers"].items() if k != "embedding"}
    retrain_buffers["embedding"] = emb_buffers

    rb = build(key, "mpe_retrain", {**comp_cfg, "init_emb": emb_src,
                                    "alpha": searched_alpha, "beta": searched_beta,
                                    "bits_idx": fbits})
    # rebuild only for the loss_fn closure; swap in our params/state
    retrain_params = jax.tree.map(jnp_array, retrain_params)
    trainer2 = Trainer(rb["loss_fn"], retrain_params, retrain_buffers,
                       jax.tree.map(jnp_array, search_state), optimizer,
                       mesh=mesh,
                       ckpt_dir=None if ckpt_dir is None else f"{ckpt_dir}/retrain")
    if steps:
        trainer2.restore()
        log_fn(f"[mpe] retrain phase ({retrain_mode}): {steps} steps")
        trainer2.run(data_fn, steps, log_fn=log_fn, prefetch=prefetch,
                     log_every=log_every)
    history["retrain"] = trainer2.history
    final_params = trainer2.params
    # the optimizer moments (2x the table) are done; packing needs the room
    _free_device_arrays(trainer2.carry["opt"])

    # ---------------- phase 4: packed export ----------------
    table, meta = build_packed_table(final_params["embedding"]["emb"], fbits,
                                     final_params["embedding"]["alpha"],
                                     final_params["embedding"]["beta"], mpe_cfg)
    result = {
        "search_params": search_params,
        "final_params": final_params,
        "buffers": retrain_buffers,
        "state": trainer2.state,
        "group_bits": np.asarray(group_bits),
        "feature_bits_idx": np.asarray(fbits),
        "avg_bits": avg_b,
        "storage_ratio": ratio,
        "packed_table": table,
        "packed_meta": meta,
        "packed_bytes": packed_storage_bytes(table),
        "history": history,
    }
    if eval_fn is not None:
        result["eval"] = eval_fn(final_params, retrain_buffers, trainer2.state)
        log_fn(f"[mpe] eval: {result['eval']}")
    return result
