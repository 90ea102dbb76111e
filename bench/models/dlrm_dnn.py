"""DLRM with the DNN backbone (paper §5.1.2) in its MPE search phase
(paper §3.2-3.3): the program's ``train.loop.Trainer`` with
``DLRM.loss_fn`` over the ``mpe_search`` compressor, as
``zoo.dlrm_builder`` binds it, from seeded weights; the count of its
operations; and the plain reference, written out below: the expectation
over the candidate quantizers with the paper's straight-through gradients
(Eqs. 2, 4-6, 8-10), the tower with training-mode batch norm, binary
cross-entropy, clipping by global norm and Adam.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import counts, reference_nn, seeded

SALT_TRAIN = 31


def n_rows(cfg: dict) -> int:
    return int(sum(cfg["field_vocabs"]))


def field_offsets(cfg: dict) -> np.ndarray:
    v = np.asarray(cfg["field_vocabs"], np.int64)
    return np.concatenate([[0], np.cumsum(v)[:-1]]).astype(np.int32)


def tower_flops(cfg: dict) -> int:
    """Forward FLOPs of one row's tower: the MLP over the concatenated
    field embeddings."""
    return counts.mlp_flops(len(cfg["field_vocabs"]) * cfg["d"],
                            cfg["mlp_hidden"])


def train_flops(cfg: dict) -> int:
    """Forward and backward FLOPs of one training sample's tower: the
    backward takes two products per forward product (the input's gradient,
    which the embeddings need, and the weights')."""
    return 3 * tower_flops(cfg)


def program_mlp(params, running):
    """The program's ``nn.mlp.MLP`` params and state of a reference tower."""
    mlp = {"layers": [{"kernel": w, "bias": b} for w, b in params["layers"]],
           "bn": [{"scale": s, "bias": b} for s, b in params["bn"]],
           "head": {"kernel": params["head"][0], "bias": params["head"][1]}}
    state = {"bn": [{"mean": m, "var": v} for m, v in running]}
    return mlp, state


def _fields(cfg):
    from repro.embeddings.table import FieldSpec
    return tuple(FieldSpec(f"f{i}", v)
                 for i, v in enumerate(cfg["field_vocabs"]))


# -- training: the MPE search phase ------------------------------------------

def _mpe(cfg):
    from repro.core.mpe import MPEConfig
    t = cfg["train"]
    return MPEConfig(bits=tuple(cfg["bits"]), group_size=cfg["group_size"],
                     tau=t["tau"], lam=t["lam"], embed_std=t["embed_std"])


def _lsq_init_alpha(std: float, b: int) -> float:
    """LSQ step-size start, 2 E|theta| / sqrt(P_b) for theta ~ N(0, std)."""
    if b < 1:
        return 1.0
    return float(2.0 * std * np.sqrt(2.0 / np.pi) / max(2 ** (b - 1) - 1, 1)
                 ** 0.5)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_table(key, n, d, std):
    return std * jax.random.normal(key, (n, d), jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _groups(vocabs, exponent, group, inv_h):
    """Frequency-sorted groups (paper §3.2): every global row's expected
    access frequency under the traffic's per-field Zipf law, rows ranked by
    it (stable), cut into groups of ``group``. Returns the group of each
    row and each group's frequency sum floored at 1, as the paper's code
    does."""
    parts = [inv_h[f] * (jnp.arange(1, v + 1, dtype=jnp.float32)
                         ** jnp.float32(-exponent))
             for f, v in enumerate(vocabs)]
    freq = jnp.concatenate(parts)
    n = freq.shape[0]
    order = jnp.argsort(-freq, stable=True)
    g = -(-n // group)
    gof = jnp.zeros((n,), jnp.int32).at[order].set(
        (jnp.arange(n) // group).astype(jnp.int32))
    sums = jax.ops.segment_sum(freq, gof, num_segments=g)
    return gof, jnp.maximum(sums, 1.0)


_init_tower = jax.jit(reference_nn.init_tower, static_argnums=(1, 2))


def train_buffers(cfg: dict, exponent: float):
    vocabs = tuple(int(v) for v in cfg["field_vocabs"])
    inv_h = jnp.asarray([1.0 / np.sum(np.arange(1, v + 1, dtype=np.float64)
                                      ** -exponent) for v in vocabs],
                        jnp.float32)
    return _groups(vocabs, float(exponent), int(cfg["group_size"]), inv_h)


def train_init(cfg: dict, seed: int, n_groups: int) -> dict:
    """The search phase's starting point in the reference's own layout:
    table N(0, embed_std), gamma 0 (uniform over widths), LSQ-initialized
    alpha, beta 0, and the recipe's tower start."""
    t = cfg["train"]
    n, d = n_rows(cfg), cfg["d"]
    emb = _init_table(seeded.prng_key(seed, SALT_TRAIN), n, d,
                      float(t["embed_std"]))
    d_in = len(cfg["field_vocabs"]) * d
    tparams, running = _init_tower(seeded.prng_key(seed, SALT_TRAIN + 1),
                                   d_in, tuple(cfg["mlp_hidden"]))
    return {"emb": emb,
            "gamma": jnp.zeros((n_groups, len(cfg["bits"])), jnp.float32),
            "alpha": jnp.asarray([_lsq_init_alpha(t["embed_std"], b)
                                  for b in cfg["bits"]], jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32),
            "tower": tparams, "running": running}


def program_params(init: dict):
    """Program params and state of a reference starting point."""
    mlp, state = program_mlp(init["tower"], init["running"])
    return ({"embedding": {k: init[k] for k in ("emb", "gamma", "alpha",
                                                 "beta")},
             "mlp": mlp}, {"mlp": state})


def leaf_name(path) -> str:
    """``embedding/emb``, ``mlp/layers/0/kernel``, ... of a pytree path."""
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def build_trainer(cfg: dict, seed: int, exponent: float):
    """The program's ``Trainer`` over ``DLRM.loss_fn`` with the
    ``mpe_search`` compressor, from the seeded start, and its buffers."""
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam
    t = cfg["train"]
    gof, freq_sum = train_buffers(cfg, exponent)
    params, state = program_params(train_init(cfg, seed, freq_sum.shape[0]))
    pcfg = DLRMConfig(fields=_fields(cfg), d_embed=cfg["d"],
                      mlp_hidden=tuple(cfg["mlp_hidden"]), backbone="dnn",
                      compressor="mpe_search", comp_cfg=_mpe(cfg)._asdict(),
                      use_batchnorm=True)
    buffers = {"embedding": {"group_of_feature": gof, "freq_sum": freq_sum},
               "offsets": jnp.asarray(field_offsets(cfg))}

    def loss_fn(p, bu, st, batch, *, step=None):
        return DLRM.loss_fn(p, bu, st, batch, pcfg, lam=t["lam"], train=True,
                            step=step)
    return Trainer(loss_fn, params, buffers, state,
                   adam(t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"]),
                   clip_norm=t["clip_norm"])


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


def program_grad_norms(trainer, b1: float) -> dict:
    """Per-leaf norm of the first step's gradient, as the optimizer got it,
    from Adam's first moment after that step: m_1 = (1 - b1) g."""
    norms = leaf_norms(trainer.carry["opt"]["mu"])
    return {leaf_name(p): float(v) / (1.0 - b1)
            for p, v in jax.tree_util.tree_leaves_with_path(norms)}


def _change_norms(params, init_params):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        params, init_params)


_change_norms_jit = jax.jit(_change_norms)


def program_change_norms(trainer, cfg: dict, seed: int, n_groups: int
                         ) -> dict:
    """Per-leaf norm of the parameters' change since the seeded start (the
    start is made again from the seed)."""
    init, _ = program_params(train_init(cfg, seed, n_groups))
    norms = _change_norms_jit(trainer.carry["params"], init)
    del init
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(norms)}


# the reference ---------------------------------------------------------------

def _bounds(b):
    return -(2 ** (b - 1)), 2 ** (b - 1) - 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _lsq(theta, alpha, beta, b):
    n_b, p_b = _bounds(b)
    v = (theta - beta) / alpha
    return alpha * jnp.clip(jnp.round(v), n_b, p_b) + beta


def _lsq_fwd(theta, alpha, beta, b):
    n_b, p_b = _bounds(b)
    v = (theta - beta) / alpha
    vbar = jnp.clip(jnp.round(v), n_b, p_b)
    return alpha * vbar + beta, (v, vbar)


def _lsq_bwd(b, res, g):
    """Paper Eqs. 4-6: theta passes the gradient inside the range; alpha
    takes N_b, P_b or round(v) - v; beta takes it where v is clipped."""
    n_b, p_b = _bounds(b)
    v, vbar = res
    inside = (v > n_b) & (v < p_b)
    d_theta = jnp.where(inside, g, 0)
    d_alpha = jnp.sum(g * jnp.where(v <= n_b, n_b,
                                    jnp.where(v >= p_b, p_b, vbar - v)))
    d_beta = jnp.sum(jnp.where(inside, 0, g),
                     axis=tuple(range(g.ndim - 1)))
    return d_theta, d_alpha.astype(g.dtype), d_beta


_lsq.defvjp(_lsq_fwd, _lsq_bwd)


def _ref_loss(p, gof_u, freq_sum, idx, label, cfg_static, mode, half):
    bits, tau, lam = cfg_static
    if half:   # a fault for the checks: the loss of half the batch
        idx, label = idx[: idx.shape[0] // 2], label[: label.shape[0] // 2]
    rows = p["emb"][idx]                                   # (B, F, d)
    probs_g = jax.nn.softmax(p["gamma"] / tau, axis=-1)    # (g, m)
    probs = probs_g[gof_u[idx]]                            # (B, F, m)
    e = jnp.zeros_like(rows)
    for i, b in enumerate(bits):
        if b:
            e = e + probs[..., i:i + 1] * _lsq(rows, p["alpha"][i],
                                               p["beta"], b)
    bsz, f, d = rows.shape
    z = reference_nn.tower(p["tower"], e.reshape(bsz, f * d), mode)
    y = label.astype(z.dtype)
    ce = jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
    reg = jnp.sum((probs_g @ jnp.asarray(bits, z.dtype)) / freq_sum)
    return ce + lam * reg


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _ref_step(p, m, v, gof_u, freq_sum, cfg_static, opt_static, mode, half,
              step, idx, label):
    lr, b1, b2, eps, clip = opt_static
    trainable = {k: p[k] for k in ("emb", "gamma", "alpha", "beta", "tower")}

    def loss(tr):
        return _ref_loss(dict(p, **tr), gof_u, freq_sum, idx, label,
                         cfg_static, mode, half)
    value, g = jax.value_and_grad(loss)(trainable)
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in leaves))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-12))
    g = jax.tree.map(lambda x: x * scale.astype(x.dtype), g)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    t = step.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda x, a, c: x - (lr * (a / bc1) / (jnp.sqrt(c / bc2) + eps)
                             ).astype(x.dtype), trainable, m, v)
    return dict(p, **new), m, v, value, g


def reference_train(cfg: dict, seed: int, batches, mode: str, *,
                    half: bool = False, exponent: float) -> dict:
    """Run the reference over ``batches`` (host dicts of local ids and
    labels) from the seeded start, on the rows those batches touch (rows
    that no batch touches get a zero gradient, so Adam leaves them where
    they are), padded to as many rows as the batches hold ids so that one
    compiled step serves every seed. Returns each step's loss and, by
    program leaf name, the first step's gradient norms and the norms of the
    change over all the steps."""
    t = cfg["train"]
    gof, freq_sum = train_buffers(cfg, exponent)
    init = train_init(cfg, seed, freq_sum.shape[0])
    offs = field_offsets(cfg)
    gids = [b["ids"].astype(np.int64) + offs[None, :] for b in batches]
    touched = np.unique(np.concatenate([g.reshape(-1) for g in gids]))
    bound = sum(g.size for g in gids)
    rows = jnp.asarray(np.pad(touched, (0, bound - touched.size)
                              ).astype(np.int32))
    p0 = {"emb": init["emb"][rows], "gamma": init["gamma"],
          "alpha": init["alpha"], "beta": init["beta"],
          "tower": init["tower"]}
    gof_u = gof[rows]
    del init, gof
    p0 = reference_nn.cast(p0, mode)
    p = p0
    m = jax.tree.map(jnp.zeros_like, {k: p[k] for k in
                                      ("emb", "gamma", "alpha", "beta",
                                       "tower")})
    v = jax.tree.map(jnp.zeros_like, m)
    cfg_static = (tuple(cfg["bits"]), float(t["tau"]), float(t["lam"]))
    opt_static = (float(t["lr"]), float(t["b1"]), float(t["b2"]),
                  float(t["eps"]), float(t["clip_norm"]))
    fs = reference_nn.cast(freq_sum, mode)
    losses, first_grad = [], None
    for k, (batch, g_ids) in enumerate(zip(batches, gids), start=1):
        idx = jnp.asarray(np.searchsorted(touched, g_ids).astype(np.int32))
        p, m, v, loss, g = _ref_step(
            p, m, v, gof_u, fs, cfg_static, opt_static, mode, half,
            jnp.asarray(k), idx, jnp.asarray(batch["label"]))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g
    change = jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                                 - b.astype(jnp.float32)))),
        {k: p[k] for k in m}, {k: p0[k] for k in m})
    gn = leaf_norms(first_grad)
    return {"losses": losses,
            "grad_norms": _program_names(gn),
            "change_norms": _program_names(change)}


def _program_names(tree) -> dict:
    """Reference leaf norms keyed by the program's leaf names."""
    mlp, _ = program_mlp(tree["tower"], [(0.0, 0.0)] * len(tree["tower"]["bn"]))
    named = {"embedding": {k: tree[k] for k in ("emb", "gamma", "alpha",
                                                 "beta")}, "mlp": mlp}
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(named)}
