"""MLPerf's DLRM-DCNv2 (mlcommons/training, ``recommendation_v2/
torchrec_dlrm``) in MPE's search phase (paper §3.2-3.3): the program's
``train.loop.Trainer`` with ``DLRM.loss_fn`` over the ``mpe_search``
compressor and the ``dcnv2`` backbone, from seeded weights; the count of
its operations and of its bag's bytes; and the plain reference, written
out below in ``jax.numpy``: per-slot gather, the expectation over the
candidate quantizers with the paper's straight-through gradients (Eqs. 2,
4-6, 8-10), each field's slots summed, the bottom MLP over the dense
features, the low-rank cross layers (arXiv:2008.13535 Eq. 1, §5), the top
MLP, binary cross-entropy + λ·reg, clipping by global norm and Adam.

Departures from MLPerf (the configuration's ``assumed``):
- Adam with MPE's recipe (lr 1e-3, clip 10) in place of MLPerf's Adagrad;
- seeded ids (iid Zipf per slot), dense features and labels in place of
  the Criteo 1TB logs;
- the chip's share of a 64-chip deployment: the six tables above 1M rows
  hold one row shard each, the others are whole;
- Glorot-uniform V and W (torchrec draws them Xavier-normal).

Frequency groups rank every row by its expected lookups a sample: its
field's Zipf probability times the field's slot count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import counts, reference_nn, seeded, spec

SALT_TRAIN = 37
_dnn = spec.load_module("models", "dlrm_dnn")
_lsq = _dnn._lsq
leaf_name = _dnn.leaf_name
program_grad_norms = _dnn.program_grad_norms
n_rows = _dnn.n_rows


def slot_offsets(cfg: dict) -> np.ndarray:
    """(ΣL_f,) the field offset of each id slot, slots in field order."""
    return np.repeat(_dnn.field_offsets(cfg),
                     cfg["multi_hot_sizes"]).astype(np.int32)


def feature_width(cfg: dict) -> int:
    """Width of the cross layers' input: the bottom MLP's output and the
    pooled fields, concatenated."""
    return cfg["bottom_mlp"][-1] + len(cfg["field_vocabs"]) * cfg["d"]


def tower_flops(cfg: dict) -> int:
    """Forward FLOPs of one sample's dense part: the bottom MLP, the cross
    layers' two products each, and the top MLP with its logit."""
    k, r = feature_width(cfg), cfg["cross_rank"]
    bottom = counts.mlp_flops(cfg["dense_in"], cfg["bottom_mlp"][:-1],
                              cfg["bottom_mlp"][-1])
    cross = cfg["cross_layers"] * 2 * (2 * k * r)
    return bottom + cross + counts.mlp_flops(k, cfg["top_mlp"])


def train_flops(cfg: dict) -> int:
    """Forward and backward FLOPs of one training sample's dense part: two
    backward products per forward product (the input's gradient and the
    weights'), but for the bottom MLP's first layer, whose input (the dense
    features) takes no gradient."""
    return 3 * tower_flops(cfg) - 2 * cfg["dense_in"] * cfg["bottom_mlp"][0]


def bag_bytes(cfg: dict, mix: dict) -> int:
    """HBM bytes of one step's multi-hot bag, B·ΣL·(3·d·4 + 4 + 4·m): each
    looked-up row read, and read and written by the scatter-add into the
    table's gradient; its group id and its m width probabilities read."""
    ids = int(mix["batch"]) * int(sum(cfg["multi_hot_sizes"]))
    f32 = counts.F32
    return ids * (3 * cfg["d"] * f32 + f32 + f32 * len(cfg["bits"]))


# -- training: the MPE search phase ------------------------------------------

def train_buffers(cfg: dict, exponent: float):
    vocabs = tuple(int(v) for v in cfg["field_vocabs"])
    weight = jnp.asarray(
        [k / np.sum(np.arange(1, v + 1, dtype=np.float64) ** -exponent)
         for v, k in zip(vocabs, cfg["multi_hot_sizes"])], jnp.float32)
    return _dnn._groups(vocabs, float(exponent), int(cfg["group_size"]),
                        weight)


def _glorot(key, a, c):
    lim = jnp.sqrt(6.0 / (a + c))
    return jax.random.uniform(key, (a, c), minval=-lim, maxval=lim)


def _mlp_init(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [(_glorot(k, a, c), jnp.zeros((c,)))
            for k, a, c in zip(keys, dims[:-1], dims[1:])]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _init_dense(key, dense_in, bottom, width, rank, n_cross, top):
    """Bottom MLP, cross layers and top MLP with its head: Glorot-uniform
    kernels, zero biases."""
    kb, kc, kt = jax.random.split(key, 3)
    kc = jax.random.split(kc, 2 * n_cross)
    top_layers = _mlp_init(kt, [width, *top, 1])
    return {"bottom": _mlp_init(kb, [dense_in, *bottom]),
            "cross": {"v": [_glorot(kc[2 * i], width, rank)
                            for i in range(n_cross)],
                      "w": [_glorot(kc[2 * i + 1], rank, width)
                            for i in range(n_cross)],
                      "b": [jnp.zeros((width,)) for _ in range(n_cross)]},
            "top": {"layers": top_layers[:-1], "head": top_layers[-1]}}


def train_init(cfg: dict, seed: int, n_groups: int) -> dict:
    """The search phase's starting point in the reference's own layout:
    table N(0, embed_std), gamma 0 (uniform over widths), LSQ-initialized
    alpha, beta 0, and the dense part's start."""
    t = cfg["train"]
    d = cfg["d"]
    emb = _dnn._init_table(seeded.prng_key(seed, SALT_TRAIN), n_rows(cfg), d,
                           float(t["embed_std"]))
    dense = _init_dense(seeded.prng_key(seed, SALT_TRAIN + 1),
                        cfg["dense_in"], tuple(cfg["bottom_mlp"]),
                        feature_width(cfg), cfg["cross_rank"],
                        cfg["cross_layers"], tuple(cfg["top_mlp"]))
    return dict(dense, emb=emb,
                gamma=jnp.zeros((n_groups, len(cfg["bits"])), jnp.float32),
                alpha=jnp.asarray([_dnn._lsq_init_alpha(t["embed_std"], b)
                                   for b in cfg["bits"]], jnp.float32),
                beta=jnp.zeros((d,), jnp.float32))


def _dense_layers(layers):
    return [{"kernel": w, "bias": b} for w, b in layers]


def program_tree(tree: dict) -> dict:
    """The program's params tree of a tree in the reference's layout."""
    return {"embedding": {k: tree[k] for k in ("emb", "gamma", "alpha",
                                                "beta")},
            "bottom": {"layers": _dense_layers(tree["bottom"])},
            "cross": tree["cross"],
            "mlp": {"layers": _dense_layers(tree["top"]["layers"]),
                    "head": _dense_layers([tree["top"]["head"]])[0]}}


def build_trainer(cfg: dict, seed: int, exponent: float):
    """The program's ``Trainer`` over ``DLRM.loss_fn`` with the
    ``mpe_search`` compressor and the ``dcnv2`` backbone, from the seeded
    start, and its buffers."""
    from repro.embeddings.table import FieldSpec
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam
    t = cfg["train"]
    fields = tuple(FieldSpec(f"f{i}", v, k) for i, (v, k) in enumerate(
        zip(cfg["field_vocabs"], cfg["multi_hot_sizes"])))
    pcfg = DLRMConfig(fields=fields, d_embed=cfg["d"],
                      mlp_hidden=tuple(cfg["top_mlp"]), backbone="dcnv2",
                      n_cross_layers=cfg["cross_layers"],
                      cross_rank=cfg["cross_rank"],
                      dense_in=cfg["dense_in"],
                      bottom_hidden=tuple(cfg["bottom_mlp"]),
                      compressor="mpe_search",
                      comp_cfg=_dnn._mpe(cfg)._asdict(), use_batchnorm=False)
    gof, freq_sum = train_buffers(cfg, exponent)
    params = program_tree(train_init(cfg, seed, freq_sum.shape[0]))
    buffers = {"embedding": {"group_of_feature": gof, "freq_sum": freq_sum},
               "offsets": jnp.asarray(slot_offsets(cfg))}

    def loss_fn(p, bu, st, batch, *, step=None):
        return DLRM.loss_fn(p, bu, st, batch, pcfg, lam=t["lam"], train=True,
                            step=step)
    return Trainer(loss_fn, params, buffers, {"mlp": {}},
                   adam(t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"]),
                   clip_norm=t["clip_norm"])


def program_change_norms(trainer, cfg: dict, seed: int, n_groups: int
                         ) -> dict:
    """Per-leaf norm of the parameters' change since the seeded start (the
    start is made again from the seed)."""
    init = program_tree(train_init(cfg, seed, n_groups))
    norms = _dnn._change_norms_jit(trainer.carry["params"], init)
    del init
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(norms)}


# the reference ---------------------------------------------------------------

TRAINABLE = ("emb", "gamma", "alpha", "beta", "bottom", "cross", "top")


def _ref_loss(p, gof_u, freq_sum, idx, dense, label, cfg_static, mode, half):
    bits, tau, lam, sizes = cfg_static
    if half:   # a fault for the checks: the loss of half the batch
        n = idx.shape[0] // 2
        idx, dense, label = idx[:n], dense[:n], label[:n]
    rows = p["emb"][idx]                                   # (B, S, d)
    probs_g = jax.nn.softmax(p["gamma"] / tau, axis=-1)    # (g, m)
    probs = probs_g[gof_u[idx]]                            # (B, S, m)
    e = jnp.zeros_like(rows)
    for i, b in enumerate(bits):
        if b:
            e = e + probs[..., i:i + 1] * _lsq(rows, p["alpha"][i],
                                               p["beta"], b)
    pooled, s = [], 0
    for n in sizes:                                        # per-field sum
        pooled.append(jnp.sum(e[:, s:s + n], axis=1))
        s += n
    h = dense
    for w, b in p["bottom"]:
        h = jnp.maximum(reference_nn.mm(h, w, mode) + b, 0)
    x0 = jnp.concatenate([h, *pooled], axis=-1)
    x = x0
    for v, w, b in zip(p["cross"]["v"], p["cross"]["w"], p["cross"]["b"]):
        x = x0 * (reference_nn.mm(reference_nn.mm(x, v, mode), w, mode)
                  + b) + x
    for w, b in p["top"]["layers"]:
        x = jnp.maximum(reference_nn.mm(x, w, mode) + b, 0)
    w, b = p["top"]["head"]
    z = (reference_nn.mm(x, w, "f32" if mode == "stated" else mode) + b)[:, 0]
    y = label.astype(z.dtype)
    ce = jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
    reg = jnp.sum((probs_g @ jnp.asarray(bits, z.dtype)) / freq_sum)
    return ce + lam * reg


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _ref_step(p, m, v, gof_u, freq_sum, cfg_static, opt_static, mode, half,
              step, idx, dense, label):
    lr, b1, b2, eps, clip = opt_static
    trainable = {k: p[k] for k in TRAINABLE}

    def loss(tr):
        return _ref_loss(dict(p, **tr), gof_u, freq_sum, idx, dense, label,
                         cfg_static, mode, half)
    value, g = jax.value_and_grad(loss)(trainable)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in jax.tree.leaves(g)))
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
    """Run the reference over ``batches`` (host dicts of per-slot local
    ids, dense features and labels) from the seeded start, on the rows
    those batches touch (rows that no batch touches get a zero gradient, so
    Adam leaves them where they are), padded to as many rows as the
    batches hold ids so that one compiled step serves every seed. Returns
    each step's loss and, by program leaf name, the first step's gradient
    norms and the norms of the change over all the steps."""
    t = cfg["train"]
    gof, freq_sum = train_buffers(cfg, exponent)
    init = train_init(cfg, seed, freq_sum.shape[0])
    offs = slot_offsets(cfg)
    gids = [b["ids"].astype(np.int64) + offs[None, :] for b in batches]
    touched = np.unique(np.concatenate([g.reshape(-1) for g in gids]))
    bound = sum(g.size for g in gids)
    rows = jnp.asarray(np.pad(touched, (0, bound - touched.size)
                              ).astype(np.int32))
    p0 = dict({k: init[k] for k in TRAINABLE}, emb=init["emb"][rows])
    gof_u = gof[rows]
    del init, gof
    p0 = reference_nn.cast(p0, mode)
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, m)
    cfg_static = (tuple(cfg["bits"]), float(t["tau"]), float(t["lam"]),
                  tuple(int(k) for k in cfg["multi_hot_sizes"]))
    opt_static = (float(t["lr"]), float(t["b1"]), float(t["b2"]),
                  float(t["eps"]), float(t["clip_norm"]))
    fs = reference_nn.cast(freq_sum, mode)
    losses, first_grad = [], None
    for k, (batch, g_ids) in enumerate(zip(batches, gids), start=1):
        idx = jnp.asarray(np.searchsorted(touched, g_ids).astype(np.int32))
        p, m, v, loss, g = _ref_step(
            p, m, v, gof_u, fs, cfg_static, opt_static, mode, half,
            jnp.asarray(k), idx,
            reference_nn.cast(jnp.asarray(batch["dense"]), mode),
            jnp.asarray(batch["label"]))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g
    change = jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                                 - b.astype(jnp.float32)))),
        {k: p[k] for k in m}, {k: p0[k] for k in m})
    return {"losses": losses,
            "grad_norms": _program_names(_dnn.leaf_norms(first_grad)),
            "change_norms": _program_names(change)}


def _program_names(tree) -> dict:
    """Reference leaf norms keyed by the program's leaf names."""
    return {leaf_name(p): float(v) for p, v in
            jax.tree_util.tree_leaves_with_path(program_tree(tree))}
