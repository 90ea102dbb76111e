"""Training steps over multi-hot bags and dense features, through the
program's ``Trainer`` loop: ``train_steps``'s run (set-up, checked steps,
window, comparison) on batches of ``ids`` (B, ΣL_f), ``dense``
(B, dense_features) and ``label`` (B,).

Traffic keys: ``batch``, ``ring_batches``, ``zipf_exponent``,
``positive_rate`` and ``checked_steps`` as ``train_steps`` reads them, and
``dense_features``. Each field's bag holds the configuration's
``multi_hot_sizes[f]`` ids, each drawn iid from the field's Zipf law over
the rows the configuration holds; the slots lie side by side in field
order. Dense feature j is log1p of a seeded geometric count whose mean
runs log-evenly from 1 to 1000 over the features, as the heavy-tailed
counts of Criteo's integer features do.

The comparison is ``train_steps``'s but for ``loss_gap``, read over the
first ``LOSS_STEPS`` steps: Adam's first two steps move each weight by
about ±lr whatever its gradient's size, so the third step's loss moves
with the rounding of gradients near nought.
"""
from __future__ import annotations

import numpy as np

from yardstick import compare, spec, traffic

LOSS_STEPS = 2

# a module of its own: this driver's ``ring`` and ``gaps`` replace its
# names, and train_steps as a cell's driver keeps its own
_steps = spec.load_module("drivers", "train_steps")
_steps_gaps = _steps.gaps


def ring(cfg: dict, mix: dict, seed: int) -> list[dict]:
    n, bsz = int(mix["ring_batches"]), int(mix["batch"])
    sizes = [int(x) for x in cfg["multi_hot_sizes"]]
    ids = np.concatenate(
        [traffic.zipf_ids(v, mix["zipf_exponent"], n * bsz * k,
                          traffic.rng(seed, 1, f)).reshape(n * bsz, k)
         for f, (v, k) in enumerate(zip(cfg["field_vocabs"], sizes))],
        axis=1)
    m = int(mix["dense_features"])
    means = np.logspace(0.0, 3.0, m)
    draws = traffic.rng(seed, 5).geometric(1.0 / (1.0 + means),
                                           size=(n * bsz, m)) - 1
    dense = np.log1p(draws).astype(np.float32)
    labels = (traffic.rng(seed, 4).random(n * bsz)
              < mix["positive_rate"]).astype(np.int32)
    return [{"ids": ids[k * bsz:(k + 1) * bsz],
             "dense": dense[k * bsz:(k + 1) * bsz],
             "label": labels[k * bsz:(k + 1) * bsz]} for k in range(n)]


def gaps(ref, losses, grad_norms, change_norms, log=None) -> dict:
    out = _steps_gaps(ref, losses, grad_norms, change_norms, log)
    out["loss_gap"] = compare.loss_gap(losses[:LOSS_STEPS],
                                       ref["losses"][:LOSS_STEPS])
    return out


def run(h) -> dict:
    # looked up at each run, so that a ``gaps`` put in this module's place
    # (calibrate.py, the fault tests) is the one train_steps calls
    _steps.ring, _steps.gaps = ring, gaps
    return _steps.run(h)
