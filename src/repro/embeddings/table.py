"""Field bookkeeping for multi-field categorical inputs.

The paper (and CTR practice) keeps one global embedding table across all
feature fields; a sample's per-field local ids are globalized by adding the
field's vocabulary offset. This keeps MPE's frequency grouping global — a rare
user-id can land in the same precision group as a rare ad-id.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np


class FieldSpec(NamedTuple):
    name: str
    vocab: int
    multiplicity: int = 1  # >1 for multi-hot fields (bag-reduced)


def field_offsets(fields: Sequence[FieldSpec]) -> np.ndarray:
    sizes = np.asarray([f.vocab for f in fields], np.int64)
    return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)


def slot_offsets(fields: Sequence[FieldSpec]) -> np.ndarray:
    """(ΣL_f,) each id slot's field offset: a multi-hot field's
    ``multiplicity`` slots sit side by side in the field's order, so its
    offset repeats once per slot. With one slot per field this is
    ``field_offsets``."""
    return np.repeat(field_offsets(fields),
                     [f.multiplicity for f in fields]).astype(np.int32)


def total_vocab(fields: Sequence[FieldSpec]) -> int:
    return int(sum(f.vocab for f in fields))


def globalize_ids(local_ids: jnp.ndarray, offsets) -> jnp.ndarray:
    """local_ids: (B, F) per-field ids -> (B, F) global table rows."""
    return local_ids + jnp.asarray(offsets)[None, :]
