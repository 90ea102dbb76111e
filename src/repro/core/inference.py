"""Packed mixed-precision inference table (paper §4, TPU-adapted).

Storage layout: one bit-packed subtable per non-zero candidate width. Rows are
permuted so every subtable is dense; two small index vectors map a global
feature id to (width bucket, local row). Sub-8-bit codes are packed into
uint32 words (see ``repro.core.packing``); a lookup gathers the packed words,
unpacks with static shifts, and dequantizes ``α_b · code + β``.

This module owns the format, and ``_lookup_rows`` is the one place that
decodes it: every width bucket is gathered, unpacked and dequantized for
every id (static shapes), and a ``where`` keeps the id's own bucket. Its
inputs are what the lookups differ by — the tier mask (``keep``, the hot
bit of ``repro.cache.tiers``), the row shard a device owns (``shard``, for
the ``shard_map`` bodies of ``repro.dist.shard``) and ``use_kernel`` (the
fused Pallas gather+unpack+dequant of ``repro.kernels.mpe_lookup`` per
bucket). ``packed_lookup`` is the routine on the whole table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.mpe import MPEConfig
from repro.core.quantizer import dequantize_codes, quantize_codes


def _pad_rows(n: int, multiple: int) -> int:
    return max(multiple, -(-n // multiple) * multiple)


def _auto_pad_multiple(n: int, n_widths: int, cap: int = 512) -> int:
    """Largest power-of-two ≤ ``cap`` whose worst-case total padding
    (``multiple`` rows per non-empty subtable) stays under n/8 rows.

    512 at production scale — every mesh axis combination divides it, so row
    shards stay even — but a small table (tests, offline export) would drown
    in 512-row padding, so the multiple scales down (≥ 8, the sublane width).
    """
    m = 8
    while m < cap and m * 2 * n_widths * 8 <= n:
        m *= 2
    return m


#: rows quantized and packed per device program: the program's (rows, d)
#: temporaries stay near 3 x 256 MB however large a width bucket is (one
#: bucket of the Criteo-scale table is ~2 GB of float rows)
_PACK_CHUNK_ROWS = 1 << 22


@functools.partial(jax.jit, static_argnames=("b",))
def _quantize_pack(rows, alpha, beta, *, b: int):
    return packing.pack_codes(quantize_codes(rows, alpha, beta, b), b)


def _pack_bucket(rows: np.ndarray, alpha, beta, b: int, padded: int):
    """Packed words of ``rows`` at width ``b``, padded to ``padded`` rows.
    Pad rows hold the most-negative code, whose packed words are all zero."""
    parts = [_quantize_pack(jnp.asarray(rows[s:s + _PACK_CHUNK_ROWS]),
                            alpha, beta, b=b)
             for s in range(0, rows.shape[0], _PACK_CHUNK_ROWS)]
    pad = jnp.zeros((padded - rows.shape[0],
                     packing.words_per_row(rows.shape[1], b)), jnp.uint32)
    return jnp.concatenate(parts + [pad], axis=0)


def build_packed_table(emb, bits_idx_per_feature, alpha, beta, cfg: MPEConfig,
                       row_pad_multiple: int | None = None,
                       row_capacities: dict | None = None):
    """Quantize + pack a trained table.

    Returns a dict pytree ``table`` plus a static metadata dict.
    ``row_pad_multiple`` defaults to a size-aware power of two (see
    ``_auto_pad_multiple``); pass 512 explicitly to force production mesh
    alignment on a small table.

    ``row_capacities`` (``{"b<width>": rows, ...}``) pins each subtable to an
    *exact* padded row count instead of the multiple-derived one — the
    serving-time repack path (``repro.serve.repack``) uses this to re-pack a
    new precision assignment into the byte layout a compiled executable
    already expects, so the swap never recompiles. Raises ``ValueError`` when
    a width bucket holds more real rows than its pinned capacity.
    """
    emb = np.asarray(emb)
    bits_idx = np.asarray(bits_idx_per_feature)
    if row_pad_multiple is None:
        n_widths = sum(1 for b in cfg.bits if b != 0)
        row_pad_multiple = _auto_pad_multiple(emb.shape[0], n_widths)
    alpha_np = np.asarray(alpha)
    beta_np = np.asarray(beta)
    n, d = emb.shape

    subtables = {}
    local_idx = np.zeros((n,), np.int32)
    for i, b in enumerate(cfg.bits):
        sel = np.nonzero(bits_idx == i)[0]
        local_idx[sel] = np.arange(sel.shape[0], dtype=np.int32)
        if b == 0:
            continue
        rows = emb[sel] if sel.size else np.zeros((0, d), emb.dtype)
        if row_capacities is not None:
            padded = int(row_capacities[f"b{b}"])
            if rows.shape[0] > padded:
                raise ValueError(
                    f"width bucket b{b} holds {rows.shape[0]} rows, over its "
                    f"pinned capacity {padded} — a capacity-conforming repack "
                    f"must assign within the compiled subtable shapes")
        else:
            padded = _pad_rows(rows.shape[0], row_pad_multiple)
        subtables[f"b{b}"] = _pack_bucket(rows, alpha_np[i], beta_np,
                                          int(b), padded)

    table = {
        "subtables": subtables,
        "local_idx": jnp.asarray(local_idx),
        "width_idx": jnp.asarray(bits_idx.astype(np.int32)),
        "alpha": jnp.asarray(alpha_np),
        "beta": jnp.asarray(beta_np),
    }
    meta = {"bits": cfg.bits, "d": d, "n": n}
    return table, meta


def _bucket_dequant(sub, loc, alpha_i, beta, *, b: int, d: int,
                    use_kernel: bool = False):
    """Gather → unpack → dequantize rows ``loc`` of one width-``b``
    subtable: (n,) -> (n, d) fp32, through ``dequantize_codes`` or, with
    ``use_kernel``, the fused Pallas kernel."""
    if use_kernel:
        from repro.kernels.mpe_lookup.kernel import packed_lookup_pallas
        return packed_lookup_pallas(loc, sub, alpha_i, beta, b=b, d=d)
    words = jnp.take(sub, loc, axis=0)
    return dequantize_codes(packing.unpack_codes(words, b, d), alpha_i, beta)


def _lookup_rows(subtables, alpha, beta, bits, d: int, widx, lidx, *,
                 keep=None, shard=0, use_kernel: bool = False):
    """The packed lookup of ids already routed to ``(widx, lidx)`` (width
    bucket, row in its subtable): (n,) -> (n, d) fp32.

    ``subtables`` holds rows ``[shard·rows_b, (shard+1)·rows_b)`` of each
    width's subtable, ``rows_b`` being its local row count. An id reads
    zeros where ``keep`` is false, where its width is 0, or where this
    shard does not own its row — so the row shards of one table sum to the
    whole lookup with one non-zero term per id. With ``shard=0`` and the
    whole subtables every row is owned."""
    out = jnp.zeros((widx.shape[0], d), jnp.float32)
    for i, b in enumerate(bits):
        if b == 0:
            continue  # zero-width features contribute the zero vector
        sub = subtables[f"b{b}"]
        rows = sub.shape[0]
        loc = lidx - shard * rows
        sel = (widx == i) & (loc >= 0) & (loc < rows)
        if keep is not None:
            sel = sel & keep
        deq = _bucket_dequant(sub, jnp.clip(loc, 0, rows - 1), alpha[i], beta,
                              b=b, d=d, use_kernel=use_kernel)
        out = jnp.where(sel[:, None], deq, out)
    return out


def packed_lookup(table, meta, ids: jnp.ndarray) -> jnp.ndarray:
    """ids: any int shape -> (*ids.shape, d) fp32 dequantized embeddings."""
    flat = ids.reshape(-1)
    out = _lookup_rows(table["subtables"], table["alpha"], table["beta"],
                       meta["bits"], meta["d"],
                       jnp.take(table["width_idx"], flat, axis=0),
                       jnp.take(table["local_idx"], flat, axis=0))
    return out.reshape(*ids.shape, meta["d"])


def packed_lookup_fn(meta):
    """``packed_lookup`` with the static metadata bound: ``(table, ids) ->
    embeddings``. The closure is jit-stable (meta never appears as a traced
    argument), so the serving engine can compile one lookup-only executable
    per cell shape for the Figure-5 lookup-vs-compute latency split."""
    return lambda table, ids: packed_lookup(table, meta, ids)


def packed_storage_bytes(table) -> int:
    """Bytes of the packed subtables (index vectors reported separately)."""
    return sum(int(v.size) * 4 for v in jax.tree.leaves(table["subtables"]))


def packed_specs(n: int, d: int, cfg: MPEConfig, width_histogram,
                 row_pad_multiple: int = 512):
    """ShapeDtypeStruct stand-ins for a packed table — used by the dry-run.

    ``width_histogram``: fraction of rows per candidate width (sums to 1).
    """
    subtables = {}
    for i, b in enumerate(cfg.bits):
        if b == 0:
            continue
        rows = _pad_rows(int(n * width_histogram[i]), row_pad_multiple)
        subtables[f"b{b}"] = jax.ShapeDtypeStruct(
            (rows, packing.words_per_row(d, b)), jnp.uint32)
    return {
        "subtables": subtables,
        "local_idx": jax.ShapeDtypeStruct((n,), jnp.int32),
        "width_idx": jax.ShapeDtypeStruct((n,), jnp.int32),
        "alpha": jax.ShapeDtypeStruct((len(cfg.bits),), jnp.float32),
        "beta": jax.ShapeDtypeStruct((d,), jnp.float32),
    }
