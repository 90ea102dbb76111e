"""Hot/cold tiered storage for MPE packed tables.

MPE's frequency-grouped precision assignment (paper §3.2/§4.1) hands the
serving layer a ready-made cache policy: the high-frequency features that get
wide precision are exactly the rows worth pinning device-resident, while the
long tail can live in host memory and be fetched per request — the split
*Mixed-Precision Embedding Using a Cache* (Yang et al., 2020) validates at
production scale.

``TieredTableStore`` splits each per-width packed subtable of a
``core.inference.build_packed_table`` pytree into

  - a **hot tier**: the top-``hot_fraction`` features by frequency, kept as
    device arrays (HBM on an accelerator). The hot tier is a pytree shaped
    for ``repro.dist.sharding.tiered_hot_pspecs`` — it row-shards over the
    mesh exactly like the monolithic table; the cold tier never does.
  - a **cold tier**: the remaining rows as host ``np.ndarray``s. A lookup
    that touches them gathers the *packed words* on the host and moves only
    those bytes over PCIe (``jax.device_put``), so the transfer inherits the
    table's compression ratio.

Lookups are bit-exact against ``core.inference.packed_lookup`` on the
monolithic table at every hot fraction. The hot tier *is* that lookup —
``tiered_hot_lookup`` runs ``core.inference``'s routine over the hot
subtables with the hot bit as its keep mask — and the cold fill unpacks the
same packed words with the same static shifts and dequantizes with the same
``α_b · code + β`` expression. The tier merge is a ``jnp.where`` on the
tier mask (never an add), so no float combine can perturb a row.

Per-tier hit/miss/byte counters are first-class — ``counters()`` backs the
hit-rate-vs-hot-fraction curve in ``benchmarks/prefetch_bench.py`` and the
hand-computed trace asserted in ``tests/test_cache.py``.

The store is an **inclusive cache**: the host side keeps a full packed
mirror of every row (indexed by ``local_idx``), and the hot tier holds
device copies of the currently-resident subset. That makes the incremental
tier moves of ``cache.policy`` cheap and safe — a demotion only flips the
``is_hot`` bit (the authoritative row never left the mirror), a promotion
copies one mirror row into a free hot slot, and both preserve every array
shape so compiled tiered cells never recompile (``apply_moves``). Training
updates enter through ``writeback``, which re-quantizes under the feature's
current width and writes the mirror *first*, then patches the hot copy if
resident — so a concurrent demotion can never lose an update (writeback
ordering).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.inference import _auto_pad_multiple, _lookup_rows, _pad_rows
from repro.core.quantizer import dequantize_codes, int_bounds, quantize_codes
from repro.embeddings.frequency import hot_feature_mask


class ColdPrefetch(NamedTuple):
    """In-flight cold-row fill for one id batch.

    Produced by ``TieredTableStore.prefetch_cold`` — the host gather has
    happened and the ``jax.device_put`` of the packed words has been
    *issued* (asynchronously) but not awaited, so creating one of these a
    step ahead overlaps the host→device copy with the current step's
    compute. Consumed by ``cold_part``/``lookup``.
    """
    n: int                 # flat batch size the fill covers
    parts: tuple           # ((width_index, positions, device_words), ...)
    bytes_moved: int       # packed bytes issued host→device


class TieredTableStore:
    """Frequency-split hot/cold view of one packed inference table.

    ``table``/``meta`` are the pytree + static metadata from
    ``build_packed_table``; ``frequencies`` is any per-feature access-count
    vector (training-log counts or the Zipf profile); ``hot_fraction`` pins
    the top fraction of features device-resident (0 = everything cold,
    1 = everything hot — both degenerate tiers stay valid).

    ``row_pad_multiple`` pads hot-subtable rows the same way the monolithic
    table pads (size-aware power of two, 512 at production scale) so the hot
    tier row-shards cleanly under ``tiered_hot_pspecs``.
    """

    def __init__(self, table, meta, frequencies, hot_fraction: float, *,
                 row_pad_multiple: int | None = None, device=None):
        self.meta = {"bits": tuple(meta["bits"]), "d": int(meta["d"]),
                     "n": int(meta["n"])}
        self.hot_fraction = float(hot_fraction)
        self.device = device
        self._freqs = np.asarray(frequencies)
        bits = self.meta["bits"]

        width_idx = np.asarray(table["width_idx"])
        is_hot = self._hot_mask(width_idx)

        if row_pad_multiple is None:
            n_widths = sum(1 for b in bits if b != 0)
            row_pad_multiple = _auto_pad_multiple(max(int(is_hot.sum()), 1),
                                                  max(n_widths, 1))
        self._row_pad_multiple = int(row_pad_multiple)
        self._policy = None
        self.hot_version = 0   # bumped on any hot-tier array replacement

        self._rebuild(table, is_hot, capacities=None)
        self.reset_counters()

    def _hot_mask(self, width_idx: np.ndarray) -> np.ndarray:
        """Frequency policy for the hot tier: top-``hot_fraction`` features,
        plus every zero-width feature — those never occupy a subtable row
        (their embedding is the zero vector), so hot residency is free."""
        is_hot = hot_feature_mask(self._freqs, self.hot_fraction)
        for i, b in enumerate(self.meta["bits"]):
            if b == 0:
                is_hot[width_idx == i] = True
        return is_hot

    def _rebuild(self, table, is_hot: np.ndarray,
                 capacities: dict | None) -> None:
        """(Re)split ``table`` into the two tiers. ``capacities`` pins each
        hot subtable to an exact row count (the repack path — compiled hot
        shapes must survive); ``None`` pads to ``row_pad_multiple``."""
        bits, d, n = self.meta["bits"], self.meta["d"], self.meta["n"]
        width_idx = np.asarray(table["width_idx"])
        local_idx = np.asarray(table["local_idx"])
        device = self.device

        tier_local = np.zeros((n,), np.int32)
        hot_subs, mirror, free_slots = {}, {}, {}
        hot_bytes = cold_bytes = mirror_bytes = 0
        for i, b in enumerate(bits):
            if b == 0:
                continue
            sub = np.asarray(table["subtables"][f"b{b}"])       # (rows_p, W)
            feats = np.nonzero(width_idx == i)[0]
            hot_f = feats[is_hot[feats]]
            cold_f = feats[~is_hot[feats]]
            tier_local[hot_f] = np.arange(hot_f.size, dtype=np.int32)
            tier_local[cold_f] = np.arange(cold_f.size, dtype=np.int32)
            # pad hot rows like build_packed_table pads (all-N_b rows), so
            # row shards stay aligned to whole packed rows
            n_b, _ = int_bounds(b)
            pad_row = np.asarray(
                packing.pack_codes(jnp.full((1, d), n_b, jnp.int32), b))
            if capacities is not None:
                padded = int(capacities[f"b{b}"])
                if hot_f.size > padded:
                    raise ValueError(
                        f"hot tier b{b} holds {hot_f.size} rows, over its "
                        f"compiled capacity {padded}")
            else:
                padded = _pad_rows(hot_f.size, self._row_pad_multiple)
            hot_rows = np.tile(pad_row, (padded, 1))
            hot_rows[:hot_f.size] = sub[local_idx[hot_f]]
            hot_subs[f"b{b}"] = jax.device_put(jnp.asarray(hot_rows), device)
            # inclusive host mirror: every packed row, indexed by local_idx —
            # the authoritative copy that cold fills, promotions and
            # writebacks all read/write
            mirror[f"b{b}"] = np.array(sub)
            # hot pad rows double as free promotion slots; stored descending
            # so pop() hands out the lowest slot first (deterministic)
            free_slots[f"b{b}"] = list(range(padded - 1, hot_f.size - 1, -1))
            hot_bytes += hot_f.size * packing.row_bytes(d, b)
            cold_bytes += cold_f.size * packing.row_bytes(d, b)
            mirror_bytes += mirror[f"b{b}"].nbytes

        # host-side routing vectors (the cold path plans gathers with them)
        self._is_hot_np = is_hot
        self._width_idx_np = width_idx
        self._tier_local_np = tier_local
        self._local_idx_np = local_idx
        self._mirror = mirror
        self._free_slots = free_slots
        self._alpha_np = np.asarray(table["alpha"])
        self._beta_np = np.asarray(table["beta"])

        # device-resident hot tier: the pytree a serve cell binds (layout
        # contract: repro.dist.sharding.tiered_hot_pspecs)
        self.hot = {
            "subtables": hot_subs,
            "tier_local": jax.device_put(jnp.asarray(tier_local), device),
            "is_hot": jax.device_put(jnp.asarray(is_hot), device),
            "width_idx": jax.device_put(jnp.asarray(width_idx.astype(np.int32)),
                                        device),
            "alpha": jax.device_put(jnp.asarray(table["alpha"]), device),
            "beta": jax.device_put(jnp.asarray(table["beta"]), device),
        }
        self._storage = {"hot_bytes": int(hot_bytes),
                         "cold_bytes": int(cold_bytes),
                         "mirror_bytes": int(mirror_bytes)}
        self.hot_version += 1

    # -- serving-time repack (repro.serve.repack) ---------------------------

    def refresh(self, table, meta, frequencies=None) -> None:
        """Re-seat a re-packed table into this store *without changing any
        hot-tier array shape* — the hook ``Engine._rebind_tiered`` uses to
        keep compiled tiered cells valid across a serving-time repack.

        The hot/cold split is recomputed from the (optionally updated)
        frequencies under the same policy as construction, then clamped to
        the compiled hot-subtable capacities: if a repack widened enough hot
        features to overflow a bucket, the coldest overflow features demote
        to the cold tier (flipping ``is_hot`` values only — the masks keep
        their shapes, so the executable is unchanged). Counters stay
        cumulative; ``storage()`` reflects the new split."""
        meta = {"bits": tuple(meta["bits"]), "d": int(meta["d"]),
                "n": int(meta["n"])}
        if meta != self.meta:
            raise ValueError(
                f"refresh changes the table's static metadata "
                f"({self.meta} -> {meta}) — that is a re-registration, "
                f"not a repack")
        if frequencies is not None:
            self._freqs = np.asarray(frequencies)

        width_idx = np.asarray(table["width_idx"])
        if self._policy is not None:
            # an adaptive policy owns the split: carry the live tier bits
            # across the repack instead of re-seating from training
            # frequencies, and rank overflow demotions by live score
            is_hot = self._is_hot_np.copy()
            for i, b in enumerate(self.meta["bits"]):
                if b == 0:
                    is_hot[width_idx == i] = True
            rank = (self._policy.scores()
                    if hasattr(self._policy, "scores") else self._freqs)
        else:
            is_hot = self._hot_mask(width_idx)
            rank = self._freqs
        caps = {k: int(v.shape[0]) for k, v in self.hot["subtables"].items()}
        for i, b in enumerate(self.meta["bits"]):
            if b == 0:
                continue
            hot_f = np.nonzero(is_hot & (width_idx == i))[0]
            over = hot_f.size - caps[f"b{b}"]
            if over > 0:    # demote the coldest overflow features
                order = hot_f[np.argsort(rank[hot_f], kind="stable")]
                is_hot[order[:over]] = False
        self._rebuild(table, is_hot, capacities=caps)

    # -- incremental tier moves (cache.policy) ------------------------------

    def attach_policy(self, policy):
        """Wire a tier policy (``cache.policy``) into the lookup stream:
        every ``prefetch_cold`` feeds its valid ids to ``policy.observe``,
        so the policy scores exactly the traffic the hit/miss counters see.
        Returns the policy for chaining."""
        self._policy = policy
        return policy

    @property
    def policy(self):
        """The attached tier policy, or ``None`` (static split)."""
        return self._policy

    def free_slot_counts(self) -> dict:
        """Free hot-subtable rows per width key (``{"b8": 3, ...}``) — the
        promotion headroom ``cache.policy`` plans against."""
        return {k: len(v) for k, v in self._free_slots.items()}

    def apply_moves(self, promote, demote) -> dict:
        """Apply one ``TierPlan``'s promotions/demotions *incrementally* —
        no re-pack, no shape change, so compiled tiered cells stay valid
        (the engine rebinds the updated arrays; zero recompiles is
        counter-asserted in tests/test_policy.py).

        Demotions flip the tier bit and free the slot — the inclusive
        mirror already holds the row, nothing is copied. Promotions copy
        mirror rows into free slots (one pow-2-padded device scatter per
        width). Plans must be feasible: every promoted feature cold, every
        demoted feature hot, and per-width promotions ≤ free slots after
        demotions (``DecayAdmissionPolicy.plan`` guarantees this)."""
        promote = np.asarray(promote, np.int64).reshape(-1)
        demote = np.asarray(demote, np.int64).reshape(-1)
        if promote.size == 0 and demote.size == 0:
            return {"promotions": 0, "demotions": 0, "bytes": 0}
        bits, d, n = self.meta["bits"], self.meta["d"], self.meta["n"]
        widx = self._width_idx_np
        if promote.size and self._is_hot_np[promote].any():
            raise ValueError("plan promotes features already hot")
        if demote.size and not self._is_hot_np[demote].all():
            raise ValueError("plan demotes features already cold")
        moved = np.concatenate([promote, demote])
        if np.unique(moved).size != moved.size:
            raise ValueError("plan lists a feature twice")
        if any(bits[widx[f]] == 0 for f in moved):
            raise ValueError("zero-width features never occupy a hot row")

        # 1) demote: free the slot, flip the bit — the mirror is authoritative
        for f in demote:
            self._free_slots[f"b{bits[widx[f]]}"].append(
                int(self._tier_local_np[f]))
        self._is_hot_np[demote] = False

        # 2) promote: copy mirror rows into free slots, batched per width
        new_subs = dict(self.hot["subtables"])
        nbytes = 0
        slot_idx, slot_val = [], []
        for i, b in enumerate(bits):
            if b == 0:
                continue
            sel = promote[widx[promote] == i]
            if sel.size == 0:
                continue
            free = self._free_slots[f"b{b}"]
            if sel.size > len(free):
                raise ValueError(
                    f"hot tier b{b} has {len(free)} free slots, plan "
                    f"promotes {sel.size}")
            slots = np.asarray([free.pop() for _ in range(sel.size)],
                               np.int32)
            self._tier_local_np[sel] = slots
            rows = self._mirror[f"b{b}"][self._local_idx_np[sel]]
            nbytes += rows.nbytes
            sub = new_subs[f"b{b}"]
            p2 = 1 << max(int(np.ceil(np.log2(sel.size))), 2)
            slots_p = np.full((p2,), sub.shape[0], np.int32)  # OOB: dropped
            slots_p[:sel.size] = slots
            rows_p = np.zeros((p2, rows.shape[1]), rows.dtype)
            rows_p[:sel.size] = rows
            new_subs[f"b{b}"] = _scatter_rows(sub, jnp.asarray(slots_p),
                                              jnp.asarray(rows_p))
            slot_idx.append(sel)
            slot_val.append(slots)
        self._is_hot_np[promote] = True

        # 3) device routing vectors: one padded scatter each, only the moves
        p2 = 1 << max(int(np.ceil(np.log2(moved.size))), 2)
        idx = np.full((p2,), n, np.int32)                     # OOB: dropped
        idx[:moved.size] = moved
        hotv = np.zeros((p2,), bool)
        hotv[:promote.size] = True
        new_is_hot = _scatter_vec(self.hot["is_hot"], jnp.asarray(idx),
                                  jnp.asarray(hotv))
        new_tl = self.hot["tier_local"]
        if slot_idx:
            up_i, up_v = np.concatenate(slot_idx), np.concatenate(slot_val)
            p2 = 1 << max(int(np.ceil(np.log2(up_i.size))), 2)
            tidx = np.full((p2,), n, np.int32)
            tidx[:up_i.size] = up_i
            tval = np.zeros((p2,), np.int32)
            tval[:up_v.size] = up_v
            new_tl = _scatter_vec(new_tl, jnp.asarray(tidx),
                                  jnp.asarray(tval))
        self.hot = dict(self.hot, subtables=new_subs, is_hot=new_is_hot,
                        tier_local=new_tl)
        self.hot_version += 1

        # storage accounting stays pad-free, keyed on the tier bit
        for i, b in enumerate(bits):
            if b == 0:
                continue
            delta = (int((widx[promote] == i).sum())
                     - int((widx[demote] == i).sum())) * packing.row_bytes(d, b)
            self._storage["hot_bytes"] += delta
            self._storage["cold_bytes"] -= delta
        self._counters["promotions"] += int(promote.size)
        self._counters["demotions"] += int(demote.size)
        self._counters["promote_bytes"] += int(nbytes)
        return {"promotions": int(promote.size),
                "demotions": int(demote.size), "bytes": int(nbytes)}

    # -- training-update writeback ------------------------------------------

    def writeback(self, ids, vectors) -> dict:
        """Flow training-time embedding updates into the store without a
        re-pack: re-quantize each vector under its feature's *current*
        width and overwrite the packed row.

        Ordering contract: the host mirror (the cold store) is written
        **first** — it is the authoritative copy — and the hot subtable is
        patched after, only for currently-resident features. A demotion
        interleaved between the two writes therefore cannot lose the
        update: demotions copy nothing, they re-expose the already-updated
        mirror row. Duplicate ids resolve last-write-wins. Zero-width
        features store no row and are skipped. Hot and cold reads of a
        written feature are bit-exact to each other (same packed words in
        both tiers; round-trip asserted in tests/test_policy.py)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        vectors = np.asarray(vectors, np.float32).reshape(ids.size,
                                                          self.meta["d"])
        if ids.size:
            # np.unique keeps the first occurrence; scan reversed to keep
            # the last (last-write-wins)
            _, first = np.unique(ids[::-1], return_index=True)
            keep = np.sort(ids.size - 1 - first)
            ids, vectors = ids[keep], vectors[keep]
        bits = self.meta["bits"]
        widx = self._width_idx_np[ids] if ids.size else np.zeros(0, np.int32)
        new_subs = dict(self.hot["subtables"])
        nbytes, written, touched_hot = 0, 0, False
        for i, b in enumerate(bits):
            if b == 0:
                continue
            sel = np.nonzero(widx == i)[0]
            if sel.size == 0:
                continue
            f = ids[sel]
            codes = quantize_codes(jnp.asarray(vectors[sel]),
                                   self._alpha_np[i], self._beta_np, b)
            words = np.asarray(packing.pack_codes(codes, b))
            # cold store FIRST: mirror is authoritative (see docstring)
            self._mirror[f"b{b}"][self._local_idx_np[f]] = words
            nbytes += words.nbytes
            written += int(f.size)
            hot_sel = np.nonzero(self._is_hot_np[f])[0]
            if hot_sel.size:
                slots = self._tier_local_np[f[hot_sel]].astype(np.int32)
                sub = new_subs[f"b{b}"]
                p2 = 1 << max(int(np.ceil(np.log2(hot_sel.size))), 2)
                slots_p = np.full((p2,), sub.shape[0], np.int32)
                slots_p[:hot_sel.size] = slots
                rows_p = np.zeros((p2, words.shape[1]), words.dtype)
                rows_p[:hot_sel.size] = words[hot_sel]
                new_subs[f"b{b}"] = _scatter_rows(sub, jnp.asarray(slots_p),
                                                  jnp.asarray(rows_p))
                nbytes += int(words[hot_sel].nbytes)
                touched_hot = True
        if touched_hot:
            self.hot = dict(self.hot, subtables=new_subs)
            self.hot_version += 1
        self._counters["writebacks"] += written
        self._counters["writeback_bytes"] += int(nbytes)
        return {"written": written, "bytes": int(nbytes)}

    # -- counters -----------------------------------------------------------

    def reset_counters(self):
        self._counters = {"hot_lookups": 0, "cold_lookups": 0,
                          "bytes_moved": 0, "prefetches": 0,
                          "promotions": 0, "demotions": 0,
                          "promote_bytes": 0,
                          "writebacks": 0, "writeback_bytes": 0}

    def counters(self) -> dict:
        """Cumulative tier traffic: ``hot_lookups``/``cold_lookups`` count id
        lookups served per tier, ``bytes_moved`` the packed host→device bytes
        of cold fills, ``hit_rate`` their ratio, plus the static per-tier
        storage bytes. Adaptive-policy activity rides along:
        ``promotions``/``demotions``/``promote_bytes`` from ``apply_moves``
        and ``writebacks``/``writeback_bytes`` from ``writeback``."""
        c = dict(self._counters, **self._storage)
        total = c["hot_lookups"] + c["cold_lookups"]
        c["hit_rate"] = c["hot_lookups"] / total if total else 1.0
        return c

    # -- cold tier (host side) ----------------------------------------------

    def prefetch_cold(self, ids, valid=None) -> ColdPrefetch:
        """Gather the batch's cold rows on the host and *issue* their async
        device transfer. Call this one step (or one chunk) ahead of the
        compute that consumes it — ``jax.device_put`` returns immediately,
        so the copy overlaps whatever is already dispatched.

        ``valid``: optional boolean mask over ``ids`` (or over its leading
        axis — e.g. the batcher's per-row validity mask) — invalid entries
        are batcher padding: they fetch nothing and stay out of the
        counters, so hit rates and bytes reflect real traffic only.

        Row counts are padded up to powers of two so the downstream eager
        unpack/scatter in ``cold_part`` sees a handful of stable shapes
        (shape-churn would compile a fresh executable per request); padded
        entries carry an out-of-bounds position, which the scatter drops.
        ``bytes_moved`` counts the real rows only."""
        ids = np.asarray(ids)
        flat = ids.reshape(-1)
        if valid is None:
            valid_flat = np.ones(flat.shape, bool)
        else:
            valid = np.asarray(valid, bool)
            if valid.shape != ids.shape:   # per-row mask -> per-id mask
                valid = np.broadcast_to(valid.reshape(valid.shape[0],
                                                      *([1] * (ids.ndim - 1))),
                                        ids.shape)
            valid_flat = valid.reshape(-1)
        if self._policy is not None:
            # the policy sees exactly the traffic the counters see
            self._policy.observe(flat[valid_flat])
        widx = self._width_idx_np[flat]
        lidx = self._local_idx_np[flat]
        cold = ~self._is_hot_np[flat] & valid_flat
        parts, nbytes = [], 0
        for i, b in enumerate(self.meta["bits"]):
            if b == 0:
                continue
            sub = self._mirror[f"b{b}"]
            sel = np.nonzero(cold & (widx == i))[0]
            if sel.size == 0 or sub.shape[0] == 0:
                continue
            rows = sub[lidx[sel]]                         # (k, W) host gather
            nbytes += rows.nbytes
            padded = 1 << max(int(np.ceil(np.log2(sel.size))), 3)
            pos = np.full((padded,), flat.size, np.int32)  # OOB pads: dropped
            pos[:sel.size] = sel
            rows_p = np.zeros((padded, rows.shape[1]), rows.dtype)
            rows_p[:sel.size] = rows
            parts.append((i, pos,
                          jax.device_put(jnp.asarray(rows_p), self.device)))
        self._counters["prefetches"] += 1
        self._counters["hot_lookups"] += int(valid_flat.sum() - cold.sum())
        self._counters["cold_lookups"] += int(cold.sum())
        self._counters["bytes_moved"] += int(nbytes)
        return ColdPrefetch(n=int(flat.size), parts=tuple(parts),
                            bytes_moved=int(nbytes))

    def cold_part(self, fill: ColdPrefetch) -> jnp.ndarray:
        """Dequantize an in-flight cold fill into a dense ``(n, d)`` fp32
        array (zeros at hot positions) — bit-exact against ``packed_lookup``
        (asserted in tests/test_cache.py).

        The integer work (unpack + scatter, jitted — fusion cannot perturb
        integer ops; the pow-2 padding of ``prefetch_cold`` keeps the shape
        cache tiny) lands the codes in a dense grid; the float dequant then
        runs as whole-array *eager* ops, because compiling the dequant lets
        LLVM contract its mul+add into a single-rounding FMA that differs
        from the reference by 1 ulp."""
        bits, d = self.meta["bits"], self.meta["d"]
        codes_grid = jnp.zeros((fill.n, d), jnp.int32)
        wgrid = jnp.full((fill.n,), -1, jnp.int32)
        for i, pos, words in fill.parts:
            codes_grid, wgrid = _scatter_codes(bits[i], d, codes_grid, wgrid,
                                               jnp.asarray(pos), words, i)
        alpha_vec = jnp.take(self.hot["alpha"], jnp.maximum(wgrid, 0), axis=0)
        deq = dequantize_codes(codes_grid, alpha_vec[:, None],
                               self.hot["beta"])
        return jnp.where((wgrid >= 0)[:, None], deq, 0.0)

    # -- full lookup --------------------------------------------------------

    def lookup(self, ids, fill: ColdPrefetch | None = None) -> jnp.ndarray:
        """ids: any int shape -> (*ids.shape, d) fp32 — bit-exact against
        ``packed_lookup`` on the monolithic table. Pass a ``fill`` from an
        earlier ``prefetch_cold(ids)`` to consume an overlapped transfer;
        otherwise the cold fetch happens synchronously here."""
        ids = jnp.asarray(ids)
        if fill is None:
            fill = self.prefetch_cold(np.asarray(ids))
        flat = ids.reshape(-1)
        hot = tiered_hot_lookup(self.hot, self.meta["bits"], self.meta["d"],
                                flat)
        is_hot = jnp.take(self.hot["is_hot"], flat, axis=0)
        out = jnp.where(is_hot[:, None], hot, self.cold_part(fill))
        return out.reshape(*ids.shape, self.meta["d"])

    def storage(self) -> dict:
        """Static per-tier packed bytes (pad-free)."""
        return dict(self._storage)


@jax.jit
def _scatter_rows(sub, slots, rows):
    """Land promoted/written packed rows in a hot subtable. ``slots`` is
    pow-2 padded with out-of-bounds indices (dropped by scatter), so the
    jit shape cache stays tiny and the subtable shape never changes."""
    return sub.at[slots].set(rows)


@jax.jit
def _scatter_vec(vec, idx, vals):
    """Patch a routing vector (``is_hot``/``tier_local``) at the moved
    features only — same pow-2 OOB-padding contract as ``_scatter_rows``."""
    return vec.at[idx].set(vals)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _scatter_codes(b: int, d: int, codes_grid, wgrid, pos, words, width_i):
    """Unpack one width's cold rows and scatter the integer codes (and the
    width id) into the dense grids. Out-of-bounds positions (the pow-2
    padding of ``prefetch_cold``) are dropped by jax scatter semantics."""
    codes = packing.unpack_codes(words, b, d)
    return (codes_grid.at[pos].set(codes),
            wgrid.at[pos].set(jnp.int32(width_i)))


def tiered_hot_lookup(hot, bits, d: int, ids: jnp.ndarray) -> jnp.ndarray:
    """Device-local gather from a hot tier: ids (any int shape) ->
    (*ids.shape, d) fp32, **zeros at cold positions** — the packed lookup
    of ``core.inference`` over the hot subtables, with ``tier_local`` as the
    row index and the hot bit as its keep mask. Pure jnp + static shapes:
    safe to close over in a jitted serve cell, shards under
    ``tiered_hot_pspecs``.
    """
    flat = ids.reshape(-1)
    out = _lookup_rows(hot["subtables"], hot["alpha"], hot["beta"], bits, d,
                       jnp.take(hot["width_idx"], flat, axis=0),
                       jnp.take(hot["tier_local"], flat, axis=0),
                       keep=jnp.take(hot["is_hot"], flat, axis=0))
    return out.reshape(*ids.shape, d)
