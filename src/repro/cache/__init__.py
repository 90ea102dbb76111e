"""Tiered embedding cache + async prefetch (ROADMAP scaling item).

Three layers:

  ``tiers``    — ``TieredTableStore``: splits an MPE packed table by feature
                 frequency into a device-resident hot tier (row-shards like
                 the monolithic table; see ``dist.sharding.tiered_hot_pspecs``)
                 and an inclusive host mirror whose rows move as packed words
                 on demand. Bit-exact against ``core.inference.packed_lookup``
                 at every hot fraction; per-tier hit/miss/byte counters;
                 incremental ``apply_moves`` promotions/demotions and
                 training-update ``writeback`` — both shape-preserving, so
                 compiled tiered cells never recompile.
  ``policy``   — ``DecayAdmissionPolicy``: exponential-decay admission
                 scores over the live lookup stream (attach with
                 ``TieredTableStore.attach_policy``) planning bounded
                 ``TierPlan`` promotion batches; ``StaticTierPolicy`` is the
                 no-op baseline.
  ``prefetch`` — ``PrefetchPipeline``: double-buffers the next batch's
                 host→device staging (and optionally its cold-row fills)
                 against the current step's compute. Same bytes, one step
                 earlier: losses are step-identical to the synchronous loop.

Consumers: ``train.loop.Trainer(run(..., prefetch=True))``,
``serve.Engine.register_tiered_model``/``score_tiered``/
``attach_tier_policy``, and ``benchmarks/prefetch_bench.py``
(→ ``BENCH_prefetch.json``).
"""
from repro.cache.policy import (DecayAdmissionPolicy, StaticTierPolicy,
                                TierPlan)
from repro.cache.prefetch import PrefetchPipeline
from repro.cache.tiers import (ColdPrefetch, TieredTableStore,
                               tiered_hot_lookup)

__all__ = [
    "TieredTableStore", "ColdPrefetch", "tiered_hot_lookup",
    "PrefetchPipeline", "DecayAdmissionPolicy",
    "StaticTierPolicy", "TierPlan",
]
