"""The traffic generators are deterministic by seed."""
from __future__ import annotations

import harness_util
import numpy as np

from yardstick import traffic

VOCABS = [5000, 300, 17]
BIG = 2**31 + 12345


def test_id_pool_same_seed_same_ids():
    a = traffic.id_pool(VOCABS, 1.1, 4096, BIG)
    b = traffic.id_pool(VOCABS, 1.1, 4096, BIG)
    c = traffic.id_pool(VOCABS, 1.1, 4096, BIG + 1)
    assert a.dtype == np.int32 and a.shape == (4096, 3)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    for f, v in enumerate(VOCABS):
        assert 0 <= a[:, f].min() and a[:, f].max() < v


def test_zipf_head_frequency():
    # P(rank 0) = 1 / H(V, s); a large sample lands within a few sigma
    v, s, n = 5000, 1.1, 200_000
    ids = traffic.zipf_ids(v, s, n, traffic.rng(3))
    p0 = 1.0 / np.sum(np.arange(1, v + 1, dtype=np.float64) ** -s)
    got = np.mean(ids == 0)
    assert abs(got - p0) < 5 * np.sqrt(p0 * (1 - p0) / n)


def test_config_vocabularies_sum_to_table_2():
    """The categorical fields hold Criteo's published cardinalities, whose
    sum is the paper's Table 2 count; the numeric fields are 1,024 buckets
    each."""
    from yardstick import spec
    cfg = spec.config("dlrm-criteo")
    v = dict(zip(cfg["field_names"], cfg["field_vocabs"]))
    assert len(v) == 39
    assert sum(v[f"C{i}"] for i in range(1, 27)) == 33_762_577
    assert all(v[f"I{i}"] == 1024 for i in range(1, 14))


def test_training_ring_same_seed_same_batches():
    from yardstick import spec
    drv = spec.load_module("drivers", "train_steps")
    cfg = harness_util.reduced_config("dlrm-criteo")
    mix = harness_util.reduced_mix("mpe-search")
    a, b = drv.ring(cfg, mix, BIG), drv.ring(cfg, mix, BIG)
    c = drv.ring(cfg, mix, BIG + 1)
    assert len(a) == mix["ring_batches"]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["ids"], y["ids"])
        np.testing.assert_array_equal(x["label"], y["label"])
    assert any((x["ids"] != z["ids"]).any() for x, z in zip(a, c))
    # the checked steps see batches that differ from each other
    assert not np.array_equal(a[0]["ids"], a[1]["ids"])
