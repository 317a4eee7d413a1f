import numpy as np

from perfbench.harness import traffic
from perfbench.tests import tiny


def _mix(name, **kw):
    return tiny.cell("dlrm", name, **kw)["traffic"]


def test_preextracted_batch_repeats_for_a_seed_and_differs_between_seeds():
    mix, vocab = _mix("preextracted"), tiny.config("dlrm")["vocab_sizes"]
    a = traffic.preextracted_batch(mix, vocab, 2**31 + 17, 3)
    b = traffic.preextracted_batch(mix, vocab, 2**31 + 17, 3)
    c = traffic.preextracted_batch(mix, vocab, 2**31 + 18, 3)
    d = traffic.preextracted_batch(mix, vocab, 2**31 + 17, 4)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    assert any(not np.array_equal(a[k], d[k]) for k in a)


def test_preextracted_ids_stay_inside_each_table_slice():
    mix = dict(_mix("preextracted"), rows_per_step=4096)
    vocab = [1, 2, 97, 4_985_551, 23]
    b = traffic.preextracted_batch(mix, vocab, 7, 0)
    for f, v in enumerate(vocab):
        ids = b[f"batch_field_{f:02d}"]
        assert ids.min() >= 0 and ids.max() < v
    # Zipf: the most popular id of a large table is far above uniform
    big = np.bincount(b["batch_field_03"]).max()
    assert big > 50


def test_zipf_ids_cover_the_population_and_are_skewed():
    rng = np.random.default_rng(0)
    ids = traffic.zipf_ids(rng, 200_000, 1000, 1.05)
    assert ids.min() >= 0 and ids.max() < 1000
    counts = np.sort(np.bincount(ids, minlength=1000))[::-1]
    assert counts[0] > 20 * np.median(counts)


def test_raw_views_repeat_and_keep_attributes_across_shards():
    mix = tiny.cell("dcnv2", "stream", rows=256)["traffic"]
    a = traffic.raw_views(mix, 5, 1)
    b = traffic.raw_views(mix, 5, 1)
    c = traffic.raw_views(mix, 6, 1)
    for view in a:
        for col in a[view]:
            np.testing.assert_array_equal(a[view][col], b[view][col])
    assert not np.array_equal(a["impressions"]["user_id"], c["impressions"]["user_id"])
    imp = a["impressions"]
    assert imp["user_id"].max() < mix["populations"]["users"]
    assert imp["ad_id"].max() < mix["populations"]["ads"]
    # a user seen in two shards has the same profile in both
    d = traffic.raw_views(mix, 5, 2)
    ua, ud = a["user_profile"], d["user_profile"]
    common = np.intersect1d(ua["user_id"], ud["user_id"])
    assert common.size
    ia = np.searchsorted(ua["user_id"], common)
    id_ = np.searchsorted(ud["user_id"], common)
    np.testing.assert_array_equal(ua["age_bucket"][ia], ud["age_bucket"][id_])
    np.testing.assert_array_equal(ua["interests_lengths"][ia], ud["interests_lengths"][id_])
    # instance ids are unique across shards
    assert np.intersect1d(imp["instance_id"], d["impressions"]["instance_id"]).size == 0
