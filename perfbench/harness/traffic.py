"""The benchmark's one traffic generator, driven by a mix's parameter file.

Two kinds of batch, both made from ``--seed`` alone:

* ``preextracted``: feature batches in the plan's output layout (one id
  vector per sparse field, the dense block, the label, the interest bag),
  ids drawn per field from a bounded Zipf over that field's table;
* ``raw_log``: the four raw ads-log views one shard holds (impressions,
  user profiles, ad inventory, basic features). Adapted from the program's
  synthetic log generator with two changes: the populations of users, ads,
  advertisers and campaigns are fixed by the mix, not tied to the shard
  size, and users and ads are drawn by a bounded Zipf. A user's or an ad's
  attributes are a hash of its id, so they agree across shards.

A bounded Zipf(s) rank is drawn from the continuous power law on
``[1, n + 1)`` by inverting its CDF, and ranks are spread over the id
space by ``id = (rank * 2654435761 + salt) mod n``, a bijection, so the
popular ids do not all sit at the start of a table.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

_NULL_INT = np.iinfo(np.int64).min
_SPREAD = 2654435761  # prime: coprime with every population size used
WORDS = ("cheap flights hotel deals shoes running phone case laptop gaming "
         "credit card insurance auto home loan pizza delivery coffee near me "
         "best price").split()


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def zipf_ids(rng: np.random.Generator, size, n: int, s: float,
             salt: int = 0) -> np.ndarray:
    """``size`` ids in ``[0, n)`` with bounded Zipf(``s``) popularity."""
    if n <= 1:
        return np.zeros(size, np.int64)
    u = rng.random(size)
    a = 1.0 - s
    rank = np.floor(((float(n + 1) ** a - 1.0) * u + 1.0) ** (1.0 / a)) - 1.0
    rank = np.clip(rank, 0, n - 1).astype(np.int64)
    return (rank * _SPREAD + int(salt)) % n


def mix64(x: np.ndarray, salt: int) -> np.ndarray:
    """splitmix64 finalizer of ``x`` (any int array) under ``salt``."""
    z = (np.asarray(x).astype(np.uint64)
         + np.uint64((0x9E3779B97F4A7C15 * (salt + 1)) % 2**64))
    with np.errstate(over="ignore"):
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def _hash_unit(x: np.ndarray, salt: int) -> np.ndarray:
    """A uniform in (0, 1) per id, fixed by the id and ``salt``."""
    return ((mix64(x, salt) >> np.uint64(11)).astype(np.float64) + 0.5) / 2.0**53


def _hash_int(x: np.ndarray, salt: int, n: int) -> np.ndarray:
    return (mix64(x, salt) % np.uint64(n)).astype(np.int64)


def _null_if(x: np.ndarray, unit: np.ndarray, rate: float, null) -> np.ndarray:
    return np.where(unit < rate, null, x)


# ---------------------------------------------------------- preextracted
def preextracted_batch(mix: Dict, vocab_sizes: Sequence[int], seed: int,
                       index: int) -> Dict[str, np.ndarray]:
    """Batch ``index`` of the pool, in the plan's split output layout."""
    rng = rng_for(seed, 1, index)
    rows, s = mix["rows_per_step"], mix["zipf_s"]
    out = {"batch_label": (rng.random(rows) < mix["label_rate"]).astype(np.float32),
           "batch_dense": np.log1p(rng.exponential(
               1.0, (rows, mix["n_dense"]))).astype(np.float32)}
    for f, v in enumerate(vocab_sizes):
        out[f"batch_field_{f:02d}"] = zipf_ids(rng, rows, int(v), s,
                                               salt=f).astype(np.int32)
    bag = mix["bag_len"]
    out["batch_seq_ids"] = rng.integers(0, 10_000, (rows, bag)).astype(np.int32)
    out["batch_seq_mask"] = np.ones((rows, bag), np.float32)
    return out


# --------------------------------------------------------------- raw log
def raw_views(mix: Dict, seed: int, index: int) -> Dict[str, Dict[str, np.ndarray]]:
    """Shard ``index``'s raw views. Ragged columns are given as
    ``<name>_values`` and ``<name>_lengths``; text columns as object arrays."""
    rng = rng_for(seed, 2, index)
    n, s, pop = mix["rows_per_step"], mix["zipf_s"], mix["populations"]
    null = mix["null_rate"]
    users = zipf_ids(rng, n, pop["users"], s, salt=1)
    ads = zipf_ids(rng, n, pop["ads"], s, salt=2)
    slot, device = rng.integers(0, 16, n), rng.integers(0, 4, n)
    geo = rng.integers(0, 512, n)
    has_ctx = rng.random(n) >= null
    ctx = np.array([f'{{"slot": {a}, "device": {b}, "geo": {c}}}' if h else ""
                    for a, b, c, h in zip(slot, device, geo, has_ctx)], dtype=object)
    impressions = {
        "instance_id": index * n + np.arange(n, dtype=np.int64),
        "user_id": users, "ad_id": ads,
        "label": (rng.random(n) < mix["label_rate"]).astype(np.int64),
        "hour": _null_if(rng.integers(0, 24, n).astype(np.int64),
                         rng.random(n), null, _NULL_INT),
        "dwell_time": _null_if(rng.exponential(3.0, n), rng.random(n), null,
                               np.nan).astype(np.float32),
        "context_json": ctx,
    }
    u = np.unique(users)
    lengths = _hash_int(u, 13, 8).astype(np.int32)
    owner = np.repeat(u, lengths)
    pos = np.arange(lengths.sum()) - np.repeat(np.cumsum(lengths) - lengths, lengths)
    user_profile = {
        "user_id": u,
        "age_bucket": _null_if(_hash_int(u, 11, 10), _hash_unit(u, 21), null, _NULL_INT),
        "gender": _null_if(_hash_int(u, 12, 3), _hash_unit(u, 22), null, _NULL_INT),
        "interests_values": _hash_int(owner * 64 + pos, 14, 10_000),
        "interests_lengths": lengths,
        "query_text": _text(u, 15),
    }
    a = np.unique(ads)
    bid = -0.5 * (np.log(_hash_unit(a, 31)) + np.log(_hash_unit(a, 32)))
    ad_inventory = {
        "ad_id": a,
        "advertiser_id": _hash_int(a, 33, pop["advertisers"]),
        "campaign_id": _null_if(_hash_int(a, 34, pop["campaigns"]),
                                _hash_unit(a, 35), null, _NULL_INT),
        "bid_price": _null_if(bid, _hash_unit(a, 36), null, np.nan).astype(np.float32),
        "title_text": _text(a, 37),
    }
    basic = {
        "instance_id": impressions["instance_id"].copy(),
        "ctr_7d": rng.beta(1, 20, n).astype(np.float32),
        "user_click_cnt": rng.poisson(5, n).astype(np.float32),
        "ad_show_cnt": rng.poisson(50, n).astype(np.float32),
    }
    return {"impressions": impressions, "user_profile": user_profile,
            "ad_inventory": ad_inventory, "basic_features": basic}


_PHRASES = None


def _text(ids: np.ndarray, salt: int) -> np.ndarray:
    """A short phrase per id from a fixed list of 256."""
    global _PHRASES
    if _PHRASES is None:
        r = np.random.default_rng(7)
        _PHRASES = np.array([" ".join(r.choice(WORDS, size=int(r.integers(1, 8))))
                             for _ in range(256)], dtype=object)
    return _PHRASES[_hash_int(ids, salt, 256)]


def unique_share(mix: Dict, vocab_sizes: Sequence[int], seed: int) -> float:
    """Unique packed ids over ids referenced, for the pool's first batch
    of a ``preextracted`` mix (the working set the dedup'd step gathers)."""
    b = preextracted_batch(mix, vocab_sizes, seed, 0)
    offs = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]])
    ids = np.stack([b[f"batch_field_{f:02d}"].astype(np.int64) + offs[f]
                    for f in range(len(vocab_sizes))], axis=1)
    return np.unique(ids).size / ids.size
