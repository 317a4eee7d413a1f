"""Plain reference of the ``dlrm`` feature spec, row by row in numpy.

What the spec states (13 dense features, 26 sparse fields, one 16-long
interest bag, the click label), computed from the raw views the traffic
generator makes. It imports nothing of the program under test.

* clean: ``context_json`` holds ``slot``, ``device`` and ``geo``; a missing
  or unparsable value, or a null sentinel, becomes 0 (0.0 for floats);
* join: user profile on ``user_id`` (columns prefixed ``u_``), ad inventory
  on ``ad_id`` (prefixed ``a_``); a key with no match gives 0 / 0.0 / an
  empty list; with duplicate keys the last row wins;
* hash: MurmurHash3's 32-bit finalizer; a cross of ``a`` and ``b`` is
  ``fmix(a * 0x9E3779B9 + fmix(b))`` on the low 32 bits; every field id is
  taken modulo ``field_size`` and field ``i`` is shifted by
  ``i * field_size``;
* dense: ``log1p(max(x, 0))``, ``x / denom``, bucket index
  ``#{boundaries <= x}``, then the three basic features merged on
  ``instance_id`` (0.0 where the instance has none).
"""

from __future__ import annotations

import json
from typing import Dict

import numpy as np

NULL_INT = np.iinfo(np.int64).min
FIELD_SIZE = 1 << 20
BAG_LEN = 16
M32 = 0xFFFFFFFF

CROSSES = (("user_id", "ad_id"), ("user_id", "a_advertiser_id"),
           ("user_id", "a_campaign_id"), ("user_id", "slot"),
           ("user_id", "geo"), ("user_id", "device"), ("user_id", "hour"),
           ("ad_id", "slot"), ("ad_id", "geo"), ("ad_id", "device"),
           ("ad_id", "hour"), ("a_advertiser_id", "slot"),
           ("a_advertiser_id", "geo"), ("a_campaign_id", "slot"),
           ("slot", "geo"), ("geo", "device"))
HASHES = (("user_id", True), ("ad_id", True), ("a_advertiser_id", False),
          ("a_campaign_id", False), ("slot", False), ("geo", False),
          ("device", False), ("hour", False), ("u_age_bucket", False),
          ("u_gender", False))


def fmix(x: int) -> int:
    x &= M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    x ^= x >> 16
    return x


def _int(v) -> int:
    v = int(v)
    return 0 if v == NULL_INT else v


def _float(v) -> float:
    v = float(v)
    return 0.0 if v != v else v


def _bucket(x, bounds) -> int:
    """Number of boundaries at or below ``x``, compared in float32."""
    return sum(np.float32(b) <= np.float32(x) for b in bounds)


def extract(views: Dict[str, Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Raw views of one batch -> ``sparse`` (B, 26) int32 ids in the packed
    field space, ``dense`` (B, 13) float32, ``bag`` (B, 16) int32 with its
    ``bag_mask``, and ``label`` (B,) float32."""
    imp, usr = views["impressions"], views["user_profile"]
    ads, basic = views["ad_inventory"], views["basic_features"]
    users = {int(k): i for i, k in enumerate(usr["user_id"])}
    inv = {int(k): i for i, k in enumerate(ads["ad_id"])}
    extra = {int(k): i for i, k in enumerate(basic["instance_id"])}
    offs = np.concatenate([[0], np.cumsum(usr["interests_lengths"])])
    n = len(imp["instance_id"])
    sparse = np.zeros((n, 26), np.int64)
    dense = np.zeros((n, 13), np.float64)
    bag = np.zeros((n, BAG_LEN), np.int64)
    mask = np.zeros((n, BAG_LEN), np.float32)
    for r in range(n):
        try:
            ctx = json.loads(imp["context_json"][r]) if imp["context_json"][r] else {}
        except (ValueError, TypeError):
            ctx = {}
        row = {"user_id": _int(imp["user_id"][r]), "ad_id": _int(imp["ad_id"][r]),
               "hour": _int(imp["hour"][r]),
               "slot": int(ctx.get("slot") or 0), "device": int(ctx.get("device") or 0),
               "geo": int(ctx.get("geo") or 0)}
        dwell = _float(imp["dwell_time"][r])
        u = users.get(row["user_id"])
        row["u_age_bucket"] = _int(usr["age_bucket"][u]) if u is not None else 0
        row["u_gender"] = _int(usr["gender"][u]) if u is not None else 0
        a = inv.get(row["ad_id"])
        row["a_advertiser_id"] = _int(ads["advertiser_id"][a]) if a is not None else 0
        row["a_campaign_id"] = _int(ads["campaign_id"][a]) if a is not None else 0
        bid = _float(ads["bid_price"][a]) if a is not None else 0.0
        ids = [fmix(((row[x] & M32) * 0x9E3779B9 + fmix(row[y])) & M32) % FIELD_SIZE
               for x, y in CROSSES]
        ids += [(fmix(row[c]) if mix else row[c]) % FIELD_SIZE for c, mix in HASHES]
        sparse[r] = [v + i * FIELD_SIZE for i, v in enumerate(ids)]
        e = extra.get(int(imp["instance_id"][r]))
        merged = ([float(basic[c][e]) for c in ("ctr_7d", "user_click_cnt", "ad_show_cnt")]
                  if e is not None else [0.0, 0.0, 0.0])
        dense[r] = [np.log1p(max(dwell, 0.0)), np.log1p(max(bid, 0.0)),
                    row["hour"] / 24.0, row["u_age_bucket"] / 10.0,
                    row["u_gender"] / 3.0, row["slot"] / 16.0, row["device"] / 4.0,
                    _bucket(dwell, (0.5, 1, 2, 4, 8, 16)),
                    _bucket(bid, (0.1, 0.3, 1, 3)),
                    _bucket(row["hour"], (6, 12, 18))] + merged
        if u is not None:
            vals = usr["interests_values"][offs[u]:offs[u + 1]][:BAG_LEN]
            bag[r, :len(vals)] = [0 if v == NULL_INT else v for v in vals]
            mask[r, :len(vals)] = 1.0
    return {"sparse": sparse.astype(np.int32), "dense": dense.astype(np.float32),
            "bag": bag.astype(np.int32), "bag_mask": mask,
            "label": np.asarray(imp["label"], np.float32)}
