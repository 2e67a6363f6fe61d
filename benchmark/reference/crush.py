"""Plain reference of CRUSH placement for an erasure-coded pool.

What Ceph computes for ``ceph pg map`` on every PG of a pool, written
out from the published algorithm (src/crush/mapper.c, hash.c,
crush_ln_table.h; src/osd/osd_types.cc and OSDMap.cc), vectorized over
the PGs with numpy:

- pps = rjenkins hash of (ceph_stable_mod(ps, pgp_num), pool id);
- crush_do_rule for rules of the steps set_choose_tries,
  set_chooseleaf_tries, take, choose_indep / chooseleaf_indep, emit,
  over straw2 buckets, with reweights applied by is_out;
- the up set keeps positional holes (an EC pool never shifts shards),
  and the primary is its first member.

The map is given as plain data (:func:`plain_map` of a ``ceph osd
crush dump``-like dict): bucket ids, types, items and 16.16 weights.
"""

from __future__ import annotations

from decimal import Decimal, getcontext

import numpy as np

NONE = 0x7FFFFFFF
UNDEF = -0x7FFFFFFF
S64_MIN = np.iinfo(np.int64).min
SEED = np.uint32(1315423911)

OP_TAKE = 1
OP_CHOOSE_INDEP = 3
OP_EMIT = 4
OP_CHOOSELEAF_INDEP = 7
OP_SET_CHOOSE_TRIES = 8
OP_SET_CHOOSELEAF_TRIES = 9


# -- rjenkins1 ----------------------------------------------------------

def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(13))  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << np.uint32(8))  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(13))  # noqa: E702
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(12))  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << np.uint32(16))  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(5))  # noqa: E702
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(3))  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << np.uint32(10))  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(15))  # noqa: E702
    return a, b, c


def u32(v) -> np.ndarray:
    return (np.asarray(v, np.int64) & 0xFFFFFFFF).astype(np.uint32)


def hash2(a, b) -> np.ndarray:
    a, b = np.broadcast_arrays(u32(a), u32(b))
    h = SEED ^ a ^ b
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash3(a, b, c) -> np.ndarray:
    a, b, c = np.broadcast_arrays(u32(a), u32(b), u32(c))
    h = SEED ^ a ^ b ^ c
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


# -- crush_ln: 2^44 * log2(u + 1), 16.48 fixed point ---------------------

def _ln_tables():
    getcontext().prec = 50
    ln2 = Decimal(2).ln()

    def log2_48(v: Decimal) -> int:
        return int((Decimal(1 << 48) * v.ln() / ln2)
                   .to_integral_value(rounding="ROUND_HALF_UP"))

    rh_lh = []
    for index1 in range(256, 513, 2):
        rh_lh += [-(-(1 << 56) // index1), log2_48(Decimal(index1) / 256)]
    ll = [log2_48(1 + Decimal(i) / (1 << 15)) for i in range(256)]
    return np.array(rh_lh, np.uint64), np.array(ll, np.uint64)


RH_LH, LL = _ln_tables()


def crush_ln(u) -> np.ndarray:
    x = np.asarray(u, np.int64) + 1
    small = (x & 0x18000) == 0
    bits = np.where(small, 15 - np.floor(np.log2(np.maximum(x, 1)))
                    .astype(np.int64), 0)
    x = x << bits
    iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh = RH_LH[index1 - 256]
    lh = RH_LH[index1 + 1 - 256]
    xl64 = (x.astype(np.uint64) * rh) >> np.uint64(48)
    index2 = (xl64 & np.uint64(0xFF)).astype(np.int64)
    lh = (lh + LL[index2]) >> np.uint64(4)
    return (iexpon << 44) + lh.astype(np.int64)


# -- the map --------------------------------------------------------------

class PlainMap:
    """Buckets as padded tables indexed by -1-id."""

    def __init__(self, buckets: dict, max_devices: int, rules: dict,
                 choose_total_tries: int) -> None:
        self.max_devices = max_devices
        self.rules = rules
        self.choose_total_tries = choose_total_tries
        nb = max(-1 - bid for bid in buckets) + 1
        width = max(len(b["items"]) for b in buckets.values())
        self.items = np.zeros((nb, width), np.int64)
        self.weights = np.zeros((nb, width), np.int64)
        self.type = np.full(nb, -1, np.int64)
        for bid, b in buckets.items():
            if b["alg"] != "straw2":
                raise NotImplementedError(f"bucket alg {b['alg']}")
            i = -1 - bid
            n = len(b["items"])
            self.items[i, :n] = b["items"]
            self.weights[i, :n] = b["weights"]
            self.type[i] = b["type"]

    def item_type(self, item: np.ndarray) -> np.ndarray:
        return np.where(item < 0, self.type[np.clip(-1 - item, 0, None)], 0)


def plain_map(dump: dict) -> PlainMap:
    return PlainMap({int(k): v for k, v in dump["buckets"].items()},
                    dump["max_devices"],
                    {int(k): v for k, v in dump["rules"].items()},
                    dump["choose_total_tries"])


def straw2(m: PlainMap, bucket: np.ndarray, x: np.ndarray,
           r: np.ndarray) -> np.ndarray:
    bi = -1 - bucket
    items = m.items[bi]
    w = m.weights[bi]
    u = hash3(x[:, None], items, r[:, None]).astype(np.int64) & 0xFFFF
    ln = crush_ln(u) - (1 << 48)
    draw = np.where(w > 0, -((-ln) // np.maximum(w, 1)), S64_MIN)
    return items[np.arange(len(bi)), np.argmax(draw, axis=1)]


def is_out(weight: np.ndarray, item: np.ndarray, x: np.ndarray) -> np.ndarray:
    w = np.where(item < len(weight),
                 weight[np.clip(item, 0, len(weight) - 1)], 0)
    draw = hash2(x, item).astype(np.int64) & 0xFFFF
    return (item >= len(weight)) | ((w < 0x10000) & ((w == 0) | (draw >= w)))


def choose_indep(m: PlainMap, weight, take: np.ndarray, x: np.ndarray,
                 numrep: int, target: int, tries: int, leaf_tries: int,
                 to_leaf: bool) -> np.ndarray:
    n = len(x)
    out = np.full((n, numrep), UNDEF, np.int64)
    leaf = np.full((n, numrep), UNDEF, np.int64)
    left = np.full(n, numrep, np.int64)
    for ftotal in range(tries):
        if not (left > 0).any():
            break
        for rep in range(numrep):
            lanes = np.nonzero((left > 0) & (out[:, rep] == UNDEF))[0]
            if not len(lanes):
                continue
            xs = x[lanes]
            r = np.full(len(lanes), rep + numrep * ftotal, np.int64)
            item = straw2(m, take[lanes], xs, r)
            while True:
                t = m.item_type(item)
                deeper = (t != target) & (item < 0)
                if not deeper.any():
                    break
                item[deeper] = straw2(m, item[deeper], xs[deeper],
                                      r[deeper])
            t = m.item_type(item)
            none = (item >= m.max_devices) | ((t != target) & (item >= 0))
            out[lanes[none], rep] = NONE
            leaf[lanes[none], rep] = NONE
            left[lanes[none]] -= 1
            ok = ~none & ~(out[lanes] == item[:, None]).any(axis=1)
            if to_leaf:
                got = np.full(len(lanes), NONE, np.int64)
                got[ok & (item >= 0)] = item[ok & (item >= 0)]
                todo = ok & (item < 0)
                for f2 in range(leaf_tries):
                    idx = np.nonzero(todo)[0]
                    if not len(idx):
                        break
                    r2 = rep + r[idx] + numrep * f2
                    dev = straw2(m, item[idx], xs[idx], r2)
                    big = dev >= m.max_devices
                    fine = ~big & ~is_out(weight, dev, xs[idx])
                    got[idx[fine]] = dev[fine]
                    todo[idx[big | fine]] = False
                ok &= got != NONE
            if target == 0:
                ok &= ~is_out(weight, item, xs)
            sel = lanes[ok]
            out[sel, rep] = item[ok]
            if to_leaf:
                leaf[sel, rep] = got[ok]
            left[sel] -= 1
    res = leaf if to_leaf else out
    return np.where(res == UNDEF, NONE, res)


def do_rule(m: PlainMap, ruleno: int, x: np.ndarray, result_max: int,
            weight) -> np.ndarray:
    weight = np.asarray(weight, np.int64)
    x = np.asarray(x, np.int64)
    tries = m.choose_total_tries + 1
    leaf_tries = 0
    result = None
    take = None
    for op, a1, a2 in m.rules[ruleno]:
        if op == OP_SET_CHOOSE_TRIES:
            tries = a1 if a1 > 0 else tries
        elif op == OP_SET_CHOOSELEAF_TRIES:
            leaf_tries = a1 if a1 > 0 else leaf_tries
        elif op == OP_TAKE:
            take = np.full(len(x), a1, np.int64)
        elif op in (OP_CHOOSE_INDEP, OP_CHOOSELEAF_INDEP):
            numrep = a1 if a1 > 0 else a1 + result_max
            result = choose_indep(m, weight, take, x, numrep, a2, tries,
                                  leaf_tries or 1,
                                  op == OP_CHOOSELEAF_INDEP)
        elif op == OP_EMIT:
            pass
        else:
            raise NotImplementedError(f"rule step op {op}")
    return result[:, :result_max]


def pg_mask(n: int) -> int:
    return (1 << (n - 1).bit_length()) - 1 if n > 1 else 0


def pps(pool_id: int, pg_num: int, pgp_num: int) -> np.ndarray:
    ps = np.arange(pg_num, dtype=np.int64)
    mask = pg_mask(pgp_num)
    folded = np.where((ps & mask) < pgp_num, ps & mask, ps & (mask >> 1))
    return hash2(folded, pool_id).astype(np.int64)


def pg_to_up(m: PlainMap, pool: dict, weight, up_osds=None):
    """(up (pg_num, size), up_primary (pg_num,)) of an erasure pool."""
    raw = do_rule(m, pool["crush_rule"],
                  pps(pool["pool_id"], pool["pg_num"], pool["pgp_num"]),
                  pool["size"], weight)
    alive = (np.ones(len(weight), bool) if up_osds is None
             else np.asarray(up_osds, bool))
    valid = (raw != NONE) & (raw >= 0) & (raw < len(alive))
    valid &= alive[np.clip(raw, 0, len(alive) - 1)]
    up = np.where(valid, raw, NONE)
    first = np.argmax(valid, axis=1)
    prim = np.where(valid.any(axis=1), up[np.arange(len(up)), first], -1)
    return up, prim
