"""Bulk CRUSH evaluator — the TPU-native replacement for the serial
`crushtool --test` loop (src/crush/CrushTester.cc -> CrushTester::test,
src/crush/mapper.c -> crush_do_rule).

Design (SURVEY.md §7 step 7): placement evaluation is embarrassingly
parallel over the input x (pg id seed), so the whole map is compiled to
dense arrays and `crush_do_rule` becomes one fused jit program:

- buckets -> padded (B, S) item/weight tables; straw2 selection is a
  masked argmax over hash32_3 -> crush_ln -> draw lanes; crush_ln is a
  precomputed 64Ki-entry lookup (u is 16-bit, so the whole 16.48
  fixed-point pipeline collapses into one gather);
- hierarchy descent -> statically unrolled to the tree depth;
- retry ladders -> statically unrolled attempt *batches*: firstn
  computes all T candidate descents per replica at once (r = rep+0..T-1
  are independent) and picks the first acceptable; indep unrolls T
  rounds.  Lanes that exhaust the unrolled budget (collision storms,
  heavy reweighting — measured O(1e-5) of lanes) are re-evaluated
  exactly on the host reference mapper, so results are ALWAYS
  bit-identical to mapper.py / the C semantics, at any budget.

Scope: all five bucket algorithms fuse (alg-dispatched per bucket row;
pure-straw2 maps compile no extra branches).  Uniform buckets'
bucket_perm_choose is stateful in C but pure per (x, r, bucket), so
each lane recomputes its Fisher-Yates prefix (_uniform_choose); the
indep r-stride through uniform buckets is applied per descent level.
Jewel tunables (choose_local_* == 0).  Equivalence is pinned by
tests/test_crush_bulk.py over randomized maps, rules and reweights.

int64: crush_ln is 16.48 fixed point, so the map tables are built and
every rung is traced and dispatched with 64-bit types on
(``CompiledCrushMap`` and ``bulk_do_rule`` run under ``with_x64``).  The
switch is thread-local and scoped to those calls: the rest of the
process (the EC tiers, Pallas kernels) keeps JAX's 32-bit defaults.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .hash import crush_hash32_2, crush_hash32_3, crush_hash32_4
from .ln import crush_ln
from .mapper import crush_do_rule
from .types import (
    CRUSH_BUCKET_LIST,
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2,
    CRUSH_BUCKET_UNIFORM,
    CRUSH_ITEM_NONE,
    ChooseArg,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
    CrushMap,
)

S64_MIN = -(1 << 63)
NONE = CRUSH_ITEM_NONE

# attempts unrolled on device per replica/round; failures beyond this
# fall back to the exact host mapper (see module docstring).  Wide
# rules auto-scale it (_auto_tries).
DEFAULT_BULK_TRIES = 8

# device budget for the chooseleaf leaf-retry ladders; deeper
# SET_CHOOSELEAF_TRIES values model the first 8 attempts and flag the
# (vanishingly rare) lane whose accepted candidate exhausts them
LEAF_TRIES_CAP = 8

# lanes per device dispatch (bulk_do_rule blocks larger sweeps)
BULK_BLOCK = 1 << 18

# residue lanes walked on the host mapper instead of compiling a
# deeper rung, per backend: each rung is a new program.  The CPU
# builds one in seconds; the TPU compiler takes minutes (a 32,768-PG
# replicated sweep's rungs: 129-357 s AOT for a v5e) while the host
# mapper takes ~9 ms a lane (~37 s for 4096).  A rung this process
# has already compiled is always used.
HOST_RESIDUE_LANES = {"cpu": 512, "tpu": 4096}

# negln[u] = 2^48 - crush_ln(u): the straw2 numerator, one gather
_NEGLN = (1 << 48) - np.asarray(crush_ln(np.arange(0x10000)))


def with_x64(fn):
    """Run ``fn`` with JAX's 64-bit types on, for this thread only."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)
    return run


class CompiledCrushMap:
    """Dense-array form of a straw2 CrushMap for the fused evaluator.

    ``choose_args`` (crush.h -> crush_choose_arg; the balancer's knob)
    are baked into the tables: per-bucket hash-id overrides become an
    alternate id table, and per-position weight_set vectors become a
    (bucket, position, slot) weight tensor indexed by the result
    position (padded positions replicate each bucket's last vector,
    matching bucket_straw2_choose's min(position, size-1) clamp).
    """

    @with_x64
    def __init__(self, cmap: CrushMap,
                 choose_args: Optional[Dict[int, "ChooseArg"]] = None
                 ) -> None:
        for b in cmap.buckets.values():
            if b.alg not in (CRUSH_BUCKET_STRAW2, CRUSH_BUCKET_STRAW,
                             CRUSH_BUCKET_LIST, CRUSH_BUCKET_TREE,
                             CRUSH_BUCKET_UNIFORM):
                raise ValueError(
                    f"bucket alg {b.alg} is not fused; use engine=host")
        self.cmap = cmap
        self.choose_args = choose_args
        ids = sorted(cmap.buckets)          # negative ids
        self.n_buckets = len(ids)
        self.row_of_id = {bid: i for i, bid in enumerate(ids)}
        S = max((cmap.buckets[b].size for b in ids), default=1)
        self.max_size = S
        P = 1
        if choose_args:
            P = max([1] + [len(a.weight_set) for a in choose_args.values()
                           if a.weight_set])
        self.n_positions = P
        items = np.full((self.n_buckets, S), NONE, np.int32)
        hash_ids = np.full((self.n_buckets, S), NONE, np.int32)
        pos_weights = np.zeros((self.n_buckets, P, S), np.int64)
        types = np.zeros(self.n_buckets, np.int32)
        sizes = np.zeros(self.n_buckets, np.int32)
        algs = np.zeros(self.n_buckets, np.int32)
        bids = np.zeros(self.n_buckets, np.int32)
        straws = np.zeros((self.n_buckets, S), np.int64)
        sum_weights = np.zeros((self.n_buckets, S), np.int64)
        raw_weights = np.zeros((self.n_buckets, S), np.int64)
        NN = max((cmap.buckets[b].num_nodes for b in ids
                  if cmap.buckets[b].alg == CRUSH_BUCKET_TREE),
                 default=0)
        node_weights = np.zeros((self.n_buckets, max(NN, 1)), np.int64)
        tree_roots = np.ones(self.n_buckets, np.int32)
        tree_steps = 0
        for bid, row in self.row_of_id.items():
            b = cmap.buckets[bid]
            items[row, :b.size] = b.items
            hash_ids[row, :b.size] = b.items
            pos_weights[row, :, :b.size] = b.item_weights
            types[row] = b.type
            sizes[row] = b.size
            algs[row] = b.alg
            bids[row] = bid
            raw_weights[row, :b.size] = b.item_weights
            if b.alg == CRUSH_BUCKET_STRAW:
                straws[row, :b.size] = b.straws
            if b.alg == CRUSH_BUCKET_TREE:
                if max(b.node_weights, default=0) >= 1 << 32:
                    # crush.h node_weights are __u32; a wider weight is
                    # unrepresentable in the wire format and would wrap
                    # the device's u64 (hash * w) product
                    raise ValueError(
                        f"tree bucket {bid} node weight exceeds __u32; "
                        "not fused — use engine=host")
                node_weights[row, :b.num_nodes] = b.node_weights
                tree_roots[row] = b.num_nodes >> 1
                tree_steps = max(tree_steps,
                                 max(b.num_nodes.bit_length() - 2, 0))
            if b.alg == CRUSH_BUCKET_LIST:
                sum_weights[row, :b.size] = b.sum_weights
            arg = choose_args.get(bid) if choose_args else None
            if arg is not None:
                if arg.ids:
                    hash_ids[row, :b.size] = arg.ids[:b.size]
                if arg.weight_set:
                    ws = arg.weight_set
                    for p in range(P):
                        pos_weights[row, p, :b.size] = \
                            ws[min(p, len(ws) - 1)][:b.size]
        self.algs_present = sorted(set(int(a) for a in algs))
        # uniform: the perm unroll length is the widest uniform bucket
        # (Fisher-Yates steps are recomputed per lane; see _uniform)
        self.max_uniform_size = max(
            (cmap.buckets[b].size for b in ids
             if cmap.buckets[b].alg == CRUSH_BUCKET_UNIFORM), default=0)
        max_neg = max((-bid for bid in ids), default=0)
        i2r = np.full(max_neg + 1, 0, np.int32)
        for bid, row in self.row_of_id.items():
            i2r[-1 - bid] = row
        self.items = jnp.asarray(items)
        self.hash_ids = jnp.asarray(hash_ids)
        self.pos_weights = jnp.asarray(pos_weights)
        self.types = jnp.asarray(types)
        self.sizes = jnp.asarray(sizes)
        self.algs = jnp.asarray(algs)
        # legacy-alg tables upload only when those algorithms exist in
        # the map (pure-straw2 maps allocate none of them)
        has_straw = CRUSH_BUCKET_STRAW in self.algs_present
        has_list = CRUSH_BUCKET_LIST in self.algs_present
        has_tree = CRUSH_BUCKET_TREE in self.algs_present
        has_uniform = CRUSH_BUCKET_UNIFORM in self.algs_present
        self.straws = jnp.asarray(straws) if has_straw else None
        self.bucket_ids = jnp.asarray(bids) \
            if (has_list or has_tree or has_uniform) else None
        self.sum_weights = jnp.asarray(sum_weights) if has_list else None
        self.raw_weights = jnp.asarray(raw_weights) if has_list else None
        self.node_weights = jnp.asarray(node_weights) if has_tree else None
        self.tree_roots = jnp.asarray(tree_roots) if has_tree else None
        self.tree_steps = tree_steps
        self.id_to_row = jnp.asarray(i2r)
        self.negln = jnp.asarray(_NEGLN)
        self.max_depth = self._depth(cmap)
        self.type_level = self._type_levels(cmap)
        self._jit_cache: Dict[tuple, object] = {}

    @staticmethod
    def _type_levels(cmap: CrushMap) -> Optional[Dict[int, int]]:
        """If the hierarchy is regular (every bucket's items all sit at
        one level, consistent per bucket type), return type -> level
        (devices = 0); else None.  Regularity lets _descend unroll
        exactly level(start) - level(target) picks instead of the tree
        depth."""
        level: Dict[int, int] = {}

        def bucket_level(bid: int) -> Optional[int]:
            if bid >= 0:
                return 0
            b = cmap.buckets[bid]
            kids = {bucket_level(i) for i in b.items}
            if len(kids) != 1 or None in kids:
                return None
            return 1 + kids.pop()

        levels: Dict[int, int] = {}
        for bid, b in cmap.buckets.items():
            lv = bucket_level(bid)
            if lv is None:
                return None
            if levels.setdefault(b.type, lv) != lv:
                return None
        levels[0] = 0
        return levels

    def descend_steps(self, start_type: Optional[int],
                      target_type: int) -> int:
        """Unroll count for a descent from start_type to target_type."""
        if (self.type_level is not None and start_type is not None
                and start_type in self.type_level
                and target_type in self.type_level):
            return max(self.type_level[start_type]
                       - self.type_level[target_type], 0)
        return self.max_depth + 1

    @staticmethod
    def _depth(cmap: CrushMap) -> int:
        depth: Dict[int, int] = {}

        def d(bid: int) -> int:
            if bid >= 0:
                return 0
            if bid not in depth:
                b = cmap.buckets[bid]
                depth[bid] = 1 + max((d(i) for i in b.items), default=0)
            return depth[bid]

        return max((d(bid) for bid in cmap.buckets), default=1)

    def row(self, item):
        return self.id_to_row[-1 - item]


def _straw2(cm: CompiledCrushMap, row, x, r, pos=0):
    """bucket_straw2_choose over table rows; broadcasts over any leading
    shape of ``row``/``r``/``pos`` (x scalar per lane).

    ``pos``: result position for the choose_args weight_set lookup
    (mapper.c passes outpos; tables replicate each bucket's last vector
    past its length, so one global clamp suffices).  Hashing uses the
    per-bucket id table (choose_args ids override).

    draw = trunc((crush_ln(u) - 2^48) / w) = -(negln[u] // w); argmax
    with first-index-wins maps to argmax over (draw, -index) — jnp.argmax
    already returns the first maximal index."""
    items = cm.items[row]                      # (..., S)
    hash_ids = cm.hash_ids[row]
    pos_c = jnp.minimum(jnp.asarray(pos), cm.n_positions - 1)
    pos_c = jnp.broadcast_to(pos_c, jnp.shape(row))
    weights = cm.pos_weights[row, pos_c]       # (..., S)
    valid = jnp.arange(cm.max_size) < cm.sizes[row][..., None]
    u = crush_hash32_3(
        jnp.asarray(x, jnp.uint32),
        hash_ids.astype(jnp.uint32),
        jnp.asarray(r, jnp.uint32)[..., None]).astype(jnp.int64) & 0xFFFF
    draw = jnp.where((weights > 0) & valid,
                     -(cm.negln[u] // jnp.maximum(weights, 1)), S64_MIN)
    return jnp.take_along_axis(
        items, jnp.argmax(draw, axis=-1)[..., None], axis=-1)[..., 0]


def _straw_legacy(cm: CompiledCrushMap, row, x, r):
    """mapper.c -> bucket_straw_choose (legacy straw): draw =
    (hash32_3 & 0xffff) * straw, argmax first-wins.  choose_args do not
    apply to legacy straw (crush_bucket_choose passes them to straw2
    only)."""
    items = cm.items[row]
    valid = jnp.arange(cm.max_size) < cm.sizes[row][..., None]
    u = crush_hash32_3(
        jnp.asarray(x, jnp.uint32),
        items.astype(jnp.uint32),
        jnp.asarray(r, jnp.uint32)[..., None]).astype(jnp.int64) & 0xFFFF
    draw = jnp.where(valid, u * cm.straws[row], -1)
    return jnp.take_along_axis(
        items, jnp.argmax(draw, axis=-1)[..., None], axis=-1)[..., 0]


def _list_choose(cm: CompiledCrushMap, row, x, r):
    """mapper.c -> bucket_list_choose: scan items from the tail; the
    first i with (hash32_4(x, item, r, bucket_id) & 0xffff) *
    sum_weights[i] >> 16 < item_weight[i] wins, else items[0]."""
    items = cm.items[row]
    valid = jnp.arange(cm.max_size) < cm.sizes[row][..., None]
    h = crush_hash32_4(
        jnp.asarray(x, jnp.uint32),
        items.astype(jnp.uint32),
        jnp.asarray(r, jnp.uint32)[..., None],
        cm.bucket_ids[row].astype(jnp.uint32)[..., None]
    ).astype(jnp.int64) & 0xFFFF
    t = (h * cm.sum_weights[row]) >> 16
    cond = valid & (t < cm.raw_weights[row])
    # highest index with cond true (the C loop runs size-1 .. 0)
    rank = jnp.where(cond, jnp.arange(cm.max_size), -1)
    best = jnp.argmax(rank, axis=-1)
    found = jnp.any(cond, axis=-1)
    chosen = jnp.take_along_axis(items, best[..., None], axis=-1)[..., 0]
    return jnp.where(found, chosen, items[..., 0])


def _tree_choose(cm: CompiledCrushMap, row, x, r):
    """mapper.c -> bucket_tree_choose: walk the implicit binary tree
    from the root node (num_nodes >> 1); at node n descend left when
    (hash32_4(x, n, r, bucket_id) * node_weight(n)) >> 32 falls under
    the left child's weight.  Unrolled to the deepest tree in the map;
    terminal (odd) nodes hold their value.  left/right = n -/+ half the
    lowbit (the height-derived stride)."""
    nw = cm.node_weights[row]              # (..., NN)
    n = cm.tree_roots[row]
    bid = cm.bucket_ids[row].astype(jnp.uint32)
    for _ in range(cm.tree_steps):
        half = (n & -n) >> 1
        left = n - half
        w = jnp.take_along_axis(nw, n[..., None], axis=-1)[..., 0]
        h = crush_hash32_4(
            jnp.asarray(x, jnp.uint32), n.astype(jnp.uint32),
            jnp.asarray(r, jnp.uint32), bid).astype(jnp.uint64)
        t = (h * w.astype(jnp.uint64)) >> jnp.uint64(32)
        wl = jnp.take_along_axis(nw, left[..., None], axis=-1)[..., 0]
        nxt = jnp.where(t < wl.astype(jnp.uint64), left, n + half)
        n = jnp.where((n & 1) == 1, n, nxt)
    return jnp.take_along_axis(cm.items[row], (n >> 1)[..., None],
                               axis=-1)[..., 0]


def _uniform_choose(cm: CompiledCrushMap, row, x, r):
    """mapper.c -> bucket_perm_choose (uniform buckets), functional.

    The C keeps per-bucket permutation *state* (perm_x / perm_n / the
    r=0 magic slot), but the visible sequence is a pure function of
    (x, r, bucket): pr = r % size, then the Fisher-Yates prefix
    perm[0..pr] with swap offsets i_p = hash32_3(x, bucket_id, p) %
    (size - p).  (The r=0 shortcut stores hash%size at slot 0 and the
    cleanup swaps it with identity — exactly what step p=0 of the full
    walk produces, so statefulness never shows.)  Each lane recomputes
    the prefix; the unroll length is the widest uniform bucket in the
    map."""
    size = cm.sizes[row]                                   # (...,)
    bid = cm.bucket_ids[row].astype(jnp.uint32)
    pr = jnp.asarray(r, jnp.int64) % jnp.maximum(size, 1)  # C: unsigned r
    S = max(cm.max_uniform_size, 1)
    ar = jnp.arange(S)
    perm = jnp.broadcast_to(ar, jnp.shape(row) + (S,)).astype(jnp.int32)
    for p in range(S - 1):
        # while perm_n <= pr: step at p runs when p <= pr (and the
        # final-entry swap is skipped at p == size-1)
        i = (crush_hash32_3(jnp.asarray(x, jnp.uint32), bid,
                            jnp.uint32(p)).astype(jnp.int64)
             % jnp.maximum(size - p, 1))
        active = (p <= pr) & (p < size - 1)
        idx = (p + i)[..., None]                           # (..., 1)
        pv = perm[..., p][..., None]
        iv = jnp.take_along_axis(perm, idx, axis=-1)
        swapped = jnp.where(ar == p, iv, perm)
        swapped = jnp.where(ar == idx, pv, swapped)
        perm = jnp.where(active[..., None], swapped, perm)
    s = jnp.take_along_axis(perm, pr[..., None].astype(jnp.int32),
                            axis=-1)
    return jnp.take_along_axis(cm.items[row], s, axis=-1)[..., 0]


def _bucket_choose(cm: CompiledCrushMap, row, x, r, pos=0):
    """mapper.c -> crush_bucket_choose over the fused algorithms;
    branches compile only for algorithms present in the map (pure
    straw2 maps pay nothing extra)."""
    res = None
    if CRUSH_BUCKET_STRAW2 in cm.algs_present:
        res = _straw2(cm, row, x, r, pos)
    if CRUSH_BUCKET_STRAW in cm.algs_present:
        s = _straw_legacy(cm, row, x, r)
        res = s if res is None else jnp.where(
            cm.algs[row] == CRUSH_BUCKET_STRAW, s, res)
    if CRUSH_BUCKET_LIST in cm.algs_present:
        lc = _list_choose(cm, row, x, r)
        res = lc if res is None else jnp.where(
            cm.algs[row] == CRUSH_BUCKET_LIST, lc, res)
    if CRUSH_BUCKET_TREE in cm.algs_present:
        tc = _tree_choose(cm, row, x, r)
        res = tc if res is None else jnp.where(
            cm.algs[row] == CRUSH_BUCKET_TREE, tc, res)
    if CRUSH_BUCKET_UNIFORM in cm.algs_present:
        uc = _uniform_choose(cm, row, x, r)
        res = uc if res is None else jnp.where(
            cm.algs[row] == CRUSH_BUCKET_UNIFORM, uc, res)
    return res


def _descend(cm: CompiledCrushMap, start_item, x, r, target_type,
             steps: Optional[int] = None, pos=0,
             indep_f=None, indep_numrep: Optional[int] = None,
             return_last_r: bool = False):
    """Walk from start_item down to an item of target_type (mapper.c
    itemtype != type descent), statically unrolled ``steps`` times
    (regular hierarchies: exactly the level distance; else tree depth).
    ``start_item``/``r``/``pos`` may be vectors (attempt batches).

    indep mode (``indep_f``/``indep_numrep`` set): crush_choose_indep
    recomputes r at EVERY descent level from the CURRENT bucket —
    r = base + (numrep+1)*ftotal when it is uniform with size % numrep
    == 0, else base + numrep*ftotal — so the stride is applied here
    per level, not baked into the r grid.  ``return_last_r`` also
    returns the r used for each lane's final pick (the parent_r the C
    passes to the chooseleaf recursion)."""
    r = jnp.asarray(r)
    if steps is None:
        steps = cm.max_depth + 1
    item = jnp.broadcast_to(jnp.asarray(start_item, jnp.int32), r.shape)
    done = jnp.zeros(r.shape, bool)
    last_r = jnp.broadcast_to(r, r.shape)
    for _ in range(steps):
        is_bucket = item < 0
        row = jnp.where(is_bucket, cm.row(item), 0)
        itype = jnp.where(is_bucket, cm.types[row], 0)
        arrived = itype == target_type
        if indep_f is not None:
            stride = jnp.where(
                (cm.algs[row] == CRUSH_BUCKET_UNIFORM)
                & (cm.sizes[row] % indep_numrep == 0),
                indep_numrep + 1, indep_numrep)
            r_lvl = r + stride * indep_f
        else:
            r_lvl = r
        picked = _bucket_choose(cm, row, x, r_lvl, pos)
        picking = ~(done | arrived | ~is_bucket)
        nxt = jnp.where(picking, picked, item)
        last_r = jnp.where(picking, r_lvl, last_r)
        done = done | arrived | (~is_bucket)
        item = nxt
    is_bucket = item < 0
    row = jnp.where(is_bucket, cm.row(item), 0)
    itype = jnp.where(is_bucket, cm.types[row], 0)
    if return_last_r:
        return item, itype == target_type, last_r
    return item, itype == target_type


def _is_out(weight_vec, item, x):
    """mapper.c -> is_out (device reweight rejection); vectorized."""
    idx = jnp.clip(item, 0, weight_vec.shape[0] - 1)
    w = weight_vec[idx]
    in_range = (item >= 0) & (item < weight_vec.shape[0])
    h = crush_hash32_2(jnp.asarray(x, jnp.uint32),
                       item.astype(jnp.uint32)).astype(jnp.int64)
    keep = (w >= 0x10000) | ((w > 0) & ((h & 0xFFFF) < w))
    return ~(in_range & keep)


def _choose_firstn(cm, take, x, numrep, type_, recurse_to_leaf,
                   weight_vec, T, take_type, leaf_tries=1,
                   leaf_cap=LEAF_TRIES_CAP, leaf_fix_iters=1,
                   exact_budget=False):
    """mapper.c -> crush_choose_firstn, attempt-batched and leaf-lazy.

    The (numrep, T) domain candidate grid is one batched descent (r =
    rep + ftotal depends only on indices); the sequential part is the
    collision / first-acceptable scan per rep — identical to the C
    retry ladder under jewel tunables (no local retries).  Leaf
    recursions run ONLY for each rep's accepted candidate: C's
    recursion is numrep=1/stable with r' = sub_r + ftotal' (sub_r = r,
    vary_r=1; no uniform stride in firstn), up to ``leaf_tries``
    attempts, each rejected when out-weighted OR colliding with an
    EARLIER position's leaf (the out2[0..outpos) scan — unlike indep,
    firstn dedups leaves across positions, so leaf resolution stays
    inside the sequential rep loop).  A candidate whose ladder is
    dead for prefix-INDEPENDENT reasons is marked bad at its
    (rep, try) position and the scan re-runs — _choose_indep's
    fixpoint, restricted per the soundness note inside (collision-
    caused ladder failures depend on the provisional prefix and flag
    need_host instead of marking; marking also requires the modeled
    ladder to cover C's full leaf budget).  Returns
    (out, count, need_host).

    ``exact_budget``: an unfilled rep at the rule's own budget is C's
    own short result (the packing matches: C skips the rep without
    advancing outpos) — valid ONLY with single-position choose_args,
    because C hashes later picks with outpos (= placed count), which
    diverges from our static rep-indexed position grid once a rep
    fails."""
    rs = (jnp.arange(numrep, dtype=jnp.int64)[:, None]
          + jnp.arange(T, dtype=jnp.int64)[None, :])        # (R, T)
    # choose_args position = outpos at bucket-choose time; every lane
    # the device keeps has all reps placed (see exact_budget note), so
    # outpos == rep for both the domain pick and the leaf recursion
    pos = jnp.arange(numrep)[:, None]                       # (R, 1)
    exact_budget = exact_budget and cm.n_positions == 1
    items, okd = _descend(cm, take, x, rs, type_,
                          cm.descend_steps(take_type, type_), pos)
    if not recurse_to_leaf and type_ == 0:
        okd = okd & ~_is_out(weight_vec, items, x)

    if not recurse_to_leaf:
        out = jnp.full(numrep, NONE, jnp.int32)
        placed_n = jnp.int32(0)
        need_host = jnp.asarray(False)
        for rep in range(numrep):
            cand = items[rep]                                # (T,)
            collide = jnp.any(out[None, :] == cand[:, None], axis=1)
            ok = okd[rep] & ~collide
            first = jnp.argmax(ok)
            any_ok = jnp.any(ok)
            slot = jnp.arange(numrep) == placed_n
            out = jnp.where(slot & any_ok, cand[first], out)
            placed_n = placed_n + any_ok.astype(jnp.int32)
            if not exact_budget:
                need_host = need_host | ~any_ok
        return out, placed_n, need_host

    L = max(1, min(leaf_tries, LEAF_TRIES_CAP, leaf_cap))
    sound = L == leaf_tries
    fix = max(1, leaf_fix_iters) if sound else 1
    ls = jnp.arange(L, dtype=jnp.int64)
    leaf_steps = cm.descend_steps(type_, 0)

    def accept_pass(bad):
        out = jnp.full(numrep, NONE, jnp.int32)
        out2 = jnp.full(numrep, NONE, jnp.int32)
        placed_n = jnp.int32(0)
        fail_pure = jnp.zeros(numrep, bool)
        coll_fail = jnp.zeros(numrep, bool)
        firsts = jnp.zeros(numrep, jnp.int32)
        unfilled = jnp.zeros(numrep, bool)
        for rep in range(numrep):
            cand = items[rep]                                # (T,)
            collide = jnp.any(out[None, :] == cand[:, None], axis=1)
            ok = okd[rep] & ~collide & ~bad[rep]
            first = jnp.argmax(ok)
            any_ok = jnp.any(ok)
            sel_item = cand[first]
            sub_r = rs[rep, first]                           # vary_r=1
            start = jnp.where(any_ok, sel_item, jnp.int32(-1))
            leaves_l, lok_l = _descend(
                cm, jnp.broadcast_to(start, (L,)), x, sub_r + ls, 0,
                leaf_steps, rep)
            # leaf_ok_pure is a pure function of (rep, try) — the
            # ONLY basis for fixpoint marks (see below); lcollide
            # depends on earlier positions' provisional leaves and
            # may only influence this pass's pick, never a mark
            leaf_ok_pure = lok_l & ~_is_out(weight_vec, leaves_l, x)
            lcollide = jnp.any(out2[None, :] == leaves_l[:, None],
                               axis=1)
            leaf_ok = leaf_ok_pure & ~lcollide
            lfirst = jnp.argmax(leaf_ok)
            lany = jnp.any(leaf_ok)
            lany_pure = jnp.any(leaf_ok_pure)
            placed = any_ok & lany
            slot = jnp.arange(numrep) == placed_n
            out = jnp.where(slot & placed, sel_item, out)
            out2 = jnp.where(slot & placed, leaves_l[lfirst], out2)
            placed_n = placed_n + placed.astype(jnp.int32)
            reparr = jnp.arange(numrep) == rep
            fail_pure = jnp.where(reparr, any_ok & ~lany_pure,
                                  fail_pure)
            coll_fail = jnp.where(reparr, any_ok & lany_pure & ~lany,
                                  coll_fail)
            firsts = jnp.where(reparr, first.astype(jnp.int32), firsts)
            unfilled = jnp.where(reparr, ~any_ok, unfilled)
        return out, out2, placed_n, fail_pure, coll_fail, firsts, \
            unfilled

    # Fixpoint soundness for firstn (review finding): a candidate may
    # fail its ladder for two reasons — every attempt dead
    # (out-weighted / no leaf), which is prefix-INDEPENDENT and safe
    # to mark bad (C rejects it against any prefix), or attempts
    # alive but colliding with EARLIER positions' leaves, which
    # depends on the pass's provisional prefix and must NOT be marked
    # (C might accept it against the final prefix).  Marks therefore
    # come only from fail_pure; a collision-caused failure surviving
    # to the final pass flags need_host (requires a dual-homed device
    # — two domain buckets sharing an osd — which real maps don't
    # produce).  On convergence the returned pass's prefix IS final,
    # so its lcollide masks are exact.
    bad = jnp.zeros((numrep, T), bool)
    cols = jnp.arange(T, dtype=jnp.int32)[None, :]
    out, out2, placed_n, fail_pure, coll_fail, firsts, unfilled = \
        accept_pass(bad)
    if fix > 8:
        def cond(st):
            return jnp.any(st[0][3]) & (st[1] < numrep * T + 1)

        def body(st):
            res, it, bad = st
            fail_pure, firsts = res[3], res[5]
            bad = bad | ((cols == firsts[:, None]) & fail_pure[:, None])
            return accept_pass(bad), it + 1, bad

        (out, out2, placed_n, fail_pure, coll_fail, firsts,
         unfilled), _, bad = jax.lax.while_loop(
            cond, body,
            ((out, out2, placed_n, fail_pure, coll_fail, firsts,
              unfilled), jnp.int32(0), bad))
    else:
        for _ in range(fix - 1):
            bad = bad | ((cols == firsts[:, None])
                         & fail_pure[:, None])
            out, out2, placed_n, fail_pure, coll_fail, firsts, \
                unfilled = accept_pass(bad)
    need_host = jnp.any(fail_pure) | jnp.any(coll_fail)
    if not exact_budget:
        need_host = need_host | jnp.any(unfilled)
    return out2, placed_n, need_host


def _choose_indep(cm, take, x, numrep, type_, recurse_to_leaf,
                  weight_vec, T, take_type, leaf_tries=1,
                  exact_budget=False, slots=None,
                  leaf_cap=LEAF_TRIES_CAP, leaf_fix_iters=1):
    """mapper.c -> crush_choose_indep, leaf-lazy and round-vectorized.

    Phase 1 — domain candidate grid (T, numrep), one batched descent
    (r = rep + stride*ftotal applied per level inside _descend).
    Phase 2 — PROVISIONAL accept: the C round loop, vectorized to one
    fused step per round.  Within a round, reps are processed in order
    and a later rep collides against items accepted by earlier reps of
    the same round; because collision is same-item-only, "rep accepts"
    reduces to "rep is the EARLIEST candidate-ok rep proposing its
    item" — an (R, R) masked comparison, no inner rep loop.
    Phase 3 — leaf descents ONLY for the numrep accepted candidates
    (not the whole grid): the recursion is crush_choose_indep(left=1,
    outpos=rep, tries=recurse_tries, parent_r=r) — up to ``leaf_tries``
    attempts at r2 = rep + parent_r + stride*l, first in-weight osd
    wins, no cross-position leaf dedup (mapper.py indep note).

    The provisional accept assumes every examined leaf succeeds; that
    matches C exactly unless an ACCEPTED candidate's leaf ladder
    fails entirely within min(leaf_tries, cap) attempts — C would then
    reject the domain candidate and reshuffle the slot — so exactly
    those lanes flag need_host.  (This replaces the old grid-wide
    okd0&~ok0 flag, which fired on leaf failures C never examines.)

    ``exact_budget``: T equals C's own try budget, so a slot left
    UNDEF after T rounds is C's own NONE hole, not a device-budget
    artifact — no host flag for it.

    ``slots``: output positions to fill (C's ``left``); defaults to
    numrep.  They differ when the rule's numrep exceeds result_max:
    mapper.c still STRIDES r by the uncapped numrep while filling only
    ``left`` slots, so the stride base must not be capped with it.

    ``leaf_cap``: rung-level bound on modeled leaf attempts.  The
    first ladder rung models try 0 only (on an un-reweighted map the
    first leaf try always lands, so tries 1..L-1 are pure waste
    there); a lane whose accepted candidate fails every MODELED try is
    flagged either way — a deeper rung (full L) or ultimately the host
    resolves whether C salvages it."""
    R = numrep if slots is None else slots
    base = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int64)[None, :],
                            (T, R))                            # r = rep
    fs = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int64)[:, None],
                          (T, R))
    # choose_args position: crush_choose_indep passes its own outpos
    # (= 0 here, one choose per take) to the domain pick, and rep to
    # the leaf recursion's bucket choose.
    items, okd0, parent_r = _descend(cm, take, x, base, type_,
                                     cm.descend_steps(take_type, type_),
                                     0, indep_f=fs,
                                     indep_numrep=numrep,
                                     return_last_r=True)
    if not recurse_to_leaf and type_ == 0:
        okd0 = okd0 & ~_is_out(weight_vec, items, x)
    ar = jnp.arange(R)
    earlier = ar[:, None] > ar[None, :]          # [rep, rep']: rep' first
    UNDEF = jnp.int32(-0x7FFFFFFF)

    def round_step(carry, inp):
        out, sel_f, placed = carry
        p, okd, f = inp                                        # (R,)
        collide = jnp.any(out[None, :] == p[:, None], axis=1)
        okb = okd & ~placed & ~collide
        blocked = jnp.any((p[:, None] == p[None, :]) & earlier
                          & okb[None, :], axis=1)
        acc = okb & ~blocked
        return (jnp.where(acc, p, out),
                jnp.where(acc, f, sel_f),
                placed | acc), None

    def accept_scan(ok_grid):
        # lax.scan (not a python unroll): one compiled round body
        # keeps XLA compile time T-independent — the deep-rung T=32
        # program took >5 min to compile unrolled
        return jax.lax.scan(
            round_step,
            (jnp.full(R, UNDEF, jnp.int32), jnp.zeros(R, jnp.int32),
             jnp.zeros(R, bool)),
            (items.astype(jnp.int32), ok_grid,
             jnp.arange(T, dtype=jnp.int32)))[0]

    if not recurse_to_leaf:
        out, sel_f, placed = accept_scan(okd0)
        need_host = jnp.asarray(False) if exact_budget \
            else jnp.any(~placed)
        return jnp.where(placed, out, NONE).astype(jnp.int32), need_host

    L = max(1, min(leaf_tries, LEAF_TRIES_CAP, leaf_cap))
    ls = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int64)[:, None],
                          (L, R))
    leaf_steps = cm.descend_steps(type_, 0)

    def leaf_eval(out, sel_f, placed):
        # accepted candidates' parent_r; unplaced slots descend from
        # the take bucket (well-defined rows), masked out by ``placed``
        pr = jnp.take_along_axis(parent_r,
                                 sel_f[None, :].astype(jnp.int64),
                                 axis=0)[0]                    # (R,)
        start = jnp.where(placed, out, jnp.int32(take))
        leaves, lok = _descend(cm, start[None, :], x,
                               jnp.broadcast_to(pr + ar, (L, R)),
                               0, leaf_steps,
                               ar[None, :], indep_f=ls,
                               indep_numrep=numrep)
        leaf_ok = lok & ~_is_out(weight_vec, leaves, x)        # (L, R)
        lfirst = jnp.argmax(leaf_ok, axis=0)
        lany = jnp.any(leaf_ok, axis=0)
        leaf_sel = jnp.take_along_axis(leaves, lfirst[None, :],
                                       axis=0)[0]
        return leaf_sel, lany

    # Leaf-aware fixpoint: a leaf-failed candidate behaves in C
    # exactly like a domain-rejected one at that grid position (the
    # slot stays UNDEF and retries; nothing is placed), and the leaf
    # outcome is a pure function of (f, rep) — so marking the failed
    # position bad and re-running the accept scan reproduces C's
    # reshuffling layer by layer.  Marking is sound ONLY when the
    # modeled ladder covers C's full leaf budget (L == leaf_tries):
    # with a truncated ladder C might salvage the candidate at an
    # unmodeled try, so those programs never mark — they flag on the
    # first failure instead.  Lanes still failing after the configured
    # layers flag need_host (a deeper rung or the host resolves).
    sound = L == leaf_tries
    rows = jnp.arange(T, dtype=jnp.int32)[:, None]
    bad = jnp.zeros((T, R), bool)
    out, sel_f, placed = accept_scan(okd0)
    leaf_sel, lany = leaf_eval(out, sel_f, placed)
    fix_iters = max(1, leaf_fix_iters) if sound else 1
    if fix_iters > 8:
        # run the fixpoint to convergence: every iteration with a
        # failing lane marks >= 1 new bad position, so <= T*R
        # iterations suffice and the converged state is exact — used
        # by the final full-budget rung (vmapped while_loop executes
        # until every lane in the block converges, lanes mask out as
        # they finish)
        def cond(st):
            bad, out, sel_f, placed, leaf_sel, lany, it = st
            return jnp.any(placed & ~lany) & (it < T * R + 1)

        def body(st):
            bad, out, sel_f, placed, leaf_sel, lany, it = st
            fail = placed & ~lany
            bad = bad | ((rows == sel_f[None, :]) & fail[None, :])
            out, sel_f, placed = accept_scan(okd0 & ~bad)
            leaf_sel, lany = leaf_eval(out, sel_f, placed)
            return (bad, out, sel_f, placed, leaf_sel, lany, it + 1)

        bad, out, sel_f, placed, leaf_sel, lany, _ = jax.lax.while_loop(
            cond, body,
            (bad, out, sel_f, placed, leaf_sel, lany, jnp.int32(0)))
    else:
        for _ in range(fix_iters - 1):
            fail = placed & ~lany
            bad = bad | ((rows == sel_f[None, :]) & fail[None, :])
            out, sel_f, placed = accept_scan(okd0 & ~bad)
            leaf_sel, lany = leaf_eval(out, sel_f, placed)
    fail = placed & ~lany
    ok = placed & lany
    need_host = (jnp.asarray(False) if exact_budget
                 else jnp.any(~placed)) | jnp.any(fail)
    return jnp.where(ok, leaf_sel, NONE).astype(jnp.int32), need_host


def _chained_single(cm, takes, count, x, type_, recurse_to_leaf,
                    weight_vec, T, firstn, from_type,
                    leaf_tries=1, leaf_cap=LEAF_TRIES_CAP,
                    leaf_fix_iters=1, exact_budget=False):
    """A SECOND choose step over the previous step's output vector
    (mapper.c: per input bucket a fresh segment, outpos=0), numrep=1
    per segment — the common chained EC shape (choose N type rack ->
    chooseleaf 1 type host).

    Domain candidates for every (try, segment) pair come from one
    batched descent (segments are independent: r restarts per segment,
    numrep=1 segments cannot self-collide, and C's chained recursion
    collision scans are empty at outpos=0); per segment the first
    acceptable try wins, with leaf recursions modeled lazily for the
    accepted candidate only — the same leaf-ladder + mark-bad fixpoint
    as _choose_indep (see its docstring for the soundness argument),
    simplified by segment independence.  firstn semantics: a segment
    that places nothing (or an invalid take inside the segment range)
    shifts downstream packing in mapper.c, so those lanes re-run on
    the host; an indep hole at the rule's own full budget
    (``exact_budget``) is C's NONE and stays on device."""
    R = takes.shape[0]
    # firstn at numrep=1: r = rep+parent_r+ftotal = ftotal.  indep at
    # numrep=1: r = rep + stride*ftotal with the per-level uniform
    # stride (size % 1 == 0 always, so uniform levels stride by 2) —
    # applied inside _descend.
    fs = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int64)[:, None], (T, R))
    if firstn:
        items, ok, parent_r = _descend(
            cm, takes[None, :], x, fs, type_,
            cm.descend_steps(from_type, type_), 0, return_last_r=True)
    else:
        items, ok, parent_r = _descend(
            cm, takes[None, :], x, jnp.zeros_like(fs), type_,
            cm.descend_steps(from_type, type_), 0, indep_f=fs,
            indep_numrep=1, return_last_r=True)
    in_seg = jnp.arange(R) < count
    valid_take = takes < 0
    live = in_seg & valid_take
    # an invalid take inside the segment range is skipped entirely by
    # mapper.c (osize does not advance) — positions shift: host lane
    need_host = jnp.any(in_seg & ~valid_take)
    if not recurse_to_leaf:
        if type_ == 0:
            ok = ok & ~_is_out(weight_vec, items, x)
        ok = ok & live[None, :]
        first = jnp.argmax(ok, axis=0)                   # (R,)
        any_ok = jnp.any(ok, axis=0)
        sel = jnp.take_along_axis(items, first[None, :], axis=0)[0]
        out = jnp.where(any_ok, sel, NONE).astype(jnp.int32)
        # an unfilled segment may still place within C's own budget —
        # host decides — unless T already IS that budget, where a
        # firstn miss still shifts packing (host) but an indep hole
        # is C's own NONE
        if firstn or not exact_budget:
            need_host = need_host | jnp.any(live & ~any_ok)
        return out, need_host

    # chooseleaf: leaf ladders ONLY for each segment's accepted
    # candidate, modeling C's recursion exactly — firstn: numrep=1
    # stable recursion, r' = sub_r + l with sub_r = r (vary_r=1), no
    # uniform stride; indep: r' = parent_r + stride*l via the
    # per-level indep stride at numrep=1.  Same provisional-accept +
    # mark-bad fixpoint as _choose_indep, but segments are independent
    # (no cross-segment collision scans in C), so the fixpoint is
    # per-segment.
    ok_dom = ok & live[None, :]
    L = max(1, min(leaf_tries, LEAF_TRIES_CAP, leaf_cap))
    sound = L == leaf_tries
    fix = max(1, leaf_fix_iters) if sound else 1
    ls = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int64)[:, None], (L, R))
    leaf_steps = cm.descend_steps(type_, 0)
    rows = jnp.arange(T, dtype=jnp.int32)[:, None]

    def accept(bad):
        okb = ok_dom & ~bad
        return jnp.argmax(okb, axis=0).astype(jnp.int32), \
            jnp.any(okb, axis=0)

    def leaf_eval(first, any_ok):
        sel_item = jnp.take_along_axis(items, first[None, :], axis=0)[0]
        sel_r = jnp.take_along_axis(parent_r, first[None, :].astype(
            jnp.int64), axis=0)[0]
        start = jnp.where(any_ok, sel_item, jnp.int32(-1))[None, :]
        if firstn:
            leaves, lok = _descend(cm, start, x, sel_r + ls, 0,
                                   leaf_steps, 0)
        else:
            leaves, lok = _descend(cm, start, x,
                                   jnp.broadcast_to(sel_r, (L, R)), 0,
                                   leaf_steps, 0, indep_f=ls,
                                   indep_numrep=1)
        leaf_ok = lok & ~_is_out(weight_vec, leaves, x)    # (L, R)
        lfirst = jnp.argmax(leaf_ok, axis=0)
        lany = jnp.any(leaf_ok, axis=0)
        leaf_sel = jnp.take_along_axis(leaves, lfirst[None, :],
                                       axis=0)[0]
        return leaf_sel, lany

    bad = jnp.zeros((T, R), bool)
    first, any_ok = accept(bad)
    leaf_sel, lany = leaf_eval(first, any_ok)
    if fix > 8:
        def cond(st):
            bad, first, any_ok, leaf_sel, lany, it = st
            return jnp.any(any_ok & ~lany) & (it < T + 1)

        def body(st):
            bad, first, any_ok, leaf_sel, lany, it = st
            fail = any_ok & ~lany
            bad = bad | ((rows == first[None, :]) & fail[None, :])
            first, any_ok = accept(bad)
            leaf_sel, lany = leaf_eval(first, any_ok)
            return (bad, first, any_ok, leaf_sel, lany, it + 1)

        bad, first, any_ok, leaf_sel, lany, _ = jax.lax.while_loop(
            cond, body,
            (bad, first, any_ok, leaf_sel, lany, jnp.int32(0)))
    else:
        for _ in range(fix - 1):
            fail = any_ok & ~lany
            bad = bad | ((rows == first[None, :]) & fail[None, :])
            first, any_ok = accept(bad)
            leaf_sel, lany = leaf_eval(first, any_ok)
    fail = any_ok & ~lany
    placed = any_ok & lany
    out = jnp.where(placed, leaf_sel, NONE).astype(jnp.int32)
    need_host = need_host | jnp.any(fail)
    if firstn or not exact_budget:
        need_host = need_host | jnp.any(live & ~any_ok)
    return out, need_host


def compile_rule(cm: CompiledCrushMap, ruleno: int, result_max: int,
                 bulk_tries: int = DEFAULT_BULK_TRIES,
                 leaf_cap: int = LEAF_TRIES_CAP,
                 leaf_fix_iters: int = 1):
    """Build fn(x, weight_vec) -> (results, count, need_host)."""
    rule = cm.cmap.rules[ruleno]
    tunables = cm.cmap.tunables
    if (tunables.choose_local_tries or tunables.choose_local_fallback_tries
            or tunables.chooseleaf_vary_r != 1
            or tunables.chooseleaf_stable != 1
            or not tunables.chooseleaf_descend_once):
        # the fused program hardcodes jewel chooseleaf semantics
        # (sub_r = r, recursion rep 0, one leaf try); older profiles run
        # on the host mapper.  The vary_r/stable checks are EXACT-value,
        # not truthiness: vary_r >= 2 is a legal upstream transitional
        # value whose host semantics are sub_r = r >> (vary_r - 1) —
        # a map carrying it would pass a falsy-only guard and silently
        # diverge from the host mapper with no need_host flag (ADVICE
        # round 5); the same reasoning gates chooseleaf_stable > 1.
        raise ValueError("bulk evaluator requires jewel tunables "
                         "(choose_local_* == 0, chooseleaf_vary_r/"
                         "stable/descend_once == 1); use engine=host")
    if cm.type_level is None:
        # an irregular hierarchy can land a descent on a wrong-type item,
        # which mapper.c treats as terminal for the replica — semantics
        # the retryable candidate grid does not reproduce
        raise ValueError("bulk evaluator requires a regular hierarchy "
                         "(uniform level per bucket type, no empty "
                         "buckets); use engine=host")
    # clamp against the rule's own maximum budget (SET_CHOOSE_TRIES
    # raises it above the tunables default — the canonical EC rule
    # carries 100), so a deep rung CAN reach exact_budget there
    T = min(bulk_tries, _rule_tries_cap(cm.cmap, ruleno))
    steps = list(rule.steps)

    # tpu-lint: jit-function
    def fn(x, weight_vec):
        results = []
        take = None
        current = None
        current_type = None  # bucket type the last choose produced
        need_host = jnp.asarray(False)
        # SET_* rule overrides (the canonical EC rule carries
        # set_chooseleaf_tries 5 + set_choose_tries 100): the running
        # values are trace-time constants.  choose_tries caps the
        # per-step device budget (a SET below T must not let the
        # device succeed where C's budget ran out); choose_leaf_tries
        # feeds the per-candidate leaf-retry ladders (capped at the
        # rung's leaf_cap; a candidate exhausting the modeled ladder
        # is marked bad / flagged per the fixpoint soundness rule).
        choose_tries_run = tunables.choose_total_tries + 1
        leaf_tries_run = 0   # 0 = descend_once default (one try)
        for op, arg1, arg2 in steps:
            T_step = max(1, min(T, choose_tries_run))
            if op == CRUSH_RULE_TAKE:
                take = arg1
                current = None
                current_type = None
            elif op == CRUSH_RULE_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries_run = arg1
            elif op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    leaf_tries_run = arg1
            elif op in (CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
                        CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if arg1 > 0:
                    raise ValueError(
                        "bulk evaluator does not fuse local-retry "
                        "ladders (set_choose_local_* > 0); use "
                        "engine=host")
            elif op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0 and arg1 != 1:
                    raise ValueError(
                        "bulk evaluator hardcodes chooseleaf_vary_r=1; "
                        "use engine=host")
            elif op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
                if arg1 >= 0 and arg1 != 1:
                    raise ValueError(
                        "bulk evaluator hardcodes chooseleaf_stable=1; "
                        "use engine=host")
            elif op in (CRUSH_RULE_CHOOSE_FIRSTN,
                        CRUSH_RULE_CHOOSELEAF_FIRSTN):
                recurse = op == CRUSH_RULE_CHOOSELEAF_FIRSTN
                if current is not None:
                    if arg1 != 1:
                        raise ValueError(
                            "bulk evaluator supports chained choose "
                            "steps only with n=1 (the chooseleaf-per-"
                            "domain EC shape); use engine=host")
                    vals, nh = _chained_single(
                        cm, current[0], current[1], x, arg2, recurse,
                        weight_vec, T_step, True, current_type,
                        leaf_tries=leaf_tries_run if leaf_tries_run
                        else 1, leaf_cap=leaf_cap,
                        leaf_fix_iters=leaf_fix_iters,
                        exact_budget=T_step >= choose_tries_run)
                    need_host = need_host | nh
                    current = (vals, current[1])
                    current_type = arg2
                    continue
                numrep = arg1 if arg1 > 0 else arg1 + result_max
                numrep = min(numrep, result_max)  # C: count = out_size cap
                take_type = (cm.cmap.buckets[take].type
                             if take in cm.cmap.buckets else None)
                vals, count, nh = _choose_firstn(
                    cm, take, x, numrep, arg2, recurse, weight_vec,
                    T_step, take_type,
                    leaf_tries=leaf_tries_run if leaf_tries_run else 1,
                    leaf_cap=leaf_cap, leaf_fix_iters=leaf_fix_iters,
                    exact_budget=T_step >= choose_tries_run)
                need_host = need_host | nh
                current = (vals, count)
                current_type = arg2
            elif op in (CRUSH_RULE_CHOOSE_INDEP,
                        CRUSH_RULE_CHOOSELEAF_INDEP):
                recurse = op == CRUSH_RULE_CHOOSELEAF_INDEP
                if current is not None:
                    if arg1 != 1:
                        raise ValueError(
                            "bulk evaluator supports chained choose "
                            "steps only with n=1 (the chooseleaf-per-"
                            "domain EC shape); use engine=host")
                    vals, nh = _chained_single(
                        cm, current[0], current[1], x, arg2, recurse,
                        weight_vec, T_step, False, current_type,
                        leaf_tries=leaf_tries_run if leaf_tries_run
                        else 1, leaf_cap=leaf_cap,
                        leaf_fix_iters=leaf_fix_iters,
                        exact_budget=T_step >= choose_tries_run)
                    need_host = need_host | nh
                    current = (vals, current[1])
                    current_type = arg2
                    continue
                numrep = arg1 if arg1 > 0 else arg1 + result_max
                slots = min(numrep, result_max)  # C: got = min(numrep, seg)
                take_type = (cm.cmap.buckets[take].type
                             if take in cm.cmap.buckets else None)
                vals, nh = _choose_indep(
                    cm, take, x, numrep, arg2, recurse, weight_vec,
                    T_step, take_type,
                    leaf_tries=leaf_tries_run if leaf_tries_run else 1,
                    exact_budget=T_step >= choose_tries_run,
                    slots=slots, leaf_cap=leaf_cap,
                    leaf_fix_iters=leaf_fix_iters)
                need_host = need_host | nh
                current = (vals, jnp.int32(vals.shape[0]))
                current_type = arg2
            elif op == CRUSH_RULE_EMIT:
                if current is not None:
                    results.append(current)
                    current = None
            else:
                raise ValueError(
                    f"bulk evaluator does not support rule op {op}")
        out = jnp.full(result_max, NONE, jnp.int32)
        pos = jnp.int32(0)
        for vals, count in results:
            n = vals.shape[0]
            idx = jnp.arange(result_max)
            src = jnp.full(result_max, NONE, jnp.int32)
            src = src.at[:n].set(vals[:min(n, result_max)])
            shifted = jnp.take(src, jnp.clip(idx - pos, 0, result_max - 1))
            write = (idx >= pos) & (idx < pos + jnp.minimum(count, n))
            out = jnp.where(write, shifted, out)
            pos = jnp.minimum(pos + count, result_max)
        return out, pos, need_host

    return fn


def _get_jitted(cm: CompiledCrushMap, ruleno: int, result_max: int,
                bulk_tries: int, leaf_cap: int = LEAF_TRIES_CAP,
                leaf_fix_iters: int = 1, plane=None):
    key = (ruleno, result_max, bulk_tries, leaf_cap, leaf_fix_iters,
           None if plane is None else (plane.mesh, plane.axis))
    jf = cm._jit_cache.get(key)
    if jf is None:
        fn = compile_rule(cm, ruleno, result_max, bulk_tries, leaf_cap,
                          leaf_fix_iters)
        vf = jax.vmap(fn, in_axes=(0, None))
        if plane is None:
            jf = jax.jit(vf)
        else:
            # mesh-sharded PG sweep (the NamedSharding path that used
            # to live only in parallel/sharded_crush.py): the x batch
            # shards over the plane's axis, the compiled map tables
            # and weight vector replicate, and GSPMD partitions the
            # sweep with zero cross-device collectives — placement
            # evaluation is embarrassingly parallel over x
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = NamedSharding(plane.mesh, P(plane.axis))
            repl = NamedSharding(plane.mesh, P())
            jf = jax.jit(vf, in_shardings=(shard, repl),
                         out_shardings=(shard, shard, shard))
        cm._jit_cache[key] = jf
    return jf


FIRST_PASS_TRIES = 2  # covers the no-collision common case


def _rule_tries_cap(cmap, ruleno: int) -> int:
    """The largest try budget the rule can ever use in C — device
    rungs above it are pure waste (compile_rule clamps T to it)."""
    cap = cmap.tunables.choose_total_tries + 1
    for op, arg1, _ in cmap.rules[ruleno].steps:
        if op == CRUSH_RULE_SET_CHOOSE_TRIES and arg1 > 0:
            cap = max(cap, arg1)
    return cap


def auto_ladder(cmap, ruleno: int, result_max: int,
                bulk_tries: int) -> List[Tuple[int, int, int]]:
    """Device (try-budget, leaf-try-cap, leaf-fix-iters) rungs
    (VERDICT r04 Next#4: residue-adaptive).

    Narrow rules keep the classic cheap first rung (2 tries covers the
    no-collision common case).  Wide-indep rules (the canonical EC
    shape) have collision-heavy retries as the COMMON case — a 2-try
    rung redoes ~70% of lanes, pure waste — so their first rung starts
    at width+2.  The first rung also models only leaf try 0 (leaf_cap
    1): on an un-reweighted map the first leaf attempt always lands,
    so the deeper attempts are computed only for the lanes that
    actually flagged.  A final 2x rung re-dispatches the measured
    residue before any lane reaches the serial host path.  Every rung
    is clamped to the rule's own C budget (results are identical at
    any budget; rungs only move where lanes are computed)."""
    width = rule_width(cmap, ruleno, result_max)
    cap = _rule_tries_cap(cmap, ruleno)
    first = FIRST_PASS_TRIES if width <= 4 else width + 2
    # (leaf_cap, fix_iters) shape the leaf-lazy chooseleaf programs —
    # firstn, indep, and both chained forms; for rules with NO
    # chooseleaf step they are normalized to (CAP, 1) so rungs
    # differing only in them would compile identical HLO under a new
    # cache key — those duplicates are dropped below
    leaf_lazy = any(op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                           CRUSH_RULE_CHOOSELEAF_INDEP)
                    for op, _, _ in cmap.rules[ruleno].steps)
    if leaf_lazy:
        cands = ((first, 1, 1),
                 (first, LEAF_TRIES_CAP, 2),
                 (bulk_tries, LEAF_TRIES_CAP, 4),
                 (2 * bulk_tries, LEAF_TRIES_CAP, 8),
                 # the final rung runs at the rule's FULL C budget
                 # (clamped to 128 scan rounds) with the CONVERGENT
                 # while_loop fixpoint (fix>8), so a slot still
                 # unfilled there is C's own NONE hole (exact_budget)
                 # and leaf reshuffling resolves on device; only a
                 # truncated leaf ladder (leaf_tries > LEAF_TRIES_CAP
                 # rules) still falls back
                 (min(cap, 128), LEAF_TRIES_CAP, 16))
    else:
        cands = ((first, LEAF_TRIES_CAP, 1),
                 (bulk_tries, LEAF_TRIES_CAP, 1),
                 (2 * bulk_tries, LEAF_TRIES_CAP, 1),
                 (min(cap, 128), LEAF_TRIES_CAP, 1))
    rungs: List[Tuple[int, int, int]] = []
    for t, lcap, fix in cands:
        t = max(1, min(t, cap))
        if rungs:
            # budgets must be non-decreasing (an explicit small
            # bulk_tries must not demote a later rung below its
            # predecessor — it would re-flag the same lanes)
            t = max(t, rungs[-1][0])
        if not rungs or t > rungs[-1][0] or lcap > rungs[-1][1] \
                or fix > rungs[-1][2]:
            rungs.append((t, lcap, fix))
    return rungs


def rule_width(cmap, ruleno: int, result_max: int) -> int:
    """Widest resolved numrep among the rule's choose steps."""
    width = 1
    for op, arg1, _ in cmap.rules[ruleno].steps:
        if op in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP,
                  CRUSH_RULE_CHOOSELEAF_FIRSTN,
                  CRUSH_RULE_CHOOSELEAF_INDEP):
            n = arg1 if arg1 > 0 else arg1 + result_max
            width = max(width, min(n, result_max))
    return width


def auto_tries(cmap, ruleno: int, result_max: int) -> int:
    """Default device try budget scaled to the rule's widest choose:
    a wide indep step (the 6-wide canonical EC rule) needs more
    collision-retry rounds than the 3-replica default — at 8 tries a
    6-of-8-host sweep left 4.6% of lanes to the (serial) host
    fallback, dominating wall time; 2n+4 tries cut it to ~0.1%.
    Results are identical at any budget (the ladder invariant); only
    where lanes are computed changes."""
    tries = DEFAULT_BULK_TRIES
    n = rule_width(cmap, ruleno, result_max)
    if n > 4:
        tries = max(tries, 2 * n + 4)
    return tries


def auto_block(cmap, ruleno: int, result_max: int, tries: int) -> int:
    """Lanes per dispatch, shrunk as tries*width grows so the
    candidate-grid footprint (O(lanes * tries * width) ints) stays
    roughly constant — a 32-wide indep rule at its auto budget would
    otherwise hold gigabytes per dispatch."""
    width = rule_width(cmap, ruleno, result_max)
    budget = BULK_BLOCK * (DEFAULT_BULK_TRIES * 6)   # the tuned case
    return max(1 << 12, min(BULK_BLOCK,
                            budget // max(1, tries * width)))


@with_x64
def bulk_do_rule(cmap, ruleno: int, xs, result_max: int,
                 weight: Optional[Sequence[int]] = None,
                 bulk_tries: Optional[int] = None,
                 return_stats: bool = False,
                 choose_args: Optional[Dict[int, "ChooseArg"]] = None,
                 mesh=None):
    """Evaluate a rule for many inputs at once on device; bit-identical
    to the host mapper.

    Adaptive ladder: a T=2-attempt pass handles the ~95% of lanes that
    place without retries; lanes that exhausted it re-run with the full
    device budget (``bulk_tries``); the residue (typically O(1e-5))
    re-runs on the exact host reference.  A lane that completes within
    a budget is byte-identical at any larger budget, so the ladder never
    changes results — only where they are computed.

    ``mesh``: shard the PG (x) axis over a device mesh — a DataPlane /
    jax Mesh, or None to follow the active data plane
    (parallel/plane.py; single-device when none is active).  Blocks
    round up to the device count and the x batch pads by repetition
    (lane results are x-pure, so pad lanes are discarded exactly like
    the tail pad).  Same rung ladder, same host residue, bit-identical
    results — the mesh only moves where lanes are computed.

    Returns (results (N, result_max) int32 with CRUSH_ITEM_NONE holes,
    counts (N,)); with return_stats also the host-fallback lane count.
    """
    from ..parallel.plane import resolve_plane
    plane = resolve_plane(mesh)
    nd = plane.n_devices if plane is not None else 1
    if isinstance(cmap, CompiledCrushMap):
        cm = cmap
        if choose_args is not None and cm.choose_args is not choose_args:
            raise ValueError(
                "choose_args differ from the ones this CompiledCrushMap "
                "was built with; rebuild CompiledCrushMap(cmap, "
                "choose_args)")
        choose_args = cm.choose_args
    else:
        cm = CompiledCrushMap(cmap, choose_args)
    if weight is None:
        weight = cm.cmap.device_weights()
    wv = jnp.asarray(np.asarray(weight, dtype=np.int64))
    xs = np.asarray(xs, dtype=np.int64)
    if bulk_tries is None:
        bulk_tries = auto_tries(cm.cmap, ruleno, result_max)

    rungs = auto_ladder(cm.cmap, ruleno, result_max, bulk_tries)
    n = len(xs)
    out = np.empty((n, result_max), np.int32)
    cnt = np.empty(n, np.int32)
    need = np.zeros(n, bool)
    # block the sweep: the candidate grids are O(lanes * tries * reps)
    # ints, so a multi-million-lane wide-indep sweep in one dispatch is
    # memory-bound (measured 2x slower than blocked on CPU); blocks
    # share one compiled program (the tail pads to the block shape)
    block = min(n, auto_block(cm.cmap, ruleno, result_max,
                              rungs[0][0])) or 1
    if nd > 1:
        block = -(-block // nd) * nd  # shard_map-divisible blocks
        from ..telemetry import metrics as tel
        tel.counter("engine_mesh_dispatches", tier="crush-bulk",
                    devices=str(nd))
    jf = _get_jitted(cm, ruleno, result_max, *rungs[0], plane=plane)

    # supervised dispatch seam (ops/supervisor.py): the first-rung
    # block dispatch classifies transient/OOM/backend-loss failures;
    # the host twin is the exact reference mapper the residue ladder
    # already falls back to, so a demoted completion is bit-identical
    # by the same invariant (host lanes never re-enter device rungs:
    # the twin answers need_host=False)
    from ..ops.supervisor import global_supervisor

    def _host_block(xs_arr):
        o_h = np.empty((len(xs_arr), result_max), np.int32)
        c_h = np.empty(len(xs_arr), np.int32)
        for j, x in enumerate(xs_arr):
            r = crush_do_rule(cm.cmap, ruleno, int(x), result_max,
                              weight=list(weight),
                              choose_args=choose_args)
            o_h[j] = r + [NONE] * (result_max - len(r))
            c_h[j] = len(r)
        return o_h, c_h, np.zeros(len(xs_arr), bool)

    def _dev_block(xs_arr):
        return jf(jnp.asarray(np.asarray(xs_arr)), wv)
    # cost-attribution capture for the fused rule program
    # (telemetry/profiler.py): the first block lowers once for XLA
    # cost_analysis (zero backend compiles — the jit cache above still
    # owns compilation), every block dispatch lands in the program's
    # latency histogram.  Keyed like the jit cache, plus the map size
    # so a 10k-OSD sweep and a toy map don't share a row.
    from ..telemetry import metrics as _tel
    from ..telemetry.profiler import global_profiler
    prof = global_profiler()
    prof_key = ("crush.bulk_rule", ruleno, result_max, rungs[0],
                block, nd, len(wv))
    captured = not _tel.enabled()
    for s in range(0, n, block):
        e = min(s + block, n)
        xs_b = xs[s:e]
        if e - s < block:
            xs_b = np.concatenate([xs_b, xs_b[:1].repeat(block - (e - s))])
        xs_d = jnp.asarray(xs_b)
        if not captured:
            captured = True
            prof.capture(prof_key, jf, (xs_d, wv),
                         name="crush.bulk_rule", plugin="crush",
                         kind="bulk-rule", batch=block,
                         pattern=f"rule{ruleno}x{result_max}",
                         engine="mesh" if nd > 1 else "device",
                         devices=nd)
        with prof.timed(prof_key, eager=_tel.enabled()):
            # verifiable=False: the device block legitimately differs
            # from the reference twin (need-host flags feed the
            # residue ladder), so CRC self-verify does not apply here
            o, c, nm = global_supervisor().dispatch(
                "crush.bulk_rule", _dev_block, (xs_b,),
                host_fn=_host_block, verifiable=False)
            out[s:e] = np.asarray(o)[:e - s]
            cnt[s:e] = np.asarray(c)[:e - s]
            need[s:e] = np.asarray(nm)[:e - s]
    redo = np.nonzero(need)[0]
    host_limit = HOST_RESIDUE_LANES.get(jax.default_backend(),
                                        HOST_RESIDUE_LANES["cpu"])

    # residue-adaptive rungs: each deeper budget re-dispatches ONLY the
    # lanes the previous rung flagged, so serial host work is bounded
    # by the residue of the deepest rung (VERDICT r04 Next#4)
    for tries, lcap, fix in rungs[1:]:
        if not redo.size:
            break
        rung_key = (ruleno, result_max, tries, lcap, fix,
                    None if plane is None else (plane.mesh, plane.axis))
        if redo.size <= host_limit and rung_key not in cm._jit_cache:
            # compiling this rung costs more than the host mapper's
            # walk of the residue (HOST_RESIDUE_LANES); a deeper rung
            # already compiled still runs.  Results are identical
            # either way (the ladder invariant)
            continue
        jf2 = _get_jitted(cm, ruleno, result_max, tries, lcap, fix,
                          plane=plane)
        rblock = min(block, auto_block(cm.cmap, ruleno, result_max,
                                       tries)) or 1
        if nd > 1:
            rblock = -(-rblock // nd) * nd
        host_lanes = []
        for s in range(0, len(redo), rblock):
            idx = redo[s:s + rblock]
            m = len(idx)
            # pad to the next power of two so redo batches reuse a
            # bounded set of compiled shapes
            padm = 1 << max(10, (m - 1).bit_length())
            padm = min(padm, rblock)
            if nd > 1:
                padm = min(-(-padm // nd) * nd, rblock)
            xs_r = xs[idx]
            if padm > m:
                xs_r = np.concatenate([xs_r, xs_r[:1].repeat(padm - m)])
            o, c, nh = jf2(jnp.asarray(xs_r), wv)
            out[idx] = np.asarray(o)[:m]
            cnt[idx] = np.asarray(c)[:m]
            host_lanes.append(idx[np.asarray(nh)[:m]])
        redo = np.concatenate(host_lanes) if host_lanes \
            else np.empty(0, np.int64)

    n_fallback = int(redo.size)
    for i in redo:
        r = crush_do_rule(cm.cmap, ruleno, int(xs[i]), result_max,
                          weight=list(weight), choose_args=choose_args)
        out[i] = r + [NONE] * (result_max - len(r))
        cnt[i] = len(r)
    from ..utils.debug import DeviceVerificationError, verification_enabled
    if verification_enabled():
        # sanitizer mode: every lane re-evaluated on the host oracle
        for i in range(len(xs)):
            r = crush_do_rule(cm.cmap, ruleno, int(xs[i]), result_max,
                              weight=list(weight),
                              choose_args=choose_args)
            r = r + [NONE] * (result_max - len(r))
            if list(out[i]) != r:
                raise DeviceVerificationError(
                    f"bulk evaluator diverged from host mapper at "
                    f"x={int(xs[i])}: {list(out[i])} != {r}")
    if return_stats:
        return out, cnt, n_fallback
    return out, cnt
