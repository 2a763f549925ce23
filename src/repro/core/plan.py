"""Multi-query planner: N declarative queries -> one shared evaluation.

A production monitor runs many concurrent queries over the *same* frames,
and most of them ask about the same few classes and regions (BlazeIt,
VidCEP).  ``repro.core.query.eval_filters`` evaluates one query tree at a
time, re-thresholding the CAM grid and re-scanning it per Spatial/Region
leaf; with N registered queries that work is repeated N times per batch.
``QueryPlan`` removes all of that redundancy:

1.  **Leaf canonicalization + dedup.**  Every leaf of every query is
    canonicalized (``query.canonicalize_leaf`` — e.g. RIGHT(a, b) and
    LEFT(b, a) are the same extremum test) and assigned a *slot*; two
    queries asking the same question about the same class share one slot,
    evaluated once.

2.  **Grouped, batched leaf lowering.**  The deduped leaf set is lowered
    by kind into a handful of fused tensor ops, with no Python loop over
    leaves or queries on the hot path:

    - Count/ClassCount slots become one gather over the (B, C+1) rounded
      count table plus a vectorised interval test (lo/hi bounds encode
      EQ/GE/LE with the CF-k/CCF-k tolerance).
    - Spatial slots are evaluated from the (B, C, 5) spatial-statistics
      tensor produced by the fused Pallas reduction
      (``kernels.spatial_predicate``): min/max row/col + cell count are
      sufficient statistics for every ORDER() relation, and Manhattan
      dilation (CLF-k) shifts extrema analytically — one grid reduction
      total, shared by all spatial leaves of all queries.
    - Region slots group by dilation radius; the grid is thresholded once
      and dilated *incrementally* radius-to-radius, and each radius builds
      one summed-area table so every rectangle-count leaf is four gathers
      — no per-leaf grid scan, no stacked-mask einsum.

3.  **Incidence-matrix reassembly.**  Query trees are normalised to NNF
    (Not pushed to the leaves), flattened into one levelized node program
    over all queries, and evaluated bottom-up: per depth level, one gather
    of child values, one ``einsum`` against a 0/1 parent-child incidence
    matrix, and one threshold (sum == n_children for And, >= 1 for Or).
    The Python loop is over tree *depth* (tiny), never over queries.  Root
    columns of the final value matrix are the per-query (B, N) masks.

4.  **Staged adaptive execution** (``StagedQueryPlan``).  ``evaluate``
    runs every slot every batch; the staged plan instead partitions the
    slots into cost tiers matching the lowering groups above — count
    gathers, then the spatial-stats tier, then one stage per Region
    dilation radius — and evaluates stage by stage with **three-valued
    propagation** through the NNF incidence program: after each stage,
    two passes of the levelized program (unknown literals forced to 0,
    then to 1) yield a lower/upper bound per (frame, query); a query
    column whose bounds agree is *decided* (And/Or gates are monotone, so
    the bounds are exact).  Execution stops the moment every query column
    is decided, and a stage whose slots no longer influence any undecided
    query column is skipped entirely — the cross-query analogue of the
    paper's per-query cheapest-first conjunct ordering, including never
    touching the grid when the count tier already answers everything.

    Stage order, and the slot order within each stage, come from
    **population-level statistics**: a ``SlotStats`` store
    (repro.core.stats) keyed by canonical leaf accumulates observed pass
    rates over every registered query's traffic, and stages are sorted by
    static-cost / expected-decisions (cheapest, most selective, most
    widely-referenced first).  The spatial tier is additionally
    class-sliced (``kernels.spatial_predicate.stage_class_slice``): the
    stats reduction only reads the grid planes the population's leaves
    mention.  Observed per-slot pass counts are accumulated on device and
    fetched in ONE deferred transfer per batch (``flush_stats``);
    ``restage`` re-sorts the stages when the learned rates change the
    order.  Within each stage the evaluation keeps the fixed-shape,
    loop-free formulation of the exhaustive plan, so every stage function
    jits once and stays jit-cache-stable across batches.

    **Row-level short-circuiting.**  Tier-granular skipping still runs a
    needed stage on the whole batch even when 90% of the *frames* are
    already decided.  The staged executor therefore compacts the
    undecided rows between tiers: after each stage's bounds propagation,
    the surviving row indices are gathered (``cascade.compact_indices``,
    the host-side generalization of ``compact_survivors``'s bucketing)
    into fixed-size power-of-two buckets — jit-cache-stable shapes, one
    compiled step per (stage, prefix, bucket) — and the next, more
    expensive tier evaluates only those rows: the count gather and SAT
    stages index their row subset directly, and the spatial tier's stats
    reduction rides the scalar-prefetched row-gather kernel
    (``kernels.spatial_predicate.spatial_stats_rows_bgc``).  Leaf values
    and bounds are scattered back into the full-batch (B, N) masks, so
    the result stays bit-identical while per-stage work scales with the
    *undecided* fraction instead of the batch size.  Reported stage costs
    (and the adaptive cascade's park/un-park decision) scale with rows
    actually evaluated, and every batch feeds the per-stage row ledger in
    ``SlotStats`` so a parked cascade can predict the staged cost without
    probing.

5.  **Measured costs and position-aware ordering** (repro.core.costmodel).
    Every cost the staged executor reasons with — the per-stage ordering
    scores, ``StageReport.cost_run``, ``predicted_batch_cost``, and the
    exhaustive baseline the adaptive cascade parks against — goes through
    a ``CostModel``: per-backend coefficients calibrated from
    microbenchmarks of the actual stage bodies (``make calibrate``), with
    a provable fallback to the legacy hand-picked constants when no
    trustworthy calibration exists.  The stage order itself comes from a
    **greedy sequential search**: stages are placed one position at a
    time, each position scored at the row count the already-placed
    prefix is predicted to leave undecided (``SlotStats.stage_survival``
    — the per-stage survival observations are position-conditioned, so a
    one-shot global sort must not consume them; placing prefix-by-prefix
    matches the conditioning direction they were measured under).  Under
    the static model costs are purely proportional to rows, every
    position scales all candidates equally, and the greedy search
    provably degenerates to the classic cost/benefit ratio sort — the
    exact legacy order.  A measured model's fixed per-stage overheads
    are what make position matter: an overhead-dominated SAT stage that
    ranks cheap at full batch ranks expensive once the count tier has
    compacted the batch to a few rows.

    The model also *steers* execution, not just pricing (the closed
    calibration loop — decision policy in docs/tuning.md): a compacted
    spatial stage runs whichever of its two bit-identical bodies (the
    row-gather kernel vs the full-batch reduction over the gathered
    rows) the calibration says is cheaper at that bucket's row count;
    the row-compaction bucket floor is derived from the fitted
    overhead-vs-per-row trade when no explicit ``min_bucket=`` is
    given; and a ``costmodel.CalibrationMonitor`` fed by the adaptive
    cascade compares each staged batch's predicted cost against its
    observed wall time, flagging re-calibration when the model has
    drifted off the machine.

The shared evaluation is bit-identical to running ``eval_filters`` per
query, and the staged plan is bit-identical to ``evaluate`` under every
stage order, statistics state, and cost model (property-tested in
tests/test_query_properties.py and tests/test_costmodel.py); staging is
purely a work-skipping transformation — boolean dilation composes
exactly, and the SAT / extremum arithmetic is integer-exact in float32.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict, defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import costmodel as CM
from repro.core import query as Q
from repro.core.cascade import compact_indices
from repro.core.filters import FilterOutputs
from repro.core.stepcache import StepCache, content_digest
from repro.kernels import spatial_predicate as SP

_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min


class CanonicalLeafTable:
    """Persistent canonical-predicate -> slot map with stable slot ids.

    The incremental half of the plan lifecycle: a ``QueryPlan`` built
    against a shared table (``QueryPlan(..., leaf_table=...)`` — the
    ``QueryRegistry`` owns one the same way it owns ``SlotStats``) keeps
    slot ids stable across registry epochs, so a query registering or
    retiring is a *delta* against the table instead of a re-numbering of
    every leaf:

    - ``sync(queries)`` diffs the new query multiset against the last
      synced one at canonical-tree granularity (each tree canonicalized
      once ever, memoized) — only the changed trees' leaves touch the
      refcounts, so a K-query delta over an N-query population is O(K),
      not O(N).
    - A leaf whose refcount drops to zero is **tombstoned**, not freed:
      it keeps its slot id, so re-registering the same predicate
      resurrects the slot — and every compiled-step signature that
      mentions it — instead of allocating a fresh column.
    - Tombstones are compacted (dead columns dropped, live slots
      renumbered densely, ``version`` bumped so plan signatures move)
      only when the dead fraction of the slot space crosses
      ``compact_threshold`` — fragmentation is bounded without paying a
      global renumber per retirement.

    Slot ids are allocated first-seen in query order, exactly like the
    pre-table planner, so a fresh private table (what a standalone
    ``QueryPlan`` builds) reproduces the legacy slot layout verbatim.
    """

    def __init__(self, *, compact_threshold: float = 0.5):
        if not 0.0 < compact_threshold <= 1.0:
            raise ValueError(f"compact_threshold must be in (0, 1], "
                             f"got {compact_threshold}")
        self.compact_threshold = compact_threshold
        self._slots: Dict[Q.Predicate, int] = {}    # key -> slot (live
        self._keys: List[Q.Predicate] = []          # AND tombstoned)
        self._refs: Dict[Q.Predicate, int] = {}     # leaf-occurrence refs
        self._canon: Dict[Q.Predicate, Q.Predicate] = {}   # query memo
        self._synced: "Dict[Q.Predicate, int]" = {}  # canon tree -> mult
        self.version = 0            # bumps on compaction (slot ids moved)
        self.registrations = 0      # new slots ever allocated
        self.retirements = 0        # slots that hit refcount 0
        self.resurrections = 0      # tombstones brought back live
        self.compactions = 0

    def canonical(self, query: Q.Predicate) -> Q.Predicate:
        """Memoized ``Q.canonicalize`` — each distinct query tree is
        canonicalized once per table lifetime, however many epochs
        re-register it."""
        tree = self._canon.get(query)
        if tree is None:
            tree = Q.canonicalize(query)
            self._canon[query] = tree
        return tree

    @property
    def width(self) -> int:
        """Slot-column count (live + tombstoned) — the leaf-matrix width
        of every plan built against this table."""
        return len(self._keys)

    @property
    def n_live(self) -> int:
        return sum(1 for k in self._keys if self._refs.get(k, 0) > 0)

    @property
    def n_tombstones(self) -> int:
        return len(self._keys) - self.n_live

    def is_live(self, slot: int) -> bool:
        return self._refs.get(self._keys[slot], 0) > 0

    def slot_of(self, key: Q.Predicate) -> int:
        return self._slots[key]

    def live_items(self) -> List[Tuple[Q.Predicate, int]]:
        """(canonical key, slot) pairs of live slots, slot-ordered."""
        return [(k, self._slots[k]) for k in self._keys
                if self._refs.get(k, 0) > 0]

    def sync(self, queries: Sequence[Q.Predicate]) -> None:
        """Make the table's refcounts reflect ``queries`` (a multiset).

        The delta-registration path: trees present in both the old and
        new population are untouched; retired trees decrement their
        leaves (tombstoning zeros), new trees allocate/resurrect slots
        first-seen in query order.  May compact (see class docstring) —
        callers build the plan *after* sync so they see the final ids."""
        trees = [self.canonical(q) for q in queries]
        new: Dict[Q.Predicate, int] = {}
        for t in trees:
            new[t] = new.get(t, 0) + 1
        # retired trees first: a slot freed here can be resurrected (not
        # re-allocated) by a new tree registering the same predicate
        for tree, old_mult in self._synced.items():
            drop = old_mult - new.get(tree, 0)
            if drop <= 0:
                continue
            for leaf in Q.leaves(tree):
                key = Q.leaf_key(leaf)
                r = self._refs[key] - drop
                assert r >= 0, f"refcount underflow for {key!r}"
                self._refs[key] = r
                if r == 0:
                    self.retirements += 1
        seen: set = set()
        for tree in trees:
            add = new[tree] - self._synced.get(tree, 0)
            if add <= 0 or tree in seen:
                continue
            seen.add(tree)
            for leaf in Q.leaves(tree):
                key = Q.leaf_key(leaf)
                if key not in self._slots:
                    self._slots[key] = len(self._keys)
                    self._keys.append(key)
                    self._refs[key] = 0
                    self.registrations += 1
                elif self._refs.get(key, 0) == 0:
                    self.resurrections += 1
                self._refs[key] += add
        self._synced = new
        self.maybe_compact()

    def maybe_compact(self) -> bool:
        """Drop tombstoned columns when they exceed ``compact_threshold``
        of the slot space.  Renumbers live slots densely (stable order),
        bumps ``version`` — plans built before a compaction keep working
        (they hold their own baked arrays) but their step signatures no
        longer match newly built plans', which is exactly right: the
        column layout changed."""
        width = len(self._keys)
        dead = [k for k in self._keys if self._refs.get(k, 0) == 0]
        if not dead or len(dead) / max(width, 1) <= self.compact_threshold:
            return False
        live = [k for k in self._keys if self._refs.get(k, 0) > 0]
        self._keys = live
        self._slots = {k: i for i, k in enumerate(live)}
        for k in dead:
            del self._refs[k]
        self.version += 1
        self.compactions += 1
        return True

    def snapshot(self) -> Dict[str, int]:
        return {"width": self.width, "live": self.n_live,
                "tombstones": self.n_tombstones, "version": self.version,
                "registrations": self.registrations,
                "retirements": self.retirements,
                "resurrections": self.resurrections,
                "compactions": self.compactions}

    def __repr__(self) -> str:
        return (f"CanonicalLeafTable(width={self.width}, "
                f"live={self.n_live}, tombstones={self.n_tombstones}, "
                f"version={self.version})")


def _count_bounds(op: Q.Op, value: int, tol: int) -> Tuple[int, int]:
    """EQ/GE/LE with +-tol as one closed interval [lo, hi] over int32."""
    if op == Q.Op.EQ:
        return value - tol, value + tol
    if op == Q.Op.GE:
        return value - tol, _I32_MAX
    return _I32_MIN, value + tol


@dataclasses.dataclass(frozen=True)
class _Level:
    """All And/Or nodes at one tree depth, across every query."""
    node_ids: np.ndarray        # (P,) columns written by this level
    child_idx: np.ndarray       # (K,) columns read (leaf slots or nodes)
    child_neg: np.ndarray       # (K,) bool — NNF literal negation
    incidence: np.ndarray       # (P, K) 0/1 parent-child matrix
    required: np.ndarray        # (P,) n_children for And, 1 for Or


@dataclasses.dataclass
class _Stage:
    """One cost tier of the staged plan (a lowering group of slots)."""
    name: str
    kind: str                   # 'count' | 'spatial' | 'region'
    slots: np.ndarray           # slot columns this stage decides
    cost: float                 # full-batch cost under the build-time
                                # CostModel (reporting / describe); live
                                # decisions re-query the model per rows
    payload: Tuple              # kind-specific baked index arrays
    radius: int = 0             # region dilation radius (cost queries)


class QueryPlan:
    """Compiles N query ASTs into one shared batched evaluation.

    ``evaluate(out) -> (B, N) bool`` is pure and jit-compatible; all index
    arrays and incidence matrices are baked at plan-build time.
    ``build_staged`` wraps the same lowering in the adaptive stage-by-stage
    executor (see module docstring §4).
    """

    def __init__(self, queries: Sequence[Q.Predicate], *, tau: float = 0.2,
                 leaf_table: Optional[CanonicalLeafTable] = None,
                 prev: Optional["QueryPlan"] = None):
        if not queries:
            raise ValueError("QueryPlan needs at least one query")
        self.queries = tuple(queries)
        for q in self.queries:
            if Q.has_temporal(q):
                raise TypeError(
                    f"QueryPlan evaluates frame-level predicates only; "
                    f"temporal operators must be compiled by "
                    f"repro.core.temporal (TemporalProgram strips them "
                    f"and plans their frame-level sub-predicates): {q!r}")
        self.tau = tau
        # delta path: ``prev=`` inherits the previous epoch's table (and
        # through it the canonicalization memo + stable slot ids);
        # ``leaf_table=`` shares a registry-owned table directly.  A
        # standalone plan builds a private table — same code path, and a
        # fresh table's first-seen allocation reproduces the legacy
        # dense slot layout exactly.
        if leaf_table is None and prev is not None:
            leaf_table = prev.leaf_table
        self.leaf_table = (leaf_table if leaf_table is not None
                           else CanonicalLeafTable())

        # ---- pass 1: canonical leaf slots (delta-sync on the table) ----
        table = self.leaf_table
        table.sync(self.queries)
        self.n_total_leaves = sum(
            len(Q.leaves(q)) for q in self.queries)
        # n_unique_leaves stays the LIVE unique count (the sharing-factor
        # denominator); n_slot_cols is the leaf-matrix width — equal on a
        # private table, wider on a shared one carrying tombstones
        self.n_slot_cols = table.width
        live = table.live_items()                   # (key, slot) pairs
        self.n_unique_leaves = len(live)
        self.slot_keys: List[Optional[Q.Predicate]] = \
            [None] * self.n_slot_cols               # None == tombstone
        for key, slot in live:
            self.slot_keys[slot] = key
        self.live_slots = np.array([slot for _, slot in live], np.int64) \
            if live else np.zeros(0, np.int64)

        # ---- distinct-tree dedup: compile each canonical query tree
        # once.  Steps, propagation state, and the incidence program all
        # live in *distinct* space (D columns); per-qid answers are an
        # O(1) gather through ``dup_map`` OUTSIDE the jitted steps — so
        # registering another copy of an already-resident template
        # changes neither the program nor any step signature.  Distinct
        # order is canonical (sorted by repr), not first-seen: retiring
        # one of several duplicates then never perturbs the program.
        trees = [table.canonical(q) for q in self.queries]
        distinct = sorted(set(trees), key=repr)
        tree_to_di = {t: i for i, t in enumerate(distinct)}
        self.dup_map = np.array([tree_to_di[t] for t in trees], np.int64)
        self.n_distinct = len(distinct)
        self._distinct_trees = tuple(distinct)

        # query <-> slot incidence, the population weight behind adaptive
        # ordering; the stage-skip test uses the distinct-space variant
        self.query_slot_incidence = np.zeros(
            (len(self.queries), self.n_slot_cols), bool)
        for qi, tree in enumerate(trees):
            for leaf in Q.leaves(tree):
                self.query_slot_incidence[qi, table.slot_of(
                    Q.leaf_key(leaf))] = True
        self.distinct_slot_incidence = np.zeros(
            (self.n_distinct, self.n_slot_cols), bool)
        for di, tree in enumerate(distinct):
            for leaf in Q.leaves(tree):
                self.distinct_slot_incidence[di, table.slot_of(
                    Q.leaf_key(leaf))] = True

        # ---- lower LIVE slots by kind into grouped numpy index tables
        # (tombstoned columns are never evaluated, never read) ----
        cnt: List[Tuple[int, int, int, int]] = []    # (slot, cls|C, lo, hi)
        spa: List[Tuple[int, int, int, bool, int]] = []  # slot,a,b,row?,r
        reg: Dict[int, List[Tuple[int, int, Tuple, int]]] = defaultdict(list)
        self._needs_grid = False
        for leaf, slot in live:
            if isinstance(leaf, Q.Count):
                lo, hi = _count_bounds(leaf.op, leaf.value, leaf.tolerance)
                cnt.append((slot, -1, lo, hi))
            elif isinstance(leaf, Q.ClassCount):
                lo, hi = _count_bounds(leaf.op, leaf.value, leaf.tolerance)
                cnt.append((slot, leaf.cls, lo, hi))
            elif isinstance(leaf, Q.Spatial):
                self._needs_grid = True
                spa.append((slot, leaf.cls_a, leaf.cls_b,
                            leaf.rel == Q.Rel.ABOVE, leaf.radius))
            elif isinstance(leaf, Q.Region):
                self._needs_grid = True
                reg[leaf.radius].append((slot, leaf.cls, leaf.rect,
                                         leaf.min_count))
            else:
                raise TypeError(f"not a leaf predicate: {leaf!r}")

        self._cnt = None
        if cnt:
            a = np.array(cnt, np.int64)
            self._cnt = (a[:, 0], a[:, 1].astype(np.int32),
                         a[:, 2].astype(np.int32), a[:, 3].astype(np.int32))
        self._spa = None
        if spa:
            self._spa = (np.array([s[0] for s in spa]),
                         np.array([s[1] for s in spa], np.int32),
                         np.array([s[2] for s in spa], np.int32),
                         np.array([s[3] for s in spa], bool),
                         np.array([s[4] for s in spa], np.int32))
        self._reg: List[Tuple[int, np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]] = []
        for radius, items in sorted(reg.items()):
            slots = np.array([i[0] for i in items])
            cls = np.array([i[1] for i in items], np.int32)
            rects = np.array([i[2] for i in items], np.int32)    # (n, 4)
            minc = np.array([i[3] for i in items], np.float32)
            self._reg.append((radius, slots, cls, rects, minc))

        # ---- pass 2: levelized node program over distinct NNF trees ----
        L = self.n_slot_cols
        internal: List[Tuple[bool, List[Tuple[int, bool]]]] = []
        node_level: Dict[int, int] = {}
        memo: Dict[Q.Predicate, Tuple[int, bool, int]] = {}

        def compile_node(node) -> Tuple[int, bool, int]:
            """-> (column, negated, level); columns 0..L-1 are leaf slots.
            Memoized on the (hashable, canonical) subtree, so a
            connective shared across distinct queries compiles to one
            internal column."""
            hit = memo.get(node)
            if hit is not None:
                return hit
            if isinstance(node, Q.Not):          # NNF: term is a leaf
                col, neg, lvl = compile_node(node.term)
                res = (col, not neg, lvl)
            elif isinstance(node, (Q.And, Q.Or)):
                if not node.terms:
                    raise ValueError(f"empty connective: {node!r}")
                ch = [compile_node(t) for t in node.terms]
                lvl = 1 + max(c[2] for c in ch)
                col = L + len(internal)
                internal.append((isinstance(node, Q.And),
                                 [(c[0], c[1]) for c in ch]))
                node_level[col] = lvl
                res = (col, False, lvl)
            else:
                res = (table.slot_of(Q.leaf_key(node)), False, 0)
            memo[node] = res
            return res

        roots = [compile_node(Q.to_nnf(t)) for t in distinct]
        self._roots = np.array([r[0] for r in roots])       # (D,)
        self._root_neg = np.array([r[1] for r in roots], bool)
        self.n_internal = len(internal)

        by_level: Dict[int, List[int]] = defaultdict(list)
        for col, lvl in node_level.items():
            by_level[lvl].append(col)
        self._levels: List[_Level] = []
        for lvl in sorted(by_level):
            cols = sorted(by_level[lvl])
            child_idx: List[int] = []
            child_neg: List[bool] = []
            spans: List[Tuple[int, int]] = []
            required = []
            for col in cols:
                is_and, children = internal[col - L]
                spans.append((len(child_idx), len(children)))
                child_idx.extend(c for c, _ in children)
                child_neg.extend(n for _, n in children)
                required.append(len(children) if is_and else 1)
            inc = np.zeros((len(cols), len(child_idx)), np.float32)
            for p, (start, k) in enumerate(spans):
                inc[p, start:start + k] = 1.0
            self._levels.append(_Level(
                node_ids=np.array(cols),
                child_idx=np.array(child_idx),
                child_neg=np.array(child_neg, bool),
                incidence=inc,
                required=np.array(required, np.float32)))

        # content signature of everything a compiled step bakes from the
        # PLAN side (the stage payloads get their own signatures): the
        # incidence program, distinct roots, leaf-matrix width, tau.
        # Duplicate registrations of a resident template change none of
        # it, so a rebuilt plan with an unchanged signature hits every
        # cached step of the previous epoch verbatim.
        sig_parts: List = [L, self.n_internal, self.n_distinct, self.tau,
                           self._roots, self._root_neg]
        for lev in self._levels:
            sig_parts.extend([lev.node_ids, lev.child_idx, lev.child_neg,
                              lev.incidence, lev.required])
        self.plan_sig = content_digest(*sig_parts)

    # -- grouped leaf evaluation ------------------------------------------

    def _count_values(self, out: FilterOutputs,
                      payload: Optional[Tuple] = None) -> jax.Array:
        """(B, k) bool for the count-gather group (CF/CCF interval tests)."""
        _, cls, lo, hi = payload if payload is not None else self._cnt
        counts = out.count_pred()                          # (B, C) int32
        ext = jnp.concatenate([counts, counts.sum(-1, keepdims=True)],
                              axis=1)
        x = ext[:, cls]                # cls == -1 wraps to the total col
        return (x >= jnp.asarray(lo)) & (x <= jnp.asarray(hi))

    def _spatial_values(self, out: FilterOutputs,
                        payload: Optional[Tuple] = None,
                        class_slice: Optional[Tuple] = None,
                        rows: Optional[jax.Array] = None,
                        body: str = "rows") -> jax.Array:
        """(B, k) bool for the spatial tier from the fused (C', 5) stats.

        ``class_slice=(classes, a_idx, b_idx)`` gathers only the grid
        planes the tier's leaves reference before the reduction
        (stage-sliced evaluation) — bit-identical, per-class stats are
        independent.  ``rows`` restricts the reduction to a gathered row
        subset (row-level short-circuiting); ``body`` picks which of the
        two bit-identical bodies reduces it: ``"rows"`` rides the
        scalar-prefetched row-gather kernel, ``"full"`` gathers the rows
        first and runs the full-batch reduction over the (R, g, g, C')
        subgrid — cheaper above the calibration's rows crossover
        (``CostModel.spatial_body`` is the chooser).  Either way the
        result is (R, k)."""
        _, a, b, use_row, radius = payload if payload is not None \
            else self._spa
        g = out.grid.shape[1]
        grid = out.grid
        if class_slice is not None and \
                len(class_slice[0]) < out.grid.shape[-1]:
            classes, a, b = class_slice
            grid = grid[..., jnp.asarray(classes)]
        if rows is not None:
            from repro.kernels import ops as kops
            if body == "full":
                stats = kops.spatial_stats_inline(grid[rows], self.tau)
            else:
                stats = kops.spatial_stats_rows_inline(grid, rows, self.tau)
        elif grid is out.grid:
            stats = out.spatial_stats(self.tau)
        else:
            from repro.kernels import ops as kops
            stats = kops.spatial_stats_inline(grid, self.tau)
        return SP.eval_spatial_leaves(
            stats, jnp.asarray(a), jnp.asarray(b), jnp.asarray(use_row),
            jnp.asarray(radius), grid=g)

    def _region_sat_values(self, occ: jax.Array, cls: np.ndarray,
                           rects: np.ndarray, minc: np.ndarray) -> jax.Array:
        """(B, k) bool rectangle-count tests on an (already dilated)
        occupancy map, via one summed-area table.

        The prefix sums run as (g, g) triangular matmuls — exact for
        0/1 cell sums and far cheaper than XLA's cumsum lowering
        on CPU (~5 ms vs ~0.1 ms on a (64, 16, 16, 8) grid)."""
        g = occ.shape[1]
        tri = jnp.tril(jnp.ones((g, g), jnp.float32))
        s = jnp.einsum("ij,bjkc->bikc", tri, occ.astype(jnp.float32))
        s = jnp.einsum("kl,bilc->bikc", tri, s)
        sat = jnp.pad(s, ((0, 0), (1, 0), (1, 0), (0, 0)))
        r0, c0, r1, c1 = (rects[:, k] for k in range(4))
        inside = (sat[:, r1, c1] - sat[:, r0, c1]
                  - sat[:, r1, c0] + sat[:, r0, c0])       # (B, n, C)
        return inside[:, np.arange(len(cls)), cls] >= jnp.asarray(minc)

    # -- leaf matrix ------------------------------------------------------

    def leaf_values(self, out: FilterOutputs) -> jax.Array:
        """(B, L_unique) bool — each deduped leaf evaluated exactly once.

        Group results are concatenated and reordered into slot order with
        ONE permutation gather at the end (scatter-free assembly)."""
        if self._needs_grid and out.grid is None:
            raise ValueError("plan has Spatial/Region leaves but the filter "
                             "head emits no grid (OD-COF)")
        parts: List[jax.Array] = []
        cols: List[np.ndarray] = []
        if self._cnt is not None:
            parts.append(self._count_values(out))
            cols.append(self._cnt[0])
        if self._spa is not None:
            parts.append(self._spatial_values(out))
            cols.append(self._spa[0])
        if self._reg:
            from repro.core import cam as CAM
            occ = out.occupancy(self.tau)        # ONE threshold pass, bool
            prev_radius = 0
            for radius, slots, cls, rects, minc in self._reg:
                if radius > prev_radius:         # incremental dilation:
                    occ = CAM.dilate_manhattan(  # radius r from radius r-1
                        occ, radius - prev_radius)
                    prev_radius = radius
                parts.append(self._region_sat_values(occ, cls, rects, minc))
                cols.append(slots)
        order = np.concatenate(cols)
        inv = np.zeros(self.n_slot_cols, np.int64)
        inv[order] = np.arange(order.size)     # tombstoned columns keep
        return jnp.concatenate(parts, axis=1)[:, inv]   # 0 — never read

    # -- full evaluation --------------------------------------------------

    def _assemble(self, leaf: jax.Array) -> jax.Array:
        """(B, L) bool leaf matrix -> (B, N) root masks via the levelized
        incidence program (distinct columns expanded through dup_map)."""
        leaf = leaf.astype(jnp.float32)
        B = leaf.shape[0]
        vals = jnp.concatenate(
            [leaf, jnp.zeros((B, self.n_internal), jnp.float32)], axis=1)
        for lev in self._levels:
            child = vals[:, lev.child_idx]
            child = jnp.where(jnp.asarray(lev.child_neg), 1.0 - child, child)
            sums = jnp.einsum("bk,pk->bp", child,
                              jnp.asarray(lev.incidence))
            newv = (sums >= jnp.asarray(lev.required) - 0.5)
            vals = vals.at[:, lev.node_ids].set(newv.astype(jnp.float32))
        masks = (vals[:, self._roots] > 0.5) ^ jnp.asarray(self._root_neg)
        return masks[:, self.dup_map]                    # (B, D) -> (B, N)

    def evaluate(self, out: FilterOutputs) -> jax.Array:
        """(B, N) per-query candidate masks from one shared leaf pass."""
        return self._assemble(self.leaf_values(out))

    def evaluate_with_counts(self, out: FilterOutputs
                             ) -> Tuple[jax.Array, jax.Array]:
        """``(masks (B, N), per-LIVE-slot pass counts)`` in one program —
        the exhaustive path of the adaptive cascade uses this so the
        population statistics keep learning while staging is parked.
        Counts align with ``live_slot_keys`` (tombstoned columns are
        never evaluated and feed no ledger)."""
        leaf = self.leaf_values(out)
        return self._assemble(leaf), leaf[:, self.live_slots].sum(0)

    @property
    def live_slot_keys(self) -> List[Q.Predicate]:
        """Canonical keys of live slots, aligned with
        ``evaluate_with_counts``'s count vector."""
        return [self.slot_keys[s] for s in self.live_slots]

    # -- three-valued propagation (staged execution) ----------------------

    def propagate_bounds(self, leaf_vals: jax.Array,
                         known: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Partial-knowledge evaluation of every query.

        ``leaf_vals``: (B, L) bool with arbitrary values at unknown slots;
        ``known``: (L,) bool.  Returns ``(value, decided)``, both (B, N)
        bool: the levelized program runs twice — unknown literals forced
        to 0 (lower bound) then to 1 (upper bound).  And/Or gates are
        monotone in their children, so the two runs bracket the true
        value exactly and agreement means *decided* (``value`` is then
        the exact answer, bit-identical to ``evaluate``).

        The program itself runs over *distinct* canonical query columns
        (the staged steps stay in that space — ``_propagate_distinct``);
        this public entry point expands to per-qid columns through
        ``dup_map``, preserving the (B, N) contract the cost-model
        calibration and external callers rely on."""
        lo, dec = self._propagate_distinct(leaf_vals, known)
        return lo[:, self.dup_map], dec[:, self.dup_map]

    def _propagate_distinct(self, leaf_vals: jax.Array,
                            known: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
        """``propagate_bounds`` in distinct-query space: (B, D) value and
        decided columns, one per distinct canonical tree."""
        leaf = leaf_vals.astype(jnp.float32)
        B = leaf.shape[0]
        known_ext = jnp.concatenate(
            [known, jnp.ones((self.n_internal,), bool)])

        def run(fill: float) -> jax.Array:
            vals = jnp.concatenate(
                [leaf, jnp.zeros((B, self.n_internal), jnp.float32)], axis=1)
            for lev in self._levels:
                child = vals[:, lev.child_idx]
                child = jnp.where(jnp.asarray(lev.child_neg),
                                  1.0 - child, child)
                child = jnp.where(known_ext[lev.child_idx], child,
                                  jnp.float32(fill))
                sums = jnp.einsum("bk,pk->bp", child,
                                  jnp.asarray(lev.incidence))
                newv = (sums >= jnp.asarray(lev.required) - 0.5)
                vals = vals.at[:, lev.node_ids].set(newv.astype(jnp.float32))
            root = vals[:, self._roots] > 0.5
            return jnp.where(known_ext[self._roots], root, fill > 0.5)

        lo_raw = run(0.0)
        hi_raw = run(1.0)
        # a negated root literal (NNF Not over a bare-leaf query) swaps
        # the bounds: lower(~x) = ~upper(x)
        neg = jnp.asarray(self._root_neg)
        lo = jnp.where(neg, ~hi_raw, lo_raw)
        hi = jnp.where(neg, ~lo_raw, hi_raw)
        return lo, lo == hi

    # -- staging ----------------------------------------------------------

    def stage_descriptors(self, cost_model: Optional[CM.CostModel] = None
                          ) -> List[_Stage]:
        """The plan's cost tiers, unordered (lowering-group granularity).
        ``cost`` carries the model's full-batch stage cost (default: the
        static fallback model)."""
        cm = cost_model if cost_model is not None else CM.static_cost_model()
        stages: List[_Stage] = []
        if self._cnt is not None:
            stages.append(_Stage("counts", "count", self._cnt[0],
                                 cm.stage_rank_cost("count"), self._cnt))
        if self._spa is not None:
            stages.append(_Stage("spatial", "spatial", self._spa[0],
                                 cm.stage_rank_cost("spatial"), self._spa))
        for radius, slots, cls, rects, minc in self._reg:
            stages.append(_Stage(f"region@r{radius}", "region", slots,
                                 cm.stage_rank_cost("region", radius=radius),
                                 (radius, slots, cls, rects, minc),
                                 radius=radius))
        return stages

    def exhaustive_cost_model(self, cost_model: Optional[CM.CostModel] = None,
                              *, batch: Optional[float] = None) -> float:
        """Cost of one ``evaluate`` call under ``cost_model`` (default:
        the static fallback).  Differs from the sum of staged stage
        costs: the exhaustive program thresholds the grid once and
        dilates incrementally radius-to-radius, while each staged region
        stage dilates from scratch (it must be skippable and
        reorderable) — the mode-switch comparison in the adaptive
        cascade has to use THIS as the exhaustive baseline or staging
        looks better than it is on multi-radius plans."""
        cm = cost_model if cost_model is not None else CM.static_cost_model()
        return cm.exhaustive_cost(
            has_counts=self._cnt is not None,
            has_spatial=self._spa is not None,
            radii=[radius for radius, *_ in self._reg],
            batch=batch if batch is not None else CM.REF_BATCH)

    def build_staged(self, stats=None, *,
                     order: Optional[Sequence[int]] = None,
                     min_bucket: Optional[int] = None,
                     cost_model: Optional[CM.CostModel] = None,
                     spatial_body: str = "auto",
                     step_cache: Optional[StepCache] = None
                     ) -> "StagedQueryPlan":
        """Adaptive stage-by-stage executor over this plan's lowering.
        ``step_cache`` shares a registry-owned compiled-step cache across
        epoch rebuilds (default: a fresh private cache)."""
        return StagedQueryPlan(self, stats, order=order,
                               min_bucket=min_bucket, cost_model=cost_model,
                               spatial_body=spatial_body,
                               step_cache=step_cache)

    @property
    def sharing_factor(self) -> float:
        """total leaves across queries / unique evaluated leaves (>= 1)."""
        return self.n_total_leaves / max(self.n_unique_leaves, 1)


# --------------------------------------------------------------------------
# Staged adaptive execution
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StageReport:
    """What one ``StagedQueryPlan.evaluate`` call actually did."""
    order: List[str] = dataclasses.field(default_factory=list)
    ran: List[str] = dataclasses.field(default_factory=list)
    skipped: List[str] = dataclasses.field(default_factory=list)
    undecided_after: List[int] = dataclasses.field(default_factory=list)
    rows_evaluated: List[int] = dataclasses.field(default_factory=list)
    # rows each executed stage actually processed: the compacted bucket
    # size, padding included (padded rows are real work — the same honest
    # accounting as ``oracle_frames_evaluated``); batch for full steps
    undecided_rows_in: List[int] = dataclasses.field(default_factory=list)
    # true undecided-row count when the stage ran (<= its bucket)
    bodies: List[str] = dataclasses.field(default_factory=list)
    # per executed stage, which body evaluated it: "batch" (uncompacted
    # full-batch step), "rows" (compacted; spatial via the row-gather
    # kernel, count/SAT via direct row indexing), or "full" (compacted
    # spatial stage that chose the full-batch reduction over the
    # gathered subgrid — the crossover-aware choice)
    steps_compiled: int = 0     # jitted steps newly traced by this batch —
                                # its wall time includes compilation, so
                                # wall-clock consumers (the calibration
                                # drift monitor) must skip it
    batch: int = 0              # B of the evaluated batch
    cost_run: float = 0.0       # cost-model cost of executed stages at the
                                # rows each actually evaluated
    cost_total: float = 0.0     # cost-model cost of the EXHAUSTIVE plan
                                # (shared threshold, incremental dilation —
                                # less than the sum of staged stage costs)
    skipped_presumed: List[str] = dataclasses.field(default_factory=list)
    # subset of ``skipped`` that only became skippable because the caller
    # presumed some query columns decided (the temporal tier's
    # window-outcome short-circuit) — the stage still has slots in a
    # presumed column and in no other undecided column
    cost_presumed_saved: float = 0.0
    # cost-model price of those stages at the full batch (a modelled
    # upper bound on the work the temporal short-circuit avoided: the
    # counterfactual row traffic of a never-evaluated column is unknown)

    @property
    def stages_run(self) -> int:
        return len(self.ran)


class StagedQueryPlan:
    """Stage-by-stage evaluation of a ``QueryPlan`` with short-circuiting.

    Evaluation walks the cost tiers in ``self.order`` (population-level
    cheapest/most-decisive first, from a ``SlotStats`` store); after each
    tier, three-valued propagation (``QueryPlan.propagate_bounds``) marks
    every (frame, query) cell decided-true / decided-false / undecided.
    The walk stops once every query column is decided, and skips any tier
    none of whose slots appears in a still-undecided query — decidedness
    is monotone in the known-slot set, so skipped tiers can never affect
    the result, and the returned masks are bit-identical to
    ``QueryPlan.evaluate``.

    Between tiers the executor additionally compacts at ROW granularity:
    frames whose every query column is decided are dropped from the next
    stage's evaluation.  The undecided row indices are bucketed host-side
    into power-of-two sizes (``cascade.compact_indices``, padding by
    repeating the last undecided row so duplicate scatters are benign) and
    the stage body evaluates only the gathered rows — the spatial tier via
    the scalar-prefetched row kernel, count/SAT tiers via direct row
    indexing — then scatters leaf values, bounds, and decidedness back
    into the persistent full-batch state.  Correctness rests on the same
    monotonicity that makes tier skipping sound: a decided (frame, query)
    cell is invariant to every still-unknown slot, so excluding that frame
    from later stages (or re-propagating it with arbitrary values at
    slots it never evaluated) cannot change its answer.

    Each executed tier is ONE jitted *step*: stage evaluation, scatter
    into the leaf matrix, both propagation passes, the per-column and
    per-row undecided reductions, and the per-slot pass-count
    accumulation, fused into a single fixed-shape program with the
    known-slot mask baked as a constant (steps are cached per (stage,
    set-of-stages-already-run, bucket), and real traffic revisits a
    handful of such prefixes x a couple of bucket sizes).  The only host
    round-trip per executed tier is the tiny (N + B,) undecided fetch
    that drives both the short-circuit and the next stage's compaction.
    Per-slot pass counts stay on device until ``flush_stats`` pulls them
    in one deferred transfer; only FULL-BATCH stage evaluations feed the
    per-slot store (a compacted stage sees its slots conditioned on the
    row being undecided — not the unconditional frame-level selectivity
    the shared ledger holds), while per-stage row traffic always feeds
    the ``SlotStats`` stage ledger for ``predicted_batch_cost``.

    A compacted *spatial* stage has two bit-identical evaluation bodies
    with different cost structure: the scalar-prefetched row-gather
    kernel (no fixed overhead, higher per-row cost) and the full-batch
    reduction over the gathered subgrid (fixed overhead, lower per-row
    cost).  The executor asks the cost model which is cheaper at each
    bucket's row count (``CostModel.spatial_body`` — the calibration's
    two coefficient sets cross at ``spatial_crossover_rows``) and keeps
    BOTH variants jitted side by side in the step cache, so the choice
    flipping between bucket sizes never re-traces.  ``spatial_body=``
    forces one body ("rows"/"full", default "auto") — the property
    tests pin that all three agree bit-for-bit; under the static model
    "auto" always resolves to the row kernel, the pre-crossover
    executor's hard-wired choice.

    ``min_bucket`` floors the bucket size (tiny buckets would multiply
    compiled variants for little win).  When not given explicitly it is
    *derived* from the cost model (``CostModel.derived_min_bucket``):
    the largest power of two whose worst-case padding cost stays within
    the measured per-stage step overhead — the static fallback derives
    the historical hand-set default 8, so disabling calibration
    reproduces the legacy floor exactly.  An explicit ``min_bucket=``
    always wins (knob precedence in docs/tuning.md).  Setting it >= B
    disables row compaction entirely and reproduces the tier-granular
    executor.

    ``cost_model`` (repro.core.costmodel) prices everything: ordering
    scores, ``StageReport.cost_run``/``cost_total``, the per-bucket
    spatial-body choice, the derived bucket floor, and
    ``predicted_batch_cost`` all query the ONE model instance, so the
    comparisons stay unit-consistent whether the model is the measured
    per-backend calibration or the static fallback (the default when
    none is given — build with ``costmodel.default_cost_model()`` to
    pick up a calibration from disk, as ``MultiQueryCascade`` does).
    """

    def __init__(self, plan: QueryPlan, stats=None, *,
                 order: Optional[Sequence[int]] = None,
                 min_bucket: Optional[int] = None,
                 cost_model: Optional[CM.CostModel] = None,
                 spatial_body: str = "auto",
                 step_cache: Optional[StepCache] = None):
        self.plan = plan
        self.cost_model = (cost_model if cost_model is not None
                           else CM.static_cost_model())
        # knob precedence (docs/tuning.md): an explicit min_bucket wins;
        # None derives the floor from the model's calibration (the
        # static fallback derives the historical default 8)
        self.min_bucket_derived = min_bucket is None
        if min_bucket is None:
            min_bucket = self.cost_model.derived_min_bucket()
        if min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got {min_bucket}")
        self.min_bucket = min_bucket
        if spatial_body not in ("auto", "rows", "full"):
            raise ValueError(f"spatial_body must be 'auto', 'rows' or "
                             f"'full', got {spatial_body!r}")
        self.spatial_body = spatial_body
        self._last_batch: Optional[int] = None
        self.stages = plan.stage_descriptors(self.cost_model)
        # (D, n_stages) — does distinct query column d own a slot in
        # stage s?  Steps and the skip test run in distinct space.
        self._uses_stage = np.stack(
            [plan.distinct_slot_incidence[:, st.slots].any(1)
             for st in self.stages], axis=1)
        # population weight per slot: how many registered queries read it
        # (qid space on purpose — duplicate registrations of a template
        # are real demand and must weight the ordering benefit)
        self._slot_weight = plan.query_slot_incidence.sum(0).astype(float)
        self.order, self._perms = self._staging_order(stats)
        self._forced_order = order is not None
        if order is not None:
            if sorted(order) != list(range(len(self.stages))):
                raise ValueError(f"order must permute stages "
                                 f"0..{len(self.stages) - 1}, got {order!r}")
            self.order = list(order)
        # compiled-step cache: signature-keyed (see repro.core.stepcache),
        # so it can be SHARED across plan instances — a registry-owned
        # cache survives epoch rebuilds and a rebuilt plan whose stage
        # signatures didn't move reuses every compiled step verbatim.
        # Without one, a private cache reproduces the per-plan behaviour.
        self.step_cache = (step_cache if step_cache is not None
                           else StepCache())
        self._stage_sigs = [self._stage_sig(si)
                            for si in range(len(self.stages))]
        self._prefix_sigs: Dict[frozenset, str] = {}
        self._wrap_refs: List = []  # keep unsigned shard_wraps alive so
        #                             their id()-based keys stay unique
        self._trace_count = 0       # lifetime traces paid by THIS plan
        # the owning fleet engine's counters (host fetches, steps built)
        self.counters: Optional[tracing.EngineCounters] = None
        self.last_report: Optional[StageReport] = None
        self._pending: Optional[Tuple[
            List[Tuple[np.ndarray, jax.Array, int]],
            List[Tuple[str, int, int, Optional[int], Optional[int]]]]] = None

    @property
    def step_cache_max(self) -> int:
        """Capacity of the (possibly shared) compiled-step cache."""
        return self.step_cache.capacity

    # -- step signatures --------------------------------------------------

    def _stage_sig(self, si: int) -> str:
        """Digest of everything stage ``si``'s body bakes: kind, the
        slot-permuted payload arrays, and the slot columns it scatters
        into.  Content-addressed — two epochs' plans over the same leaf
        table produce equal signatures for a stage whose leaf content
        (and within-stage order) didn't change, whatever their stage
        *indices* are."""
        st = self.stages[si]
        perm = self._perms[si]
        parts: List = [st.kind, st.radius]
        for p in st.payload:
            if isinstance(p, np.ndarray):
                parts.append(p[perm])
            else:
                parts.append(p)                  # region radius scalar
        parts.append(st.slots[perm])
        return content_digest(*parts)

    def _prefix_sig(self, ran: frozenset) -> str:
        """Digest of the SET of slot columns already known when a step
        runs.  Steps bake ``known`` as a slot-set union, so the
        signature is order-free: two stage orders reaching the same
        known-set share one compiled step, and a re-permutation inside
        an earlier stage never invalidates later stages' steps."""
        sig = self._prefix_sigs.get(ran)
        if sig is None:
            slots = np.zeros(0, np.int64) if not ran else np.unique(
                np.concatenate([self.stages[sj].slots for sj in ran]))
            sig = content_digest(slots)
            self._prefix_sigs[ran] = sig
        return sig

    # -- ordering ---------------------------------------------------------

    def _slot_rates(self, stats) -> np.ndarray:
        """(L,) prior-smoothed pass rate per slot column, quantized so a
        stable order does not flap (and re-jit) on statistical noise.
        Tombstoned columns (no canonical key) sit at the neutral prior —
        they appear in no stage, so the value is never consulted."""
        rates = np.full(self.plan.n_slot_cols, 0.5)
        if stats is None or self.plan.live_slots.size == 0:
            return rates
        rates[self.plan.live_slots] = stats.pass_rates(
            self.plan.live_slot_keys, canonical=True)
        return np.round(rates, 3)

    def _staging_order(self, stats
                       ) -> Tuple[List[int], Dict[int, np.ndarray]]:
        """Greedy sequential (position-aware) stage-order search; slots
        within a stage most-selective first.

        Each position is filled with the remaining stage minimizing
        cost-per-expected-decision, where the cost side is the
        ``CostModel``'s price for the rows the already-placed prefix is
        predicted to leave undecided (``SlotStats.stage_survival`` —
        observed survivals are conditioned on the prefix that ran before
        the stage, so consuming them prefix-by-prefix is the one sound
        direction; a one-shot global sort on them would let a
        historically-last tier look free).  The *benefit* aggregates
        over the registered population: sum over the stage's slots of
        (queries referencing the slot) x (1 - pass rate) — a cheap stage
        whose slots fail often for many queries places early, the
        classic cascade rule lifted from one query's conjuncts to the
        whole query set.

        Under the static cost model stage costs are proportional to
        rows, the predicted row count multiplies every candidate at a
        given position equally, and the greedy search reduces exactly to
        the legacy ``sorted(cost / benefit)`` order (regression-pinned
        in tests/test_costmodel.py) — measured models with fixed
        per-stage overheads are where position changes the ranking."""
        rates = self._slot_rates(stats)
        cm = self.cost_model
        B = float(self._last_batch or CM.REF_BATCH)
        n = len(self.stages)
        benefit = [float(np.sum(self._slot_weight[st.slots]
                                * (1.0 - rates[st.slots])))
                   for st in self.stages]
        # quantized like the rates, so the order does not flap on noise
        survival = [round(stats.stage_survival(st.name), 3)
                    if stats is not None else 1.0 for st in self.stages]
        order: List[int] = []
        remaining = list(range(n))
        frac = 1.0
        while remaining:
            rows = max(frac, 1.0 / B) * B        # at least one row reaches
            best = min(remaining, key=lambda si: (
                cm.stage_cost(self.stages[si].kind, rows=rows, batch=B,
                              radius=self.stages[si].radius)
                / (benefit[si] + 1e-3), si))
            remaining.remove(best)
            order.append(best)
            frac *= survival[best]
        perms = {si: np.argsort(rates[st.slots], kind="stable")
                 for si, st in enumerate(self.stages)}
        return order, perms

    def restage(self, stats) -> bool:
        """Re-sort stages/slots from the population stats.  Returns True
        when anything changed.  Nothing is ever *dropped* from the step
        cache here: step identity is content-signed (stage signature +
        known-slot-set prefix), so a stage whose within-stage slot order
        moved simply starts producing a new signature and re-jits
        lazily, a pure stage re-ordering keeps hitting every compiled
        step, and a permutation that flips back re-hits the retained
        old-signature entries instead of paying a fresh trace (rate
        noise oscillating across the quantization boundary used to
        re-trace per flip — the per-stage-index invalidation this
        replaces also wiped steps whose leaf content never changed).
        An explicit ``order=`` given at construction is sticky: restage
        only refreshes the within-stage slot permutations, never the
        forced stage order."""
        order, perms = self._staging_order(stats)
        if self._forced_order:
            order = self.order
        changed = order != self.order
        for si in range(len(self.stages)):
            if not np.array_equal(perms[si], self._perms[si]):
                self._perms[si] = perms[si]
                self._stage_sigs[si] = self._stage_sig(si)
                changed = True
        self.order = order
        return changed

    # -- stage compilation ------------------------------------------------

    def _stage_body(self, si: int) -> Callable:
        """``(out, rows=None) -> (B|R, k) bool`` for one stage,
        slot-permuted (unjitted).  ``rows`` restricts evaluation to a
        gathered row subset (row-level short-circuiting)."""
        plan = self.plan
        st = self.stages[si]
        perm = self._perms[si]
        if st.kind == "count":
            slots, cls, lo, hi = st.payload
            payload = (slots[perm], cls[perm], lo[perm], hi[perm])

            def body(out, rows=None, payload=payload):
                if rows is not None:
                    out = FilterOutputs(counts=out.counts[rows])
                return plan._count_values(out, payload)

            return body
        if st.kind == "spatial":
            slots, a, b, use_row, radius = st.payload
            payload = (slots[perm], a[perm], b[perm], use_row[perm],
                       radius[perm])
            classes, a_idx, b_idx = SP.stage_class_slice(payload[1],
                                                         payload[2])
            cs = (classes, a_idx, b_idx)
            return lambda out, rows=None, body="rows": plan._spatial_values(
                out, payload, class_slice=cs, rows=rows, body=body)
        from repro.core import cam as CAM
        radius, slots, cls, rects, minc = st.payload
        cls, rects, minc = cls[perm], rects[perm], minc[perm]

        def body(out, rows=None, radius=radius, cls=cls, rects=rects,
                 minc=minc):
            grid = out.grid if rows is None else out.grid[rows]
            occ = CAM.threshold_map(grid, plan.tau, logits=False)
            if radius:              # boolean dilation composes exactly, so
                occ = CAM.dilate_manhattan(occ, radius)     # from-scratch
            return plan._region_sat_values(occ, cls, rects, minc)

        return body

    def _stage_slots(self, si: int) -> np.ndarray:
        return self.stages[si].slots[self._perms[si]]

    def _body_for(self, si: int, bucket: Optional[int]) -> str:
        """Which body evaluates stage ``si`` at this bucket (the
        ``StageReport.bodies`` vocabulary).  Only a *compacted spatial*
        stage has a real choice: forced by ``spatial_body=`` when not
        "auto" (the property tests pin bit-identity of both), otherwise
        the cost model picks the cheaper of its two coefficient sets at
        the bucket's row count — the static model always answers "rows",
        reproducing the pre-crossover executor exactly."""
        if bucket is None:
            return "batch"
        if self.stages[si].kind != "spatial":
            return "rows"
        if self.spatial_body != "auto":
            return self.spatial_body
        return self.cost_model.spatial_body(rows=bucket)

    def _get_step(self, si: int, ran: frozenset, bucket: Optional[int],
                  body: str = "batch") -> Callable:
        """Fused jitted step for stage ``si`` given the set of stages that
        already ran: eval + scatter + both propagation passes + undecided
        reductions + pass counts, one program.  The known-slot mask is a
        trace-time constant, so the propagation's unknown-literal selects
        fold away.

        ``bucket=None`` is the full-batch step (every row still
        undecided).  With a bucket, the step takes a padded (bucket,)
        row-index vector plus the real survivor count and evaluates /
        propagates only the gathered rows, scattering results back into
        the persistent (B, ...) state — decided rows are invariant to the
        slots they never evaluated, so the scatter-back is exact.
        ``body`` (from ``_body_for``) selects the compacted spatial
        stage's evaluation body and is part of the cache key: both
        variants stay jitted side by side, so the crossover decision
        flipping between bucket sizes never re-traces.

        Keys are content signatures (plan program + stage payload +
        known-slot set), never stage indices or object identity, so a
        shared registry-owned cache serves rebuilt plans across epochs —
        and can never serve a step whose baked content changed."""
        key = ("step", self.plan.plan_sig, self._stage_sigs[si],
               self._prefix_sig(ran), bucket, body)
        step = self.step_cache.get(key)
        if step is not None:
            return step
        plan = self.plan
        stage_body = self._stage_body(si)
        slots = self._stage_slots(si)
        spatial = self.stages[si].kind == "spatial"
        known = np.zeros(plan.n_slot_cols, bool)
        for sj in ran:
            known[self.stages[sj].slots] = True
        known[slots] = True

        if bucket is None:
            # full-batch step: every row is (re)evaluated and the bounds
            # derive from leaf_vals alone, so no prior value/decided
            # state is threaded in.  ``presumed`` is a traced (D,) bool
            # mask of distinct query columns the caller already decided
            # (temporal window short-circuit): it joins the undecided
            # reductions only — the raw decided state stays
            # propagation-derived — so presumption changing between
            # batches never re-traces.
            def step_fn(out, leaf_vals, presumed):
                vals = stage_body(out)                     # (B, k) bool
                leaf_vals = leaf_vals.at[:, slots].set(vals)
                value, decided = plan._propagate_distinct(leaf_vals, known)
                dec = decided | presumed[None, :]
                undec = jnp.concatenate([~dec.all(0), ~dec.all(1)])
                return leaf_vals, value, decided, undec, vals.sum(0)
        else:
            def step_fn(out, leaf_vals, value, decided, idx, n_real,
                        presumed):
                vals = (stage_body(out, rows=idx, body=body) if spatial
                        else stage_body(out, rows=idx))    # (R, k) bool
                sub = leaf_vals[idx].at[:, slots].set(vals)
                leaf_vals = leaf_vals.at[idx].set(sub)
                v, dec = plan._propagate_distinct(sub, known)
                value = value.at[idx].set(v)
                decided = decided.at[idx].set(dec)
                dec_eff = decided | presumed[None, :]
                undec = jnp.concatenate([~dec_eff.all(0), ~dec_eff.all(1)])
                # padded duplicate rows must not inflate the pass counts
                valid = jnp.arange(vals.shape[0]) < n_real
                return (leaf_vals, value, decided, undec,
                        (vals & valid[:, None]).sum(0))

        return self._built(key, si, step_fn)

    # -- execution --------------------------------------------------------

    def evaluate(self, out: FilterOutputs,
                 presumed_decided: Optional[np.ndarray] = None) -> jax.Array:
        """(B, N) bool masks, bit-identical to ``QueryPlan.evaluate`` —
        but stages stop/skip as soon as the undecided set allows, and
        each stage evaluates only the rows still undecided (compacted
        into a power-of-two bucket) once the first tiers have decided
        part of the batch.

        ``presumed_decided`` — optional (N,) bool mask of query columns
        the caller has already decided out-of-band (the temporal tier
        marks a query whose *window* outcome is latched; see
        repro.core.temporal).  Presumed columns stop contributing to the
        stage-skip test, the early stop, and the undecided-row
        compaction, exactly as if the plan had decided them — but their
        returned mask values are UNSPECIFIED (the caller owns their
        answers) and they feed no ledger.  Stages skipped only thanks to
        the presumption are reported in ``StageReport.skipped_presumed``
        and priced into ``cost_presumed_saved``."""
        plan = self.plan
        B = out.counts.shape[0]
        self._last_batch = B
        N = len(plan.queries)
        if presumed_decided is None:
            presumed = np.zeros(N, bool)
        else:
            presumed = np.asarray(presumed_decided, bool)
            if presumed.shape != (N,):
                raise ValueError(f"presumed_decided must be shape ({N},), "
                                 f"got {presumed.shape}")
        if presumed.all():
            # nothing left to evaluate: every stage is a presumed skip
            report = StageReport(
                order=[self.stages[s].name for s in self.order],
                cost_total=plan.exhaustive_cost_model(self.cost_model,
                                                      batch=B),
                batch=B)
            stage_rows = []
            for si in self.order:
                st = self.stages[si]
                report.skipped.append(st.name)
                report.skipped_presumed.append(st.name)
                report.cost_presumed_saved += self.cost_model.stage_cost(
                    st.kind, rows=B, batch=B, radius=st.radius)
                stage_rows.append((st.name, 0, B, None, None))
            self.last_report = report
            self._pending = ([], stage_rows)
            return jnp.zeros((B, N), bool)
        # Distinct-query space: stage state, propagation, and the skip /
        # stop tests run over the D distinct canonical trees; expansion
        # to the N query columns happens once at return (outside every
        # jitted step), so duplicate registrations of a template never
        # change a traced program.  A distinct column is presumed only
        # when ALL the query columns mapping to it are presumed — a
        # shared column with one live subscriber must keep evaluating.
        D = plan.n_distinct
        presumed_d = np.ones(D, bool)
        np.logical_and.at(presumed_d, plan.dup_map, presumed)
        presumed_dev = jnp.asarray(presumed_d)
        leaf_vals = jnp.zeros((B, plan.n_slot_cols), bool)
        value = jnp.zeros((B, D), bool)
        decided = jnp.zeros((B, D), bool)
        undecided_cols = ~presumed_d
        undecided_rows = np.ones(B, bool)
        report = StageReport(order=[self.stages[s].name for s in self.order],
                             cost_total=plan.exhaustive_cost_model(
                                 self.cost_model, batch=B),
                             batch=B)
        traces_before = self._trace_count
        pending: List[Tuple[np.ndarray, jax.Array, int]] = []
        stage_rows: List[Tuple[str, int, int, Optional[int],
                               Optional[int]]] = []
        ran: frozenset = frozenset()
        for si in self.order:
            st = self.stages[si]
            if not (self._uses_stage[:, si] & undecided_cols).any():
                report.skipped.append(st.name)
                if (self._uses_stage[:, si] & presumed_d).any():
                    # would have run for a presumed column's sake alone
                    report.skipped_presumed.append(st.name)
                    report.cost_presumed_saved += \
                        self.cost_model.stage_cost(st.kind, rows=B,
                                                   batch=B,
                                                   radius=st.radius)
                stage_rows.append((st.name, 0, B, None, None))
                continue
            if st.kind != "count" and out.grid is None:
                raise ValueError(
                    f"stage {st.name!r} has Spatial/Region leaves of an "
                    f"undecided query but the filter head emits no grid "
                    f"(OD-COF)")
            n_rows = int(undecided_rows.sum())
            if n_rows < B:
                idx, _ = compact_indices(undecided_rows,
                                         min_bucket=self.min_bucket, cap=B)
            else:                   # every row undecided (first stage /
                idx = None          # uniform traffic): skip the nonzero+
            if idx is None or idx.size >= B:        # pad bookkeeping
                body = self._body_for(si, None)
                step = self._get_step(si, ran, None, body)
                leaf_vals, value, decided, undec, counts = step(
                    out, leaf_vals, presumed_dev)
                rows_eval, seen = B, B
            else:
                body = self._body_for(si, idx.size)
                step = self._get_step(si, ran, idx.size, body)
                leaf_vals, value, decided, undec, counts = step(
                    out, leaf_vals, value, decided, jnp.asarray(idx),
                    jnp.asarray(n_rows, jnp.int32), presumed_dev)
                rows_eval, seen = idx.size, n_rows
            if seen == B:
                # only full-batch evaluations feed the per-slot ledger: a
                # compacted stage observes its slots CONDITIONED on the
                # row being undecided, and folding that into the shared
                # store would corrupt the unconditional frame-level
                # selectivities every adaptive ordering (FilterCascade
                # conjuncts, _staging_order benefits) is keyed on — a
                # leaf that passes 60% of busy frames but 6% of all
                # frames must not converge to 0.6.  Cold-neutral beats
                # wrong-converged; the exhaustive path and full-batch
                # stages keep those slots learning.
                pending.append((self._stage_slots(si), counts, seen))
            undec = np.asarray(undec)               # ONE (D + B,) fetch
            undecided_cols, undecided_rows = undec[:D], undec[D:]
            # (rows paid incl. padding, true undecided in/out: the row
            # ledger uses the work convention, the survival ledger the
            # real-row one)
            stage_rows.append((st.name, rows_eval, B, n_rows,
                               int(undecided_rows.sum())))
            ran = ran | {si}
            report.ran.append(st.name)
            report.rows_evaluated.append(rows_eval)
            report.undecided_rows_in.append(n_rows)
            report.bodies.append(body)
            # priced at the body that actually ran (a forced spatial_body
            # must be charged for its own choice, not the model's)
            report.cost_run += self.cost_model.stage_cost(
                st.kind, rows=rows_eval, batch=B, radius=st.radius,
                body=body if body in ("rows", "full") else None)
            # reported in query columns (the operator-facing unit): a
            # distinct column counts once per non-presumed subscriber
            report.undecided_after.append(
                int((undecided_cols[plan.dup_map] & ~presumed).sum()))
            if not undecided_cols.any():
                break
        assert report.ran, "every query owns at least one slot, so the " \
                           "first ordered stage always runs"
        for sj in self.order[len(report.ran) + len(report.skipped):]:
            report.skipped.append(self.stages[sj].name)
            stage_rows.append((self.stages[sj].name, 0, B, None, None))
        report.steps_compiled = self._trace_count - traces_before
        self.last_report = report
        self._pending = (pending, stage_rows)
        return value[:, plan.dup_map]

    # -- fleet execution (stream-axis group steps) ------------------------

    def _get_group_step(self, si: int, ran: frozenset,
                        bucket: Optional[int], body: str, n_streams: int,
                        shard_wrap: Optional[Callable],
                        wrap_sig: Optional[Tuple] = None) -> Callable:
        """Stream-axis-aware variant of ``_get_step``: the same fused
        stage step vmapped over a leading (S,) stream axis, optionally
        wrapped by ``shard_wrap`` (a ``jax.shard_map``
        closure over a device mesh's stream axis) before jitting, so S
        streams' stage work runs as ONE dispatched program — per device,
        a contiguous block of streams — instead of S host round-trips.

        Group steps share the single-stream signature-keyed cache (their
        keys carry the extra stream count + mesh identity, so the two
        families never collide).  The wrap closure itself cannot be
        content-hashed, so callers owning a stable mesh pass
        ``wrap_sig`` — a digest of the mesh topology
        (``ShardedPlanGroupEngine`` derives one from device ids + axis
        layout) — letting rebuilt engines over the same mesh re-hit
        compiled group steps across epochs.  Without one we fall back to
        the closure's ``id`` and pin the closure alive for the cache's
        lifetime (a recycled id must never alias a dead closure's
        entries).  The per-stream math is identical to the single-stream
        step — reductions in the stage bodies are over exact
        integer-valued occupancy data, so the vmapped slices are
        bit-identical to S serial evaluations (pinned by the
        multi-stream property tests)."""
        if shard_wrap is None:
            wrap_key: Optional[Tuple] = None
        elif wrap_sig is not None:
            wrap_key = wrap_sig
        else:
            self._wrap_refs.append(shard_wrap)     # keep id() unambiguous
            wrap_key = ("wrapid", id(shard_wrap))
        key = ("gstep", self.plan.plan_sig, self._stage_sigs[si],
               self._prefix_sig(ran), bucket, body, n_streams, wrap_key)
        step = self.step_cache.get(key)
        if step is not None:
            return step
        plan = self.plan
        stage_body = self._stage_body(si)
        slots = self._stage_slots(si)
        spatial = self.stages[si].kind == "spatial"
        known = np.zeros(plan.n_slot_cols, bool)
        for sj in ran:
            known[self.stages[sj].slots] = True
        known[slots] = True

        # ``presumed`` is the per-stream (D,) slice of the caller's
        # presumed-decided mask (vmapped over the stream axis), joining
        # the undecided reductions exactly as in the single-stream step
        if bucket is None:
            def step_fn(out, leaf_vals, presumed):
                vals = stage_body(out)                     # (B, k) bool
                leaf_vals = leaf_vals.at[:, slots].set(vals)
                value, decided = plan._propagate_distinct(leaf_vals, known)
                dec = decided | presumed[None, :]
                undec = jnp.concatenate([~dec.all(0), ~dec.all(1)])
                return leaf_vals, value, decided, undec, vals.sum(0)
        else:
            def step_fn(out, leaf_vals, value, decided, idx, n_real,
                        presumed):
                vals = (stage_body(out, rows=idx, body=body) if spatial
                        else stage_body(out, rows=idx))    # (R, k) bool
                sub = leaf_vals[idx].at[:, slots].set(vals)
                leaf_vals = leaf_vals.at[idx].set(sub)
                v, dec = plan._propagate_distinct(sub, known)
                value = value.at[idx].set(v)
                decided = decided.at[idx].set(dec)
                dec_eff = decided | presumed[None, :]
                undec = jnp.concatenate([~dec_eff.all(0), ~dec_eff.all(1)])
                valid = jnp.arange(vals.shape[0]) < n_real
                return (leaf_vals, value, decided, undec,
                        (vals & valid[:, None]).sum(0))

        grp = jax.vmap(step_fn)
        if shard_wrap is not None:
            grp = shard_wrap(grp)
        return self._built(key, si, grp)

    def _built(self, key: Tuple, si: int, fn: Callable) -> Callable:
        """Jit ``fn`` as stage ``si``'s step (device program
        ``jit_plan_<stage>``) and cache it under ``key``."""
        step = jax.jit(tracing.named(fn, f"plan_{self.stages[si].name}"))
        self._trace_count += 1
        if self.counters is not None:
            self.counters.steps_built += 1
        self.step_cache.put(key, step)
        return step

    def evaluate_group(self, outs: FilterOutputs, *,
                       shard_wrap: Optional[Callable] = None,
                       wrap_sig: Optional[Tuple] = None,
                       presumed_decided: Optional[np.ndarray] = None
                       ) -> jax.Array:
        """(S, B, N) bool masks for S streams' stacked batches —
        per-stream slice bit-identical to ``evaluate`` on that stream's
        batch alone.

        ``outs`` carries a leading stream axis (counts (S, B, C), grid
        (S, B, g, g, C) or None); the caller stacks per-stream filter
        outputs and typically ``jax.device_put``s them with a
        stream-axis ``NamedSharding`` one chunk ahead of compute
        (``distributed.multistream`` owns that double-buffering).

        Staging decisions are **group-uniform**: a tier runs when ANY
        stream's undecided queries need it, the row-compaction bucket is
        the power-of-two covering the WORST stream's undecided count,
        and the spatial body is chosen once for the group at that
        bucket.  Both relaxations only ever evaluate *more* rows/tiers
        for a stream than its solo staging would — and decided
        (frame, query) cells are invariant to extra evaluation (the same
        monotonicity that makes tier skipping sound) — so per-stream
        answers stay bit-identical while the group keeps one fused step
        per stage (one host sync per stage for the whole fleet slice,
        not per stream).

        Ledger feedback aggregates across streams: full-batch stage
        evaluations contribute S·B frames of unconditional per-slot
        pass counts, and the stage row/survival ledgers see the group's
        total paid rows over an S·B-row batch (``flush_stats`` is
        unchanged).  ``StageReport`` costs are priced per stream at the
        rows each stream's slice evaluated, times S — the cost model
        prices the sharded step as S vmapped stage bodies.

        ``presumed_decided`` — optional (S, N) bool mask of query
        columns each *stream's* temporal tier has already
        window-decided (see ``evaluate``'s single-stream contract; the
        fleet engine stacks ``TemporalProgram.suppressed_signals``-
        driven decidedness per stream).  Presumption is per-stream:
        stream s's presumed columns stop feeding its skip/stop/
        compaction tests while other streams keep evaluating, and the
        group-uniform relaxation still holds — presumption only ever
        *removes* work, never changes an evaluated cell.  Presumed
        columns' returned values are UNSPECIFIED, as in ``evaluate``;
        stages skipped only thanks to presumption land in
        ``StageReport.skipped_presumed`` / ``cost_presumed_saved``.

        ``wrap_sig`` — optional stable content signature for
        ``shard_wrap`` (mesh topology digest); lets rebuilt engines over
        the same mesh re-hit compiled group steps across registry
        epochs (see ``_get_group_step``)."""
        plan = self.plan
        S, B = outs.counts.shape[:2]
        self._last_batch = B
        N = len(plan.queries)
        D = plan.n_distinct
        if presumed_decided is None:
            presumed = np.zeros((S, N), bool)
        else:
            presumed = np.asarray(presumed_decided, bool)
            if presumed.shape != (S, N):
                raise ValueError(f"presumed_decided must be shape "
                                 f"({S}, {N}), got {presumed.shape}")
        # per-stream distinct-space presumption: a distinct column is
        # presumed only when ALL query columns mapping to it are (same
        # rule as the single-stream path, applied per stream)
        presumed_d = np.ones((S, D), bool)
        for s in range(S):
            np.logical_and.at(presumed_d[s], plan.dup_map, presumed[s])
        if presumed_d.all():
            # every stream's every query is window-decided: the whole
            # group batch is one presumed skip (the fleet engine's
            # temporal all-decided fast path)
            report = StageReport(
                order=[self.stages[s].name for s in self.order],
                cost_total=S * plan.exhaustive_cost_model(self.cost_model,
                                                          batch=B),
                batch=S * B)
            stage_rows = []
            for si in self.order:
                st = self.stages[si]
                report.skipped.append(st.name)
                report.skipped_presumed.append(st.name)
                report.cost_presumed_saved += S * self.cost_model.stage_cost(
                    st.kind, rows=B, batch=B, radius=st.radius)
                stage_rows.append((st.name, 0, S * B, None, None))
            self.last_report = report
            self._pending = ([], stage_rows)
            return jnp.zeros((S, B, N), bool)
        presumed_dev = jnp.asarray(presumed_d)
        leaf_vals = jnp.zeros((S, B, plan.n_slot_cols), bool)
        value = jnp.zeros((S, B, D), bool)
        decided = jnp.zeros((S, B, D), bool)
        undecided_cols = ~presumed_d
        undecided_rows = np.ones((S, B), bool)
        report = StageReport(order=[self.stages[s].name for s in self.order],
                             cost_total=S * plan.exhaustive_cost_model(
                                 self.cost_model, batch=B),
                             batch=S * B)
        traces_before = self._trace_count
        pending: List[Tuple[np.ndarray, jax.Array, int]] = []
        stage_rows: List[Tuple[str, int, int, Optional[int],
                               Optional[int]]] = []
        ran: frozenset = frozenset()
        for si in self.order:
            st = self.stages[si]
            if not (self._uses_stage[None, :, si] & undecided_cols).any():
                report.skipped.append(st.name)
                if (self._uses_stage[None, :, si] & presumed_d).any():
                    # would have run for presumed columns' sake alone
                    report.skipped_presumed.append(st.name)
                    report.cost_presumed_saved += \
                        S * self.cost_model.stage_cost(
                            st.kind, rows=B, batch=B, radius=st.radius)
                stage_rows.append((st.name, 0, S * B, None, None))
                continue
            if st.kind != "count" and outs.grid is None:
                raise ValueError(
                    f"stage {st.name!r} has Spatial/Region leaves of an "
                    f"undecided query but the filter head emits no grid "
                    f"(OD-COF)")
            with tracing.span(f"repro.plan.tier.{st.name}"):
                n_rows = undecided_rows.sum(1)              # (S,)
                worst = int(n_rows.max())
                if worst >= B:
                    bucket = B                              # full-batch step
                else:
                    bucket = max(1, int(self.min_bucket))
                    while bucket < worst:
                        bucket <<= 1
                    bucket = min(bucket, B)
                if bucket >= B:
                    body = self._body_for(si, None)
                    step = self._get_group_step(si, ran, None, body, S,
                                                shard_wrap, wrap_sig)
                    leaf_vals, value, decided, undec, counts = step(
                        outs, leaf_vals, presumed_dev)
                    rows_eval = B
                else:
                    body = self._body_for(si, bucket)
                    step = self._get_group_step(si, ran, bucket, body, S,
                                                shard_wrap, wrap_sig)
                    # per-stream undecided rows padded (compact_indices
                    # discipline: repeat the last survivor so duplicate
                    # scatters are benign) to the GROUP bucket
                    idx = np.zeros((S, bucket), np.int32)
                    for s in range(S):
                        rows_s = np.nonzero(undecided_rows[s])[0]
                        n = rows_s.size
                        idx[s, :n] = rows_s
                        idx[s, n:] = rows_s[-1] if n else 0
                    leaf_vals, value, decided, undec, counts = step(
                        outs, leaf_vals, value, decided, jnp.asarray(idx),
                        jnp.asarray(n_rows.astype(np.int32)), presumed_dev)
                    rows_eval = bucket
                if rows_eval == B:
                    # full-batch group evaluation: S·B unconditional frames
                    # feed the per-slot ledger (compacted steps stay out —
                    # same conditioning argument as the serial path)
                    pending.append((self._stage_slots(si), counts.sum(0),
                                    S * B))
                # ONE (S, D + B) fetch per stage
                undec = tracing.to_host(undec, "plan_undecided", self.counters)
            undecided_cols, undecided_rows = undec[:, :D], undec[:, D:]
            stage_rows.append((st.name, rows_eval * S, S * B,
                               int(n_rows.sum()),
                               int(undecided_rows.sum())))
            ran = ran | {si}
            report.ran.append(st.name)
            report.rows_evaluated.append(rows_eval * S)
            report.undecided_rows_in.append(int(n_rows.sum()))
            report.bodies.append(body)
            report.cost_run += S * self.cost_model.stage_cost(
                st.kind, rows=rows_eval, batch=B, radius=st.radius,
                body=body if body in ("rows", "full") else None)
            report.undecided_after.append(
                int((undecided_cols[:, plan.dup_map] & ~presumed).sum()))
            if not undecided_cols.any():
                break
        for sj in self.order[len(report.ran) + len(report.skipped):]:
            report.skipped.append(self.stages[sj].name)
            stage_rows.append((self.stages[sj].name, 0, S * B, None, None))
        report.steps_compiled = self._trace_count - traces_before
        self.last_report = report
        self._pending = (pending, stage_rows)
        return value[:, :, plan.dup_map]

    def flush_stats(self, stats) -> None:
        """Fold the last batch's per-slot pass counts into ``stats`` with
        ONE device fetch (counts were accumulated on device per stage).
        Only full-batch stage evaluations contribute (see ``evaluate`` —
        compacted stages observe conditional rates the shared ledger must
        not absorb); per-stage row traffic (including skipped stages at
        0 rows) goes to the stage ledger behind
        ``predicted_batch_cost``."""
        if not self._pending:
            return
        with tracing.span("repro.plan.flush_stats"):
            self._flush(stats)

    def _flush(self, stats) -> None:
        pending, stage_rows = self._pending
        self._pending = None
        if pending:
            counts = tracing.to_host(
                jnp.concatenate([c for _, c, _ in pending]), "plan_counts",
                self.counters)
            off = 0
            for slots, _, seen in pending:
                stats.observe_many(
                    [self.plan.slot_keys[s] for s in slots],
                    counts[off:off + len(slots)], seen, canonical=True)
                off += len(slots)
        for name, rows, batch, surv_in, surv_out in stage_rows:
            stats.observe_stage_rows(name, rows, batch)
            if surv_in:                          # executed on real rows:
                stats.observe_stage_survival(    # feed the greedy order
                    name, surv_in, surv_out)     # search's prefix model

    def predicted_batch_cost(self, stats,
                             step_overhead: Optional[float] = None,
                             *, batch: Optional[int] = None) -> float:
        """Ledger-predicted cost-model cost of one staged batch: each
        stage priced at its learned row fraction of ``batch`` (default:
        the last evaluated batch size, else the reference batch), plus
        ``step_overhead`` (default: the cost model's measured/static
        per-stage overhead) per expected execution.  This is how a
        *parked* adaptive cascade keeps re-deciding the
        staged-vs-exhaustive mode switch between probe batches — the
        per-stage undecided-rate feedback accumulated by ``flush_stats``
        substitutes for running the staged path (cold ledger ->
        full-batch assumption, matching the pre-compaction model)."""
        cm = self.cost_model
        if step_overhead is None:
            step_overhead = cm.step_overhead()
        B = float(batch or self._last_batch or CM.REF_BATCH)
        cost = 0.0
        for si in self.order:
            st = self.stages[si]
            if stats is None:
                frac, execd = 1.0, 1.0
            else:
                frac = stats.stage_row_frac(st.name)
                execd = stats.stage_exec_rate(st.name)
            # expected stage cost = P(executes) x cost at the rows seen
            # WHEN it executes (frac folds skipped batches in as zero
            # rows, so the conditional row count is frac/execd of the
            # batch).  Pricing the unconditional frac directly would
            # charge a measured model's full fixed overhead for stages
            # the ledger says are almost always skipped — the parked
            # cascade would then never un-park on exactly the skewed
            # traffic the prediction exists for.  Under the static
            # model (no fixed part) this reduces to the legacy
            # unit_cost * frac arithmetic exactly.
            rows_cond = min(frac / max(execd, 1e-9), 1.0) * B
            cost += execd * cm.stage_cost(st.kind, rows=rows_cond, batch=B,
                                          radius=st.radius) \
                + step_overhead * execd
        return cost

    def describe(self) -> List[Dict]:
        """Operator view of the current staging (order, cost, slots)."""
        return [{"stage": self.stages[si].name,
                 "kind": self.stages[si].kind,
                 "cost": self.stages[si].cost,
                 "slots": [repr(self.plan.slot_keys[s])
                           for s in self._stage_slots(si)]}
                for si in self.order]


def plan_queries(queries: Sequence[Q.Predicate], *,
                 tau: float = 0.2,
                 leaf_table: Optional[CanonicalLeafTable] = None,
                 prev: Optional[QueryPlan] = None) -> QueryPlan:
    return QueryPlan(queries, tau=tau, leaf_table=leaf_table, prev=prev)
