"""Query mixes: the program's queries from a mix file, and the plain
reference of their answers.

A mix file (``bench/mixes/<name>.json``) lists queries as JSON trees:

    {"kind": "count", "op": ">=", "value": 1, "tolerance": 0}
    {"kind": "class_count", "cls": 0, "op": "<=", "value": 2, "tolerance": 1}
    {"kind": "spatial", "a": 0, "rel": "left", "b": 1, "radius": 1}
    {"kind": "region", "cls": 5, "rect": [r0, c0, r1, c1], "min_count": 16,
     "radius": 0}
    {"kind": "and" | "or", "terms": [...]}, {"kind": "not", "term": ...}
    {"kind": "duration", "pred": ..., "min_frames": 3}
    {"kind": "sliding_count", "pred": ..., "window": 8, "op": ">=",
     "value": 4}

``reference_answers`` evaluates them over the filter outputs the served
path produced, written from the query language's definitions (paper
section II; temporal operators latched within each hopping window) with
numpy alone:

- counts are rounded (half to even) and clipped to [0, 64]; ``tolerance``
  widens the comparison;
- a class is present in a cell when its map value exceeds tau (0.2),
  dilated by Manhattan ``radius``;
- LEFT(a, b): some a-cell lies in a column left of some b-cell, i.e.
  min col(a) < max col(b); ABOVE likewise over rows; RIGHT and BELOW
  mirror them;
- a region holds when at least ``min_count`` present cells of the class
  lie in the half-open rectangle;
- Duration: from the frame that completes the first run of
  ``min_frames`` consecutive true frames in the window, true to the
  window's end; SlidingCount: from the frame that completes the first
  sub-window of ``window`` frames whose true-frame count satisfies the
  comparison.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

from repro.core import query as Q

TAU = 0.2
MAX_COUNT = 64
OPS = {"==": Q.Op.EQ, ">=": Q.Op.GE, "<=": Q.Op.LE}
RELS = {"left": Q.Rel.LEFT, "right": Q.Rel.RIGHT, "above": Q.Rel.ABOVE,
        "below": Q.Rel.BELOW}


def to_query(d: Dict[str, Any]):
    """A mix entry as the program's query object."""
    k = d["kind"]
    if k == "count":
        return Q.Count(OPS[d["op"]], d["value"], d.get("tolerance", 0))
    if k == "class_count":
        return Q.ClassCount(d["cls"], OPS[d["op"]], d["value"],
                            d.get("tolerance", 0))
    if k == "spatial":
        return Q.Spatial(d["a"], RELS[d["rel"]], d["b"], d.get("radius", 0))
    if k == "region":
        return Q.Region(d["cls"], tuple(d["rect"]), d.get("min_count", 1),
                        d.get("radius", 0))
    if k in ("and", "or"):
        terms = tuple(to_query(t) for t in d["terms"])
        return Q.And(terms) if k == "and" else Q.Or(terms)
    if k == "not":
        return Q.Not(to_query(d["term"]))
    if k == "duration":
        return Q.Duration(to_query(d["pred"]), d["min_frames"])
    if k == "sliding_count":
        return Q.SlidingCount(to_query(d["pred"]), d["window"],
                              OPS[d["op"]], d["value"])
    raise ValueError(f"unknown query kind {k!r}")


def _compare(x, op: str, v: int, tol: int = 0):
    if op == "==":
        return (x >= v - tol) & (x <= v + tol)
    if op == ">=":
        return x >= v - tol
    if op == "<=":
        return x <= v + tol
    raise ValueError(op)


def _dilate(occ: np.ndarray, radius: int) -> np.ndarray:
    """(F, g, g) bool, grown by Manhattan distance ``radius``."""
    for _ in range(radius):
        grown = occ.copy()
        grown[:, 1:] |= occ[:, :-1]
        grown[:, :-1] |= occ[:, 1:]
        grown[:, :, 1:] |= occ[:, :, :-1]
        grown[:, :, :-1] |= occ[:, :, 1:]
        occ = grown
    return occ


class _Frames:
    """Frame-level predicate values over F frames of filter outputs."""

    def __init__(self, counts: np.ndarray, grid: np.ndarray):
        self.counts = np.clip(np.round(counts), 0, MAX_COUNT).astype(int)
        self.grid = grid
        self._occ: Dict[Tuple[int, int], np.ndarray] = {}

    def occ(self, cls: int, radius: int) -> np.ndarray:
        key = (cls, radius)
        if key not in self._occ:
            self._occ[key] = _dilate(self.grid[..., cls] > TAU, radius)
        return self._occ[key]

    def value(self, d) -> np.ndarray:
        k = d["kind"]
        if k == "count":
            return _compare(self.counts.sum(-1), d["op"], d["value"],
                            d.get("tolerance", 0))
        if k == "class_count":
            return _compare(self.counts[:, d["cls"]], d["op"], d["value"],
                            d.get("tolerance", 0))
        if k == "spatial":
            r = d.get("radius", 0)
            a, b = self.occ(d["a"], r), self.occ(d["b"], r)
            axis = 2 if d["rel"] in ("left", "right") else 1
            idx = np.arange(a.shape[axis])
            pa, pb = a.any(3 - axis), b.any(3 - axis)      # (F, g)
            big = a.shape[axis]
            min_a = np.where(pa, idx, big).min(1)
            max_a = np.where(pa, idx, -1).max(1)
            min_b = np.where(pb, idx, big).min(1)
            max_b = np.where(pb, idx, -1).max(1)
            both = pa.any(1) & pb.any(1)
            if d["rel"] in ("left", "above"):
                return both & (min_a < max_b)
            return both & (max_a > min_b)
        if k == "region":
            r0, c0, r1, c1 = d["rect"]
            inside = self.occ(d["cls"], d.get("radius", 0))[:, r0:r1, c0:c1]
            return inside.sum((1, 2)) >= d.get("min_count", 1)
        if k == "and":
            return np.logical_and.reduce([self.value(t) for t in d["terms"]])
        if k == "or":
            return np.logical_or.reduce([self.value(t) for t in d["terms"]])
        if k == "not":
            return ~self.value(d["term"])
        raise ValueError(f"{k!r} is not a frame-level query")


def _latched(hit_at: Sequence[bool]) -> np.ndarray:
    return np.logical_or.accumulate(np.asarray(hit_at, bool))


def _temporal(d, frames: _Frames, windows) -> np.ndarray:
    k = d["kind"]
    if k in ("and", "or"):
        parts = [_temporal(t, frames, windows) for t in d["terms"]]
        red = np.logical_and if k == "and" else np.logical_or
        return red.reduce(parts)
    if k == "not":
        return ~_temporal(d["term"], frames, windows)
    if k not in ("duration", "sliding_count"):
        return frames.value(d)
    pred = frames.value(d["pred"])
    out = np.zeros(len(pred), bool)
    for lo, hi in windows:
        p = pred[lo:hi]
        n = d["min_frames"] if k == "duration" else d["window"]
        done = np.zeros(hi - lo, bool)
        for end in range(n - 1, hi - lo):
            run = p[end - n + 1:end + 1]
            done[end] = run.all() if k == "duration" else \
                _compare(int(run.sum()), d["op"], d["value"])
        out[lo:hi] = _latched(done)
    return out


def reference_answers(queries: Sequence[Dict], counts: np.ndarray,
                      grid: np.ndarray, windows) -> np.ndarray:
    """(F, N) answers of one camera's F frames (filter outputs in frame
    order), hopping windows given as (lo, hi) frame spans."""
    frames = _Frames(np.asarray(counts), np.asarray(grid))
    return np.stack([_temporal(q, frames, windows) for q in queries], -1)

