"""The comparison that decides ``correct``.

Every answered query row is an answer that can be checked on its own: the ids
and distances the client received. Once the window has closed, a sample of
those rows drawn from the seed (each answered row equally likely) is compared
with ``reference.py`` over a corpus the harness regenerates from its seed:

* ``malformed`` - sampled rows whose ids leave the corpus, repeat, or whose
  distances do not ascend. Exact: limit 0.
* ``unanswered`` - requests admitted and never answered. Exact: limit 0.
* ``dist_err`` - the widest gap between a served distance and the exact
  distance of the id it was served with, over the distance's scale (see
  ``reference._scale``). Its limit sits between the program's readings and
  the bfloat16 control's (``PERF.md`` gives both).
* ``recall_at_10`` - mean share of each sampled row's exact ten nearest that
  were served. Its floor is the recall the configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from bench import gen
from bench import reference


@dataclass
class Group:
    """Answered rows that share one corpus: ``corpus()`` regenerates it."""

    corpus: Callable
    queries: np.ndarray  # (m, d) float32, as sent
    ids: np.ndarray      # (m, k) as received
    dists: np.ndarray    # (m, k) as received


@dataclass
class Number:
    name: str
    value: float
    limit: float
    higher_is_worse: bool = True

    @property
    def ok(self) -> bool:
        if self.higher_is_worse:
            return bool(self.value <= self.limit)
        return bool(self.value >= self.limit)

    def line(self) -> str:
        op = "<=" if self.higher_is_worse else ">="
        return (f"check {self.name} {self.value!r} (limit {op} {self.limit!r}): "
                f"{'ok' if self.ok else 'FAILED'}")


def malformed_rows(ids: np.ndarray, dists: np.ndarray, n: int) -> np.ndarray:
    """(m,) bool: row has an id outside [0, n), a repeated id, or a distance
    that is not finite or not ascending."""
    bad = ((ids < 0) | (ids >= n)).any(1)
    s = np.sort(ids, axis=1)
    bad |= (s[:, 1:] == s[:, :-1]).any(1)
    bad |= ~np.isfinite(dists).all(1)
    with np.errstate(invalid="ignore"):
        bad |= (np.diff(dists, axis=1) < 0).any(1)
    return bad


def sample_rows(total: int, size: int, seed: int) -> np.ndarray:
    """Sorted indices of ``min(size, total)`` answered rows, drawn without
    replacement from the seed."""
    size = min(size, total)
    return np.sort(gen.np_rng(seed, 3).choice(total, size=size, replace=False))


def judge(groups: list[Group], unanswered: int, *, metric: str, k: int,
          limits: dict, sample: int, seed: int) -> tuple[bool, list[Number]]:
    """Compare a sample of the answered rows with the reference."""
    sizes = [g.ids.shape[0] for g in groups]
    picks = sample_rows(sum(sizes), sample, seed)
    starts = np.cumsum([0] + sizes)
    bad = 0
    err = 0.0
    hits = 0
    rows = 0
    for g, lo, hi in zip(groups, starts[:-1], starts[1:]):
        mine = picks[(picks >= lo) & (picks < hi)] - lo
        if mine.size == 0:
            continue
        base = g.corpus()
        q, ids, dists = g.queries[mine], g.ids[mine], g.dists[mine]
        bad += int(malformed_rows(ids, dists, base.shape[0]).sum())
        exact, _ = reference.exact_topk(q, base, k, metric)
        d_ref, scale = reference.pair_distances(q, base, ids, metric)
        gap = np.abs(dists.astype(np.float64) - d_ref) / scale
        err = max(err, float(np.nanmax(gap)) if np.isfinite(gap).any() else np.inf)
        hits += int((ids[:, :, None] == exact[:, None, :]).any(-1).sum())
        rows += mine.size
        del base
    recall = hits / max(rows * k, 1)
    numbers = [
        Number("malformed", bad, 0),
        Number("unanswered", unanswered, 0),
        Number("dist_err", err, limits["dist_err"]),
        Number("recall_at_10", recall, limits["recall_at_10"],
               higher_is_worse=False),
    ]
    return all(x.ok for x in numbers) and rows > 0, numbers
