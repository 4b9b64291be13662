"""The open-loop generator: a traffic mix (a data file) made into a timed
schedule of queries, and each query made into a ``GridQuery`` plan.

A mix file (``bench/mixes/<name>.json``) holds only parameters:

- ``arrivals``: ``"poisson"`` (exponential gaps at the cell's rate);
- ``select`` and ``programs``: the column and the statistics every query
  folds (``"mean"``, ``"variance"``);
- ``filter``: what selects each query's rows, one of
  - ``{"kind": "age_sex_window", "lo": [a, b], "width": [c, d],
    "age_cap": e, "sex": [...]}``: ``lo ~ U[a, b)``, ``width ~ U[c, d]``,
    ``hi = min(lo + width, e)``, sex drawn uniformly from the list
    (``null`` is either sex).  Every query brings a fresh predicate;
  - ``{"kind": "catalog", "entries": [[lo, hi, sex], ...], "zipf_s": s}``:
    a fixed catalog whose plans are built once and shared, drawn with
    Zipf weights ``1 / rank**s`` (``null`` bounds select everything);
- ``sample``: how many answers the correctness check compares, drawn from
  the seed (the largest selection is always among them).

The rate is the cell's, in ``bench/cells/<cell>.json``.

The arrival times and the query parameters are fixed per (mix, rate,
window), drawn from a fixed base seed; ``--seed`` only permutes which
query arrives at which time.  So every seed offers the same load, with
the same bursts, over the same selections in another order: the tail
measures the system, not the luck of one seed's bursts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: seed of the fixed multisets (gaps, query parameters)
BASE_SEED = 0


@dataclasses.dataclass(frozen=True)
class Query:
    index: int
    t_due: float                   # seconds after the window opens
    lo: Optional[float]
    hi: Optional[float]
    sex: Optional[int]
    entry: Optional[int] = None    # catalog entry, None for a fresh filter


def _arrival_gaps(rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    gaps = []
    t = 0.0
    while True:
        g = float(rng.exponential(1.0 / rate))
        if t + g >= seconds:
            return np.asarray(gaps, np.float64)
        t += g
        gaps.append(g)


def schedule(mix: Dict, rate: float, seconds: float,
             seed: int) -> List[Query]:
    """The window's queries, in due order."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    base = np.random.default_rng(BASE_SEED)
    gaps = _arrival_gaps(rate, seconds, base)
    n = len(gaps)
    flt = mix["filter"]
    kind = flt["kind"]
    if kind == "age_sex_window":
        a, b = flt["lo"]
        c, d = flt["width"]
        lo = base.uniform(a, b, n)
        hi = np.minimum(lo + base.uniform(c, d, n), float(flt["age_cap"]))
        choices = list(flt["sex"])
        sex = [choices[i] for i in base.integers(0, len(choices), n)]
        params = [(float(lo[i]), float(hi[i]), sex[i], None)
                  for i in range(n)]
    elif kind == "catalog":
        entries = flt["entries"]
        w = 1.0 / np.arange(1, len(entries) + 1) ** float(flt["zipf_s"])
        picks = base.choice(len(entries), n, p=w / w.sum())
        params = [tuple(entries[e]) + (int(e),) for e in picks]
    else:
        raise ValueError(f"unknown filter kind {kind!r}")
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)).spawn(3)[2])
    params = [params[i] for i in rng.permutation(n)]
    due = np.cumsum(gaps)
    return [Query(i, float(due[i]), *_norm(params[i]))
            for i in range(n)]


def _norm(p: Tuple) -> Tuple:
    lo, hi, sex, entry = p
    return (None if lo is None else float(lo),
            None if hi is None else float(hi),
            None if sex is None else int(sex), entry)


def mask_of(q: Query, ages: np.ndarray, sexes: np.ndarray) -> np.ndarray:
    """The rows a query selects, from the benchmark's own columns."""
    m = np.ones(len(ages), bool)
    if q.lo is not None:
        m &= ages >= q.lo
    if q.hi is not None:
        m &= ages < q.hi
    if q.sex is not None:
        m &= sexes == q.sex
    return m


def sample(queries: Sequence[Query], masks: np.ndarray, k: int,
           seed: int) -> List[int]:
    """Indices of the queries whose answers are compared: ``k`` drawn from
    the seed, plus the one that selects the most rows."""
    n = len(queries)
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)).spawn(4)[3])
    picked = set(rng.choice(n, min(k, n), replace=False).tolist()) if n else set()
    if n:
        picked.add(int(np.argmax(masks.sum(axis=1))))
    return sorted(picked)


class PlanMaker:
    """Builds each query's plan; catalog plans are built once and shared,
    so repeats carry the same predicate object (the frontend's
    single-flight key)."""

    def __init__(self, session, mix: Dict):
        from repro.core.stats import MeanProgram, VarianceProgram

        makers = {"mean": MeanProgram, "variance": VarianceProgram}
        self.session = session
        self.column = mix["select"]
        self.programs = [makers[p] for p in mix["programs"]]
        self._catalog: Dict[int, Any] = {}

    def _build(self, q: Query):
        from repro.core.query import age_sex_predicate

        plan = self.session.scan().select(self.column)
        if not (q.lo is None and q.hi is None and q.sex is None):
            plan = plan.where(age_sex_predicate(q.lo, q.hi, q.sex),
                              ["age", "sex"])
        for make in self.programs:
            plan = plan.map(make())
        return plan.reduce()

    def plan(self, q: Query):
        if q.entry is None:
            return self._build(q)
        if q.entry not in self._catalog:
            self._catalog[q.entry] = self._build(q)
        return self._catalog[q.entry]

    def rows_plan(self, rows: np.ndarray, ages: np.ndarray):
        """A warm-up plan selecting exactly ``rows`` (by their ages)."""
        chosen = np.unique(ages[rows])

        def pred(cols):
            return np.isin(cols["age"], chosen)

        plan = self.session.scan().select(self.column).where(pred, ["age"])
        for make in self.programs:
            plan = plan.map(make())
        return plan.reduce()
