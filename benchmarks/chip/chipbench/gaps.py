"""Token gaps, their kinds, and the percentiles read from them.

Every gap between two successive tokens of one request is one sample of the
time-between-tokens tail; none is left out. A gap is ``plain`` when no
admission prefill ran between its two tokens, ``tail`` when a prefix-hit
(tail-only) prefill ran, and ``full`` when a prefill of a whole prompt ran.
An admission's prefill runs between the last token before it and the first
token after it in every live lane, and ends with the admitted request's own
first token, so a gap holds an admission when that first token falls inside
it.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Sequence

import numpy as np

KINDS = ("plain", "tail", "full")


@dataclasses.dataclass
class Gaps:
    values: np.ndarray        # seconds, one per gap
    kinds: List[str]          # one of KINDS per gap

    def share(self, kind: str) -> float:
        """Percent of all gaps that are of ``kind``."""
        if not self.kinds:
            return 0.0
        return 100.0 * sum(k == kind for k in self.kinds) / len(self.kinds)

    def median(self, kind: str):
        v = [x for x, k in zip(self.values, self.kinds) if k == kind]
        return float(np.median(v)) if v else None

    def summary(self) -> Dict[str, object]:
        """Shares and medians of each kind, and how far the 95th
        percentile's rank lies from the nearest edge between two kinds
        (percentage points), when the kinds are ordered plain < tail <
        full."""
        full = self.share("full")
        stall = full + self.share("tail")
        edges = [e for e in (full, stall) if 0.0 < e < 100.0]
        return {
            "n": len(self.kinds),
            "share_pct": {k: self.share(k) for k in KINDS},
            "median_s": {k: self.median(k) for k in KINDS},
            "p95_edge_margin_pct": (min(abs(5.0 - e) for e in edges)
                                    if edges else None),
        }


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile of all samples, by linear interpolation
    between closest ranks (numpy's default)."""
    if len(values) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


def classify(emits: Dict[int, List[float]],
             admission_kind: Dict[int, str]) -> Gaps:
    """``emits``: rid -> host-clock time of each of its tokens, in order.
    ``admission_kind``: rid -> "full" or "tail", the kind of prefill that
    produced the request's first token."""
    firsts = sorted((ws[0], admission_kind[rid])
                    for rid, ws in emits.items() if ws)
    at = [w for w, _ in firsts]
    values, kinds = [], []
    for ws in emits.values():
        for a, b in zip(ws, ws[1:]):
            lo, hi = bisect.bisect_right(at, a), bisect.bisect_left(at, b)
            inside = {firsts[i][1] for i in range(lo, hi)}
            kinds.append("full" if "full" in inside else
                         "tail" if "tail" in inside else "plain")
            values.append(b - a)
    return Gaps(np.asarray(values, np.float64), kinds)
