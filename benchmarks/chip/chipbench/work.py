"""The work a window did, counted from the program's report: which
positions each request prefilled, and the context of every decode lane at
every decode step. Kernel and model operation counts are built on these.

A request admitted at step clock ``a`` with prompt length ``P`` and ``n``
served tokens emits its first token from the prefill and decodes at clocks
``a .. a + n - 2``; at clock ``a + j`` its lane writes position ``P + j`` and
attends over ``P + j + 1`` positions.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Tuple


def decode_contexts(report) -> Dict[int, List[int]]:
    """step clock -> attended length of every live lane in that decode."""
    steps: Dict[int, List[int]] = collections.defaultdict(list)
    for r in report.results:
        n = len(r.tokens) - r.prompt_len
        a = int(r.admitted_at)
        for j in range(n - 1):
            steps[a + j].append(r.prompt_len + j + 1)
    return dict(steps)


def prefilled(report) -> List[Tuple[int, int]]:
    """(first, last + 1) prompt positions each request computed in its
    prefill; positions served from shared prefix blocks are not computed."""
    return [(r.shared_prefix, r.prompt_len) for r in report.results]
