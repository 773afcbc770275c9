"""The measured span: from chunk completion to chunk completion.

``t0`` is the moment the first window chunk completes at the sink: the
pipeline is full, one row executes and the next is staged. The span opens at
``t0`` and closes at the last completion at or before ``t0 + seconds``, so it
holds whole completion-to-completion intervals and no edge cuts a row in two.
So that a stall anywhere in the window still shows: if the wait from the last
completion to ``t0 + seconds`` is longer than the longest gap between two
completions inside the span, the span closes at ``t0 + seconds`` instead.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence


@dataclass(frozen=True)
class Span:
    start: float
    end: float
    counted: List[int]  # indices into ``times`` of the completions counted
    stalled: bool  # closed at t0 + seconds, not at a completion

    @property
    def seconds(self) -> float:
        return self.end - self.start


def measured_span(times: Sequence[float], t0: float, seconds: float) -> Optional[Span]:
    """``times``: completion times of the chunks sent after the one that set
    ``t0``, in any order. None when fewer than two of them fall in
    (t0, t0 + seconds]: one interval has no second reading and no rate."""
    counted = sorted((i for i, t in enumerate(times) if t0 < t <= t0 + seconds), key=lambda i: times[i])
    if len(counted) < 2:
        return None
    edges = [t0] + [times[i] for i in counted]
    longest_gap = max(b - a for a, b in zip(edges, edges[1:]))
    stalled = (t0 + seconds) - edges[-1] > longest_gap
    return Span(start=t0, end=t0 + seconds if stalled else edges[-1], counted=counted, stalled=stalled)
