"""The serve tick split into its phases, from the engine's spans.

A ``serve.tick`` span holds, on the ticker thread, the upload of its
padded batch (``query.rows_upload``), the dispatch of the plan and the
padding mask (``query.rows_dispatch``), both inside ``query.infer_rows``,
and the wait for the predictions (``serve.result_wait``).  What the tick
spends outside the three is its own: coalescing, bookkeeping, hand-back.
Each phase is counted only under a tick the window recorded, so the four
parts add up to the ticks' total time.
"""

from __future__ import annotations

import dataclasses

__all__ = ["TICK", "PHASES", "TickSplit", "split"]

TICK = "serve.tick"
PHASES = ("query.rows_upload", "query.rows_dispatch", "serve.result_wait")


@dataclasses.dataclass
class TickSplit:
    ticks: int
    tick_s: float                  # the ticks' total time
    phase_s: dict                  # phase name -> seconds under the ticks
    found: set                     # phases with at least one span

    def mean_ms(self, phase: str):
        """A phase's seconds per tick in ms; None where the program
        records no such span (a program older than the phase)."""
        if not self.ticks or phase not in self.found:
            return None
        return self.phase_s[phase] / self.ticks * 1e3

    def self_ms(self):
        """A tick's time outside its phases, per tick in ms; None where
        the program records none of the phases."""
        if not self.ticks or not self.found:
            return None
        return (self.tick_s - sum(self.phase_s.values())) / self.ticks * 1e3


def split(spans) -> TickSplit:
    """The window's ``serve.tick`` spans and their phases' seconds."""
    by_id = {s.span_id: s for s in spans}

    def under_tick(span):
        while span is not None:
            if span.name == TICK:
                return True
            span = by_id.get(span.parent_id)
        return False

    ticks = [s for s in spans if s.name == TICK]
    phase_s = dict.fromkeys(PHASES, 0.0)
    found = set()
    for s in spans:
        if s.name in phase_s and under_tick(s):
            phase_s[s.name] += s.duration_s
            found.add(s.name)
    return TickSplit(ticks=len(ticks),
                     tick_s=sum(s.duration_s for s in ticks),
                     phase_s=phase_s, found=found)
