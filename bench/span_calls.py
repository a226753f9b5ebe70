"""The window's ``sweep.run`` calls as the program's own spans record them
(``repro.spans``), for the per-layer readers that read spans.

Readers run in the benchmark's process after the window and before the
reference check, which calls no ``sweep.run``. So the window's calls are
the last ``ctx.calls`` completed ``fg.sweep`` roots that did not fail; the
warm-up call's root precedes them. Where the program records no spans, or
the ring holds fewer roots than the window's calls, there is nothing to
read and the readers return ``None``.
"""

from __future__ import annotations

ROOT = "fg.sweep"
PULL = "fg.sweep.pull"


def window_calls(ctx):
    """``[(root, its spans)]`` of the window's calls, oldest first, or
    ``None``."""
    try:
        from repro import spans
    except ImportError:          # a program without spans
        return None
    roots = [s for s in spans.recent(ROOT)
             if s.parent is None and not s.failed]
    if ctx.calls < 1 or len(roots) < ctx.calls:
        return None
    return [(r, spans.tree(r)) for r in roots[-ctx.calls:]]


def pull_ns(tree) -> int:
    """Nanoseconds the call's host spent in its pulls, blocked on the
    device."""
    return sum(s.ns for s in tree if s.name == PULL)
