"""Host milliseconds per ``sweep.run`` call outside its pulls (program
spans): the ``fg.sweep`` root's duration less its ``fg.sweep.pull``
children, the mean over the window's calls."""

from statistics import fmean

from bench.span_calls import pull_ns, window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    return fmean((r.ns - pull_ns(t)) * 1e-6 for r, t in calls)
