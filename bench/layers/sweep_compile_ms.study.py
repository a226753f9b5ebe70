"""Milliseconds per ``sweep.run`` call of tracing, lowering, backend
compilation and persistent-cache loads (program counters on the call's
spans), the mean over the window's calls; about 0 when no call
recompiles."""

from statistics import fmean

from bench.span_calls import window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    from repro import spans

    return fmean(spans.compile_seconds(t) * 1e3 for _, t in calls)
