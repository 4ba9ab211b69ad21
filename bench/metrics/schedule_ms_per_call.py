"""Scheduler layer (``core/scheduler.py`` through ``serve/cluster.py``
``ClusterServer.serve``): host ms per serve call in the program's
``serve.schedule`` span (admission, offers, drain and the schedule's
finish), from the profiler trace that carries the span. No reading where
the program has no such span."""

SPAN = "serve.schedule"


def read(run):
    view = run.trace
    if view is None:
        return None
    calls = sum(s[2] == "bench.serve" for s in view.spans)
    spans = [h[1] - h[0] for h in view.host
             if h[2] == SPAN and h[0] >= view.t0 and h[1] <= view.t1]
    if not calls or not spans:
        return None
    return 1e-6 * sum(spans) / calls
