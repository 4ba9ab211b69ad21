"""Admission layer (``serve/cluster.py`` ``ClusterServer``): median wait,
in ms, from when a request was due until the serve call that admitted it
started, on the benchmark's clock. The server takes one batch per call
and returns when the batch is placed and dispatched, so the wait is the
time a request queues behind the call in progress: batching, a shorter
call or a server that admits while it executes moves it."""
import statistics


def read(run):
    waits = [1e3 * (r.start_s - r.due_s) for r in run.completed]
    return statistics.median(waits) if waits else None
