"""Scheduler (admission queue): how long the requests admitted in the
window waited from their engine enqueue to the start of the admission
attempt that succeeded, 95th percentile, in ms."""
from stage_hist import percentile_ms


def read(run):
    return percentile_ms(run, "queue_wait_hist")
