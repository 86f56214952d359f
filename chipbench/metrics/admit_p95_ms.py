"""Scheduler (admission): how long the admissions of the window took, from
their start (radix match, page reservation, prefill or extend) to the
first token's host sync, 95th percentile, in ms."""
from stage_hist import percentile_ms


def read(run):
    return percentile_ms(run, "admit_hist")
