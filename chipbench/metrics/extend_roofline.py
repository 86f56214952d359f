"""Model programs: the paged extend programs' share of their roofline, in
%. The least time the chip needs for the admissions that began and ended
inside the traced span (``costs/extend.py``: the larger of their
operations over peak and their bytes over bandwidth, per admission), over
the extend programs' device time in the span. It counts the work, not the
implementation."""
from costs import extend

PROGRAM = "_extend_paged_fn"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    adm = run.admissions_in_span()
    if t <= 0 or not adm:
        return None
    pk = run.peaks
    least = 0.0
    for start, stop in adm:
        c = extend.cost(run.dims, run.tied, start, stop)
        least += max(c["flops"] / pk["bf16_flops_per_s"],
                     c["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
