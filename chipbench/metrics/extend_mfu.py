"""Model programs: the operations the admissions inside the traced span
need, over the extend programs' device time times the chip's bf16 peak,
in % (the whole extend program's share of the peak, beside
``extend_roofline``)."""
from costs import extend

PROGRAM = "_extend_paged_fn"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    adm = run.admissions_in_span()
    if t <= 0 or not adm:
        return None
    flops = sum(extend.cost(run.dims, run.tied, a, b)["flops"]
                for a, b in adm)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
