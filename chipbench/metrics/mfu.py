"""Model programs: the operations that the tokens prefilled and decoded
inside the traced span need, over the span's wall time times the chips'
bf16 peak, in %."""
from costs import extend, model


def read(run):
    if run.trace is None:
        return None
    steps, contexts = run.decode_steps_in_span()
    flops = model.tokens_flops(run.dims, steps, contexts, steps)
    flops += sum(extend.cost(run.dims, run.tied, a, b)["flops"]
                 for a, b in run.admissions_in_span())
    if flops == 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])
