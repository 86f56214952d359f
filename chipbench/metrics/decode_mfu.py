"""Model programs: the operations the decode steps inside the traced span
need (every layer, attention over the real context, logits of each
sampled token), over the decode programs' device time times the chip's
bf16 peak, in % (the whole decode step's share of the peak, beside
``paged_decode_roofline``)."""
from costs import model

PROGRAM = "_decode_chunk_fn"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    steps, contexts = run.decode_steps_in_span()
    if t <= 0 or steps == 0:
        return None
    flops = model.tokens_flops(run.dims, steps, contexts, steps)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
