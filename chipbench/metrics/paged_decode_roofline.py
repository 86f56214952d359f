"""Kernels: the Pallas paged decode attention kernel's share of its
roofline, in %. The least time for the K/V of the real contexts of the
decode steps inside the traced span (``costs/paged_decode.py``), over the
kernel's summed device time in the span. In paged serving the kernel is
the one Pallas call (``tpu_custom_call``) of the decode program."""
from costs import paged_decode

KERNEL, PROGRAM = "tpu_custom_call", "_decode_chunk_fn"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_time(KERNEL, PROGRAM)
    steps, contexts = run.decode_steps_in_span()
    if t <= 0 or steps == 0:
        return None
    pk = run.peaks
    c = paged_decode.cost(run.dims, contexts, steps)
    least = max(c["flops"] / pk["bf16_flops_per_s"],
                c["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
