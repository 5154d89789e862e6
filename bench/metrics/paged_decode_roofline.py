"""Least time the paged decode attention could take for the decode tokens
received in the traced span (their KV context, query and output bytes at
the chip's HBM bandwidth; the kernel is bandwidth-bound), over the summed
device time of the kernel's events in the trace."""
from harness import measures, peaks

#: the custom call of repro.kernels.paged_attention, as the trace names it
#: (``%paged_attention.8 = ... custom-call(...)``, seen on a v5e)
KERNEL = "paged_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs = run.trace.kernel_seconds(KERNEL)
    if secs <= 0:
        return None
    t0, t1 = run.trace_span
    need = peaks.decode_attention_bytes(
        run.model, measures.decode_contexts(run, t0, t1))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / secs
