"""Share of the chip's bf16 peak that the served work needed in the traced
span: forward FLOPs of the prompt positions prefilled (no cached or
recomputed ones) and of the decode tokens received, each over its actual
context, plus one logit row per token received; padded rows do not count."""
from harness import measures, peaks


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t0, t1 = run.trace_span
    flops = peaks.forward_flops(run.model, run.prefill_spans,
                                measures.decode_contexts(run, t0, t1),
                                measures.logit_rows(run, t0, t1))
    return 100.0 * flops / ((t1 - t0) * run.peaks["bf16_flops"])
