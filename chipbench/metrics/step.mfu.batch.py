"""Model FLOP/s utilization of the decode step: model FLOPs of the
tokens the traced window's decode ticks emitted (from the shapes, each
at its context) over the device time of the decode program inside the
traced window and the chip's bf16 peak.  Prefill is left out of both."""

from chipbench.record import decode_model_flops

DECODE_PROGRAM = "jit_decode_fn"    # the engine's decode step in the trace


def read(run):
    if run.trace is None:
        return None
    secs = run.trace["module_s"].get(DECODE_PROGRAM)
    if not secs:
        return None
    lo, hi = run.traced
    flops = decode_model_flops(run, lo, hi)
    return 100.0 * flops / secs / run.peaks["bf16_flops_per_s"] or None
