"""Admission prefill's share of the model's device time: executions of the
``prefill_into_pages`` program over those of it and the ``decode_step``
program, in the traced window.  The decode step is the program that runs
the paged kernel; the prefill program is the other one that runs during
step-function calls that admitted requests (``harness.trace``)."""
from harness.trace import step_programs

# the paged kernel: its source op is ".../pallas_call", its custom-call
# target "tpu_custom_call"; no other Pallas kernel runs in the window
KERNEL = ("pallas_call", "tpu_custom_call")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    runs = step_programs(t, ctx.window.steps, ctx.window.trace_t0, KERNEL)
    pre = sum(e.end - e.start for e in runs["prefill"])
    dec = sum(e.end - e.start for e in runs["decode"])
    if not runs["decode"] or pre <= 0:
        return None         # without both programs there is no share
    return 100.0 * pre / (pre + dec)
