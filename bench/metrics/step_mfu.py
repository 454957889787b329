"""Model FLOPs the served path did in the traced window over what the
chip's bf16 peak could do in that window.

Counted work: every prefilled tail token (attending to its shared prefix
and the tail before it) and every decoded token of a request, with their
logits; padding rows and positions, idle pool rows and shared prefix
tokens are not work.  Which prefix pages each admission shared is the
harness's reading, held against the decoder's counter (a run where they
disagree is not correct).
"""
from work import dense_gqa


def read(ctx):
    t, pk = ctx.trace, ctx.peaks
    if t is None or pk is None or t.window_s <= 0:
        return None
    c = ctx.cfg
    flops = 0
    for s in ctx.traced_steps():
        for tail, base in s.prefill_rows:
            flops += dense_gqa.prefill_flops(c, tail, base)
        for n_valid in s.decode_ctx:
            flops += dense_gqa.decode_flops(c, n_valid)
    if flops == 0:
        return None
    return 100.0 * flops / (t.window_s * pk["bf16_flops_per_s"])
