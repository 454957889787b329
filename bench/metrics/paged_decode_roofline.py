"""The paged decode attention kernel's share of its roofline: the least
time the chip needs for the kernel's work in the traced window (the larger
of its FLOPs over peak FLOP/s and its bytes over peak bandwidth) over the
kernel's device time there.  The work is the algorithm's, from the shapes
(``work/paged_decode_attention.py``): one call per layer of every decode
step, over the rows that decode, at their valid lengths."""
from work import paged_decode_attention as pda

# the paged kernel: its source op is ".../pallas_call", its custom-call
# target "tpu_custom_call"; no other Pallas kernel runs in the window
KERNEL = ("pallas_call", "tpu_custom_call")


def read(ctx):
    t, pk = ctx.trace, ctx.peaks
    if t is None or pk is None:
        return None
    secs = 1e-9 * sum(e.end - e.start for e in t.matching_ops(*KERNEL))
    if secs <= 0:
        return None
    c = ctx.cfg
    flops = nbytes = 0
    for s in ctx.traced_steps():
        if s.decode_ctx:
            f, b = pda.call_work(s.decode_ctx, c["num_attention_heads"],
                                 c["num_key_value_heads"], c["head_dim"])
            flops += f * c["num_hidden_layers"]
            nbytes += b * c["num_hidden_layers"]
    if flops == 0:
        return None
    least, _bound = pda.roofline_seconds(flops, nbytes,
                                         pk["bf16_flops_per_s"],
                                         pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
