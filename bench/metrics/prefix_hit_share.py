"""Share of admitted prompt tokens the decoder mapped from resident prefix
pages: window deltas of its ``shared_tokens_total`` over shared plus
``prefill_tokens_total``."""


def read(ctx):
    steps = ctx.traced_steps()
    shared = sum(s.shared_delta for s in steps)
    prefilled = sum(s.prefill_delta for s in steps)
    if shared + prefilled == 0:
        return None
    return 100.0 * shared / (shared + prefilled)
