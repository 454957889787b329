"""Process start to the first timed request: initialisation, weights from
the seed on the device, every compile and the warm-up."""


def read(ctx):
    return ctx.run.setup_s
