"""setup_s: process start to the first timed step: imports, device start,
compiles (or reads from the persistent cache), weights and AdamW state from
the seed, and the first three steps that the comparison reads."""


def read(ctx):
    return ctx.setup_s
