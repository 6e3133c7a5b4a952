"""setup_s: seconds from process start to the first timed round.

Import and chip initialisation, the generated data, building the
program's run, and the warm-up rounds with their compiles (host
clock)."""


def read(ctx):
    return ctx.setup_s
