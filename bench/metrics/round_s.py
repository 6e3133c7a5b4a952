"""round_s: seconds per federated round over the measured window.

The window's wall time over the rounds completed in it, evaluations
included (host clock; every round ends on the device before the next
starts)."""


def read(ctx):
    return ctx.window["wall_s"] / ctx.window["rounds"]
