"""Training samples stepped in the window, over the window, the last step
blocked on."""


def read(ctx):
    return ctx.get("samples_per_s")
