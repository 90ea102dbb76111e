"""Set-up time: process start to the opening of the measured window —
imports, the seeded weights or table, the program's export, engine or
trainer construction, compilation (from the cache after a cell's first run)
and warm-up."""


def read(ctx):
    return ctx.get("setup_s")
