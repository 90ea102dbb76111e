"""Share of the HBM bandwidth reached by the training step's program: the
bytes one step's dense update needs (``yardstick.counts.dense_adam_bytes``
over every parameter: the table, its Adam moments, gamma and the tower)
times the runs of that program in the traced window, over the chip's HBM
bandwidth, divided by the program's device time there
(``yardstick.trace``). The step's program is the one that took the most
device time in the window."""
from yardstick import trace


def read(ctx):
    prog, nbytes = trace.main_program(ctx.get("trace")), ctx.get("step_bytes")
    if prog is None or not nbytes or not prog[1] or not prog[2]:
        return None
    _, seconds, runs = prog
    return 100.0 * nbytes * runs / ctx["peaks"]["hbm_bytes_per_s"] / seconds
