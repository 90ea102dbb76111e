"""The training step's share of the chip's bf16 peak: one step's tower
forward and backward FLOPs (the model's ``train_flops`` per sample times
the batch) times the runs of the step's program in the traced window, over
the peak, divided by the program's device time there
(``yardstick.trace``). The step's program is the one that took the most
device time in the window."""
from yardstick import trace


def read(ctx):
    prog, flops = trace.main_program(ctx.get("trace")), ctx.get("step_flops")
    if prog is None or not flops or not prog[1] or not prog[2]:
        return None
    _, seconds, runs = prog
    return 100.0 * flops * runs / ctx["peaks"]["bf16_flops"] / seconds
