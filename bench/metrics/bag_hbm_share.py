"""Share of the HBM bandwidth reached by the multi-hot embedding bag: the
bag's counted bytes a step (the model's ``bag_bytes(cfg, mix)``: rows
read, the scatter-add's read and write of them, their group ids and width
probabilities read) times the runs of the step's program in the traced
window, over the chip's HBM bandwidth, divided by that program's device
time there (``yardstick.trace``). It divides by the whole step's time, not
the bag's, so for now it is a fixed multiple of ``train_hbm_share``: once
the benchmark reads the step's time by part, the bag's own parts (gather,
quantize, table gradient) can be the divisor instead. The step's program
is the one that took the most device time in the window. A configuration
whose model counts no bag bytes reads nothing."""
from yardstick import spec, trace


def read(ctx):
    prog, cfg = trace.main_program(ctx.get("trace")), ctx.get("cfg")
    if prog is None or cfg is None or not prog[1] or not prog[2]:
        return None
    count = getattr(spec.load_module("models", cfg["model"]), "bag_bytes",
                    None)
    if count is None:
        return None
    _, seconds, runs = prog
    return (100.0 * count(cfg, ctx["traffic"]) * runs
            / ctx["peaks"]["hbm_bytes_per_s"] / seconds)
