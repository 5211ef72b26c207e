"""Share of a gradient step's device time, in percent, outside the
forward's compiled program: ``LogdetPlan``'s forward runs as the program
``jit_fwd`` (the jitted ``fwd`` of ``core/plan.py``), and everything else
the step runs on the device is the backward."""

FORWARD = "jit_fwd"


def read(ctx):
    total = ctx.trace.op_seconds()
    forward = ctx.trace.op_seconds(lambda op: op.module == FORWARD)
    if total <= 0 or forward <= 0:
        return None
    return 100.0 * (total - forward) / total
