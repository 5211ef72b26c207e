"""Share of a logdet's device time, in percent, in the engine's
trailing-update scopes (``core/engine.py``): the ops whose named-scope
path holds one of ``SCOPES``, over all device op time of the window."""

SCOPES = ("engine.update", "engine.panel_apply", "engine.fused_step")


def read(ctx):
    def in_update(op):
        return any(s in SCOPES for s in op.scope.split("/"))

    total = ctx.trace.op_seconds()
    update = ctx.trace.op_seconds(in_update)
    if total <= 0 or update <= 0:
        return None
    return 100.0 * update / total
