"""Share of a logdet's device time, in percent, in the engine's column-swap
loop, the scope ``engine.panel_swap`` (``apply_panel`` in
``core/engine.py``), over all device op time of the window.

An op counts when its named-scope path holds that scope.  XLA adds
copies of a loop's carry inside the loop's body with no scope of their
own (an empty ``tf_op``), and the trace gives a ``while`` op no scope
either.  So a ``while`` op's event, which holds its body's ops, is the
swap loop when every scoped op directly in its body has a path that
holds the scope before its last ``/while/body/``; that ``while`` op and
the unscoped ops directly in its body count too.  Ops nest in time on
one device's line only, so the share is read in one-chip cells.
"""

SCOPE = "engine.panel_swap"
BODY = "/while/body/"


def _in_scope(path: str) -> bool:
    return SCOPE in path.split("/")


def read(ctx):
    trace = ctx.trace
    if trace.devices != 1:
        return None
    ops = sorted(trace.ops, key=lambda o: (o.start, -o.end))
    loop_of, stack = [None] * len(ops), []
    for i, op in enumerate(ops):
        while stack and ops[stack[-1]].end <= op.start:
            stack.pop()
        loop_of[i] = next((j for j in reversed(stack)
                           if ops[j].category == "while"), None)
        stack.append(i)
    # a loop is the swap loop while no scoped op of its body says no
    verdict = {}
    for i, op in enumerate(ops):
        j = loop_of[i]
        if j is not None and BODY in op.scope:
            here = _in_scope(op.scope.rsplit(BODY, 1)[0])
            verdict[j] = verdict.get(j, True) and here
    swap = {j for j, yes in verdict.items() if yes}

    def counted(i, op):
        return (_in_scope(op.scope) or i in swap
                or (not op.scope and loop_of[i] in swap))

    total = trace.op_seconds()
    share = sum(op.own for i, op in enumerate(ops) if counted(i, op)) * 1e-9
    if total <= 0 or share <= 0:
        return None
    return 100.0 * share / total
