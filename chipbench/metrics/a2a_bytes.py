"""a2a_bytes: MB (1e6 bytes) per step that each chip hands to all-to-all
ops: the operand bytes of every executed all-to-all (of the start op
where the compiler splits one into start and done), averaged over
chips."""

SENDS = ("all-to-all", "all-to-all-start")


def read(ctx):
    pred = lambda op: op.opcode in SENDS  # noqa: E731
    if not ctx.trace.count(pred):
        return None
    return ctx.trace.sum(pred, lambda op: op.operand_bytes()) / 1e6 \
        / ctx.steps
