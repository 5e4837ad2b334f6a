"""a2a_exposed_ms: device ms per step in all-to-all ops during which no
other op runs on that chip, averaged over chips."""

from chipbench.trace import A2A_OPCODES


def read(ctx):
    pred = lambda op: op.opcode in A2A_OPCODES  # noqa: E731
    if not ctx.trace.count(pred):
        return None
    return 1e3 * ctx.trace.exposed(pred) / ctx.steps
