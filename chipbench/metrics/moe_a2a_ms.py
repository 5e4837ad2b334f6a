"""moe_a2a_ms: device ms per step in the MoE exchange's all-to-all ops,
those under ``obs/dispatch_a2a`` or ``obs/combine_a2a`` (forward,
rematerialised forward and backward), averaged over chips.  The
attention's and the LM head's all-to-alls are not counted."""
import re

from chipbench.trace import A2A_OPCODES

LEGS = re.compile(r"\bobs/(dispatch_a2a|combine_a2a)\b")


def moe_a2a(op) -> bool:
    return op.opcode in A2A_OPCODES and LEGS.search(op.scope) is not None


def read(ctx):
    if not ctx.trace.count(moe_a2a):
        return None
    return 1e3 * ctx.trace.time(moe_a2a) / ctx.steps
