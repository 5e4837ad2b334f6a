"""moe_a2a_bytes: MB (1e6 bytes) per step that each chip hands to the MoE
exchange's all-to-alls (``moe_a2a_ms``'s ops): the operand bytes of each
send, averaged over chips.  ``a2a_bytes`` counts every all-to-all."""
from chipbench.metrics.a2a_bytes import SENDS
from chipbench.metrics.moe_a2a_ms import LEGS


def moe_send(op) -> bool:
    return op.opcode in SENDS and LEGS.search(op.scope) is not None


def read(ctx):
    if not ctx.trace.count(moe_send):
        return None
    return ctx.trace.sum(moe_send, lambda op: op.operand_bytes()) / 1e6 \
        / ctx.steps
