"""unscoped_ms: device ms per step in ops under no ``obs/`` scope
(embedding, the norms and residual adds around the blocks, and what the
partitioner adds without metadata), averaged over chips.  Read only
where the trace carries a step-level scope: a program that scoped only
the MoE layer says nothing about what the step leaves unnamed.  With
``moe_layer_ms``, ``attention_ms``, ``lm_head_ms`` and ``optimizer_ms``
it sums every op of the step."""
import re

OBS = re.compile(r"\bobs/")
STEP = re.compile(r"\bobs/(attention|lm_head|optimizer)\b")


def read(ctx):
    if not ctx.trace.count(lambda op: STEP.search(op.scope) is not None):
        return None
    t = ctx.trace.time(lambda op: OBS.search(op.scope) is None)
    return 1e3 * t / ctx.steps
