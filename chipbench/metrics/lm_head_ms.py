"""lm_head_ms: device ms per step in ops under ``obs/lm_head`` (the final
norm, the unembedding and its exchange, the cross-entropy, z-loss and
router aux terms), forward and backward, averaged over chips."""
import re

SCOPE = re.compile(r"\bobs/lm_head\b")


def read(ctx):
    t = ctx.trace.time(lambda op: SCOPE.search(op.scope) is not None)
    return 1e3 * t / ctx.steps if t > 0 else None
