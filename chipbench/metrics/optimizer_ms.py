"""optimizer_ms: device ms per step in ops under ``obs/optimizer``
(gradient clipping, the learning-rate schedule and the AdamW update),
averaged over chips."""
import re

SCOPE = re.compile(r"\bobs/optimizer\b")


def read(ctx):
    t = ctx.trace.time(lambda op: SCOPE.search(op.scope) is not None)
    return 1e3 * t / ctx.steps if t > 0 else None
