"""attention_ms: device ms per step in ops under ``obs/attention`` (the
attention mixer: its projections and the sequence<->head exchanges it
issues), forward, rematerialised forward and backward, averaged over
chips."""
import re

SCOPE = re.compile(r"\bobs/attention\b")


def read(ctx):
    t = ctx.trace.time(lambda op: SCOPE.search(op.scope) is not None)
    return 1e3 * t / ctx.steps if t > 0 else None
