"""moe_layer_ms: device ms per step in ops under any ``obs/`` phase scope
(gate, hash/compress, exchange, expert MLP, decompress), forward and
backward, averaged over chips."""


def read(ctx):
    t = ctx.trace.time(lambda op: op.phase is not None)
    return 1e3 * t / ctx.steps if t > 0 else None
