"""expert_mlp_ms: device ms per step in ops under ``obs/expert_mlp``,
forward and backward, averaged over chips."""


def read(ctx):
    t = ctx.trace.time(lambda op: op.phase == "obs/expert_mlp")
    return 1e3 * t / ctx.steps if t > 0 else None
