"""routing_ms: device ms per step in the routing kernels (the dispatch
scatter and combine gather, and their fused codec twins), forward and
backward, averaged over chips.  Kernel calls are named after their jitted
Pallas wrapper; this table maps the wrappers to the registry ops."""

KERNELS = {"dispatch_scatter_pallas": "dispatch_scatter",
           "combine_gather_pallas": "combine_gather",
           "dispatch_scatter_quantize_pallas": "dispatch_scatter_quantize",
           "dequantize_combine_gather_pallas": "dequantize_combine_gather"}


def read(ctx):
    t = ctx.trace.time(lambda op: op.kernel in KERNELS)
    return 1e3 * t / ctx.steps if t > 0 else None
