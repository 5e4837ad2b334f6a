"""lsh_kernels_ms: device ms per step in the LSH kernels, averaged over
chips.  Kernel calls are named after their jitted Pallas wrapper; this
table maps the wrappers to the registry ops they implement."""

KERNELS = {"lsh_hash_pallas": "lsh_hash",
           "segment_centroid_pallas": "segment_centroid",
           "residual_apply_pallas": "residual_apply",
           "dequantize_residual_apply_pallas": "dequantize_residual_apply"}


def read(ctx):
    t = ctx.trace.time(lambda op: op.kernel in KERNELS)
    return 1e3 * t / ctx.steps if t > 0 else None
