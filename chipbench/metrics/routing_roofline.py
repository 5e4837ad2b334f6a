"""routing_roofline: the routing kernels' share of their roofline, in %.

The least time is each call's interface bytes (its operands read once and
its results written once, from the shapes of the call) over the chip's
HBM bandwidth; routing does no arithmetic that counts, so bytes bound it.
The share is that least time over the calls' measured time."""

from chipbench.metrics.routing_ms import KERNELS


def read(ctx):
    pred = lambda op: op.kernel in KERNELS  # noqa: E731
    t = ctx.trace.time(pred)
    if t <= 0:
        return None
    least = ctx.trace.sum(pred, lambda op: op.interface_bytes()) \
        / ctx.peaks.hbm_bytes_per_s
    return 100.0 * least / t
