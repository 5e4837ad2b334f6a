"""Per-layer metric readers, one file per metric of BENCHMARK.json.

Each file defines ``read(ctx)``, which returns the metric's value or
None where the trace holds nothing to read.  ``ctx`` carries the reduced
trace (``chipbench.trace.Trace``), the window's steps and tokens, the
chip count, the chip's peaks and the model FLOPs per trained token.
"""
