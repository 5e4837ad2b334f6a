"""A benchmark run with the timed path broken underneath (CPU).

Each fault is planted in the program, or for the control in the step the
program's place is taken by, and the rest of the run is the harness's
own: set-up, window, readings and the comparison.  ``run`` returns the
result object.  Run as a script (``python3 -m chipbench.tests.faults
<fault> <configuration> <traffic> <chips>``) it prints that object as
JSON, for faults that need several devices, whose count has to be fixed
before JAX starts.
"""
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

from chipbench import harness, program
from chipbench.tests.cells import make_root

SEED = 2**33 + 7


def _wrap_step(change):
    """Patch ``program.train_step`` so the harness compiles and drives
    ``change(step)`` in place of the program's step."""
    real = program.train_step

    def patched(*a, **k):
        return change(real(*a, **k))
    return mock.patch.object(program, "train_step", patched)


def _frozen(step):
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def _half_batch(step):
    def f(state, batch):
        half = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    return f


def _no_exchange():
    program.import_program()
    from repro.comm.planner import CommPlan
    return mock.patch.object(
        CommPlan, "moe_exchange",
        lambda self, send, compute_fn, codec=None: compute_fn(send))


def _control():
    """The plain reference, with float8 matrix products, in the program's
    place: its readings stand for the program's."""
    stack = contextlib.ExitStack()
    real_start = harness.Trainer.start

    def start(self, seed, batches, **kw):
        import jax
        self.control_args = (seed, [jax.device_get(b) for b in batches])
        return real_start(self, seed, batches, **kw)

    def readings(self, dev):
        seed, batches = self.control_args
        return self.reference(seed, batches, precision="fp8",
                              keep_moment="moment" in dev)

    stack.enter_context(mock.patch.object(harness.Trainer, "start", start))
    stack.enter_context(mock.patch.object(harness.Trainer, "readings",
                                          readings))
    return stack


FAULTS = {
    "none": lambda: contextlib.nullcontext(),
    "frozen_state": lambda: _wrap_step(_frozen),
    "half_batch": lambda: _wrap_step(_half_batch),
    "no_exchange": _no_exchange,
    "control": _control,
}


def run(fault: str, conf: str, mix: str, chips: int, seed: int = SEED):
    with tempfile.TemporaryDirectory() as tmp:
        root = make_root(Path(tmp), [("t", conf, mix, chips)])
        with FAULTS[fault]():
            res, _ = harness.run("t", seed, 0.2, False,
                                 t_start=time.perf_counter(),
                                 require_tpu=False, root=root)
    return res


if __name__ == "__main__":
    print(json.dumps(run(sys.argv[1], sys.argv[2], sys.argv[3],
                         int(sys.argv[4]))))
