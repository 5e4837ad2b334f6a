"""Phase-level trace annotation: gated ``jax.named_scope`` wrappers.

The MoE forward decomposes into the paper's phases —

    gate -> hash/compress -> dispatch-a2a -> expert-MLP -> combine-a2a
         -> decompress          (+ stage-transfer at pipeline boundaries)

and the rest of the train step into the step-level scopes
(``STEP_SCOPES``): the attention mixer, the LM head with its loss, and
the optimizer.  No scope nests inside another, so every op of the step
sits under at most one of them.

``phase_scope(PH_*)`` wraps each region in a ``jax.named_scope`` so the
names land in HLO op metadata and in ``jax.profiler`` traces (xplane
rows group by scope).  Activation is a TRACE-TIME decision: the scopes
are real only inside an ``activate(True)`` context, and ``nullcontext``
otherwise — named_scope changes HLO metadata, and the obs-off contract
is byte-identical HLO, so the default path must never see a scope.  The
train step (``runtime/step.py``, ``runtime/pipeline_schedule.py``)
activates from ``ObsConfig`` around its loss, gradient and update, so
the custom-VJP backwards traced during differentiation see it too; the
MoE layer activates again for when it is traced outside a step.
Library code therefore calls ``phase_scope`` unconditionally and never
threads config.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

import jax

# Phase names: the "obs/" prefix namespaces the scopes in HLO metadata /
# profiler rows and is what tests grep for.  PHASES orders them as they
# execute; obs/timeline.py uses the bare names (PREFIX stripped) for its
# wall-time attribution.
PREFIX = "obs/"
PH_GATE = PREFIX + "gate"
PH_COMPRESS = PREFIX + "hash_compress"
PH_DISPATCH = PREFIX + "dispatch_a2a"
PH_EXPERT = PREFIX + "expert_mlp"
PH_COMBINE = PREFIX + "combine_a2a"
PH_DECOMPRESS = PREFIX + "decompress"
PH_STAGE = PREFIX + "stage_transfer"
PHASES = (PH_GATE, PH_COMPRESS, PH_DISPATCH, PH_EXPERT, PH_COMBINE,
          PH_DECOMPRESS, PH_STAGE)

# Step-level scopes: the train step outside the MoE layer.  Kept apart
# from PHASES, which obs/timeline.py prices as the MoE layer's phases.
PH_ATTENTION = PREFIX + "attention"     # the attention mixer, exchanges
PH_LM_HEAD = PREFIX + "lm_head"         # final norm, unembedding, loss
PH_OPTIMIZER = PREFIX + "optimizer"     # clipping, AdamW update
STEP_SCOPES = (PH_ATTENTION, PH_LM_HEAD, PH_OPTIMIZER)

_ACTIVE: list = []              # stack of bools; [-1] is the live setting


@contextlib.contextmanager
def activate(enabled: bool = True) -> Iterator[None]:
    """Turn phase scopes on (or explicitly off) for the code traced under
    this context.  Stack-shaped so a pipeline step activating tracing
    composes with the MoE layer activating it again."""
    _ACTIVE.append(bool(enabled))
    try:
        yield
    finally:
        _ACTIVE.pop()


def active() -> bool:
    return bool(_ACTIVE) and _ACTIVE[-1]


def phase_scope(name: str):
    """``jax.named_scope(name)`` when tracing is activated, else a no-op
    context — safe to use unconditionally at every call site."""
    if active():
        return jax.named_scope(name)
    return contextlib.nullcontext()
