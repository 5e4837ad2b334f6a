"""The plain float32 reference's architecture-free half: Adam, the step,
the precisions, and the readings the check compares.

A model module (``chipbench/models/<name>.py``, named by the
configuration file's ``"model"``) gives the architecture: its weights
and its loss, written from the configuration file and the published
description in straightforward ``jax.numpy``: no kernels, no caches, and
nothing imported from the program under test.  ``run`` spreads the
weights and Adam moments over the cell's chips (each leaf split along
one axis, ``spread``) so that a deep model fits; the arithmetic is the
same on one chip or many.  Matrix products run at
``jax.default_matmul_precision("highest")``; weights are stored in the
type the configuration states (bf16, router in f32) and rounded to it
after every update, as the program stores them, and the Adam moments are
f32.

``precision="fp8"`` is the control, the reference computed a precision
below the configuration's bf16: every activation it holds (the residual
stream, each matrix product's operands and result) and each of their
gradients is rounded to float8 e4m3 with one power-of-two scale per
tensor, where the plain reference keeps float32 (``_held``, ``_mm``).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

F32 = jnp.float32
E4M3_MAX = 448.0


class Adam(NamedTuple):
    lr: float
    warmup_steps: int
    total_steps: int
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float


def adam_from_config(c: Dict) -> Adam:
    o = c["optimizer"]
    return Adam(lr=o["lr"], warmup_steps=o["warmup_steps"],
                total_steps=o["total_steps"], b1=o["b1"], b2=o["b2"],
                eps=o["eps"], weight_decay=o["weight_decay"],
                clip_norm=o["clip_norm"])


# ----------------------------------------------------------- helpers ----

def _round_fp8(x):
    """x rounded to float8 e4m3 with one power-of-two scale for the
    tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / E4M3_MAX)))
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    """x held in float8 e4m3, and its gradient too."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


def _held(x, precision: str):
    """An activation as the reference holds it between operations: float32,
    or float8 for the control."""
    return _fp8(x) if precision == "fp8" else x


def _mm(spec: str, a, b, precision: str):
    a, b = _held(a.astype(F32), precision), _held(b.astype(F32), precision)
    return _held(jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST),
                 precision)


# --------------------------------------------------------------- step ---

def _lr(step, o: Adam):
    s = step.astype(F32)
    warm = o.lr * (s + 1.0) / max(1, o.warmup_steps)
    prog = jnp.clip((s - o.warmup_steps)
                    / max(1, o.total_steps - o.warmup_steps), 0.0, 1.0)
    cos = o.lr * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(s < o.warmup_steps, warm, cos)


def train_step(loss_fn, params, m, v, step, tokens, labels, d, o: Adam,
               groups: int, precision: str = "f32", fault: str = ""):
    """AdamW on the model module's ``loss_fn``, with global-norm clipping
    and decoupled weight decay on every float leaf.  Gradients are taken
    with respect to float32 copies of the weights, and the updated
    weights are rounded back to their stored type."""
    wide = jax.tree.map(lambda p: p.astype(F32)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params)
    loss, g = jax.value_and_grad(loss_fn, allow_int=True)(
        wide, tokens, labels, d, groups, precision, fault)
    floats = lambda t: [x for x in jax.tree.leaves(t)
                        if jnp.issubdtype(x.dtype, jnp.floating)]
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(F32))) for x in floats(g)))
    clip = jnp.minimum(1.0, o.clip_norm / jnp.maximum(gn, 1e-9))
    lr = _lr(step, o)
    t = (step + 1).astype(F32)
    bc1, bc2 = 1.0 - o.b1 ** t, 1.0 - o.b2 ** t

    def upd(p, gr, mm, vv):
        if not jnp.issubdtype(p.dtype, jnp.floating):
            return p, mm, vv
        gr = gr.astype(F32) * clip
        mm = o.b1 * mm + (1 - o.b1) * gr
        vv = o.b2 * vv + (1 - o.b2) * gr * gr
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + o.eps) \
            + o.weight_decay * p.astype(F32)
        return (p.astype(F32) - lr * u).astype(p.dtype), mm, vv

    out = jax.tree.map(upd, params, g, m, v)
    pick = lambda i: jax.tree.map(lambda _, o3: o3[i], params, out)
    return pick(0), pick(1), pick(2), loss


def zeros_moments(params):
    """f32 zeros for each float leaf; a scalar stands in for an integer
    leaf, which the optimiser does not move."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, F32)
                        if jnp.issubdtype(p.dtype, jnp.floating)
                        else jnp.zeros((), jnp.int32), params)


def leaf_names(params):
    """Stable names of the float leaves, e.g. ``blocks/0/ffn/w_up``."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        if jnp.issubdtype(x.dtype, jnp.floating):
            out.append("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in path))
    return out


def float_leaf_norms(tree):
    """[norm of each float leaf] in ``leaf_names`` order (f32)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)
                      if jnp.issubdtype(x.dtype, jnp.floating)])


def change_norms(params, params0):
    """Norm of each float leaf's change from ``params0``."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)
                                                  - b.astype(F32))))
                      for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(params0))
                      if jnp.issubdtype(a.dtype, jnp.floating)])


def host_floats(tree):
    """The float leaves of ``tree`` as float32 host arrays."""
    import numpy as np
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)
            if jnp.issubdtype(x.dtype, jnp.floating)]


def spread(shapes, devices):
    """Shardings that split each leaf of ``shapes`` over ``devices`` along
    its largest axis that divides evenly (never the layer axis of a
    stacked block leaf); a leaf with none is kept whole on each."""
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def one(path, x):
        first = 1 if any(getattr(k, "key", None) == "blocks"
                         for k in path) else 0
        axes = [a for a in range(first, x.ndim) if x.shape[a] % n == 0]
        if n == 1 or not axes:
            return NamedSharding(mesh, P())
        a = max(axes, key=lambda i: x.shape[i])
        return NamedSharding(mesh, P(*[None] * a, "x"))
    return jax.tree_util.tree_map_with_path(one, shapes)


def run(model, key, batches, d, o: Adam, groups: int, steps: int = 3,
        precision: str = "f32", fault: str = "",
        keep_moment: bool = False, devices=None) -> Dict:
    """The reference's readings over the first ``steps`` steps of the
    model module ``model`` (``d``: its ``dims``) from its seeded weights:
    each step's loss, each float leaf's norm of the first gradient as the
    optimiser takes it (clipped, recovered from the first moment), and
    each float leaf's change over the ``steps`` steps.
    Weights and moments are spread over ``devices`` (default: the first
    device); batches are whole on each."""
    devices = list(devices or jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        shapes = jax.eval_shape(partial(model.init_params, d=d), key)
        p_sh = spread(shapes, devices)
        m_sh = spread(jax.eval_shape(zeros_moments, shapes), devices)
        whole = NamedSharding(Mesh(np.array(devices), ("x",)), P())
        init = jax.jit(partial(model.init_params, d=d), out_shardings=p_sh)
        params = init(key)
        m = jax.jit(zeros_moments, out_shardings=m_sh)(params)
        v = jax.jit(zeros_moments, out_shardings=m_sh)(params)
        step = jax.jit(partial(train_step, model.loss_fn, d=d, o=o,
                               groups=groups, precision=precision,
                               fault=fault),
                       donate_argnums=(0, 1, 2),
                       out_shardings=(p_sh, m_sh, m_sh, whole))
        losses, grad, extra = [], None, {}
        for i in range(steps):
            b = jax.device_put(batches[i], whole)
            params, m, v, loss = step(params, m, v, jnp.int32(i),
                                      b["tokens"], b["labels"])
            losses.append(loss)
            if i == 0:
                grad = jax.jit(float_leaf_norms)(m) / (1 - o.b1)
                if keep_moment:
                    extra["moment"] = host_floats(m)
        change = jax.jit(lambda p, k: change_norms(p, init(k)))(params, key)
        return {"losses": [float(x) for x in losses],
                "grad_norms": [float(x) for x in grad],
                "change_norms": [float(x) for x in change],
                "leaves": leaf_names(params), **extra}
