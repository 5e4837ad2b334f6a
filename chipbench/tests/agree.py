"""The plain reference against the program at a small size on the CPU.

Both run in float32, the LSH wire too, from the same weights and batch:
the program's loss and gradients (``models/model.py``, with its kernels,
routing, LSH and, on several devices, its all-to-all) and the
reference's.  Run as a script
(``python3 -m chipbench.tests.agree <configuration> <traffic>``) it prints
the gaps as JSON, for the several-device case, whose device count has to
be fixed before JAX starts.
"""
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, program, reference
from chipbench.tests.cells import BENCH, DATA
from chipbench.traffic import Traffic


def gaps(conf_name: str, mix_name: str, seed: int = 3) -> dict:
    conf_path = DATA / f"{conf_name}.json"
    conf = json.loads(conf_path.read_text())
    mix = json.loads((DATA / f"{mix_name}.json").read_text())
    model = harness.model_module(conf, conf_path, BENCH / "models")
    program.import_program()
    from repro.models import model as model_lib
    use_lsh = mix["use_lsh"]
    cfg = program.model_config(conf, model, phases=False)
    lsh = dataclasses.replace(cfg.moe.lsh, wire_dtype="float32")
    cfg = cfg.replace(dtype="float32",
                      moe=dataclasses.replace(cfg.moe, lsh=lsh))
    mesh = program.mesh(conf)
    d = model.dims(conf, use_lsh=use_lsh)._replace(wire_dtype="float32")
    wide = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        model.init_params(jax.random.PRNGKey(seed), d))
    b = {k: jnp.asarray(v) for k, v in
         Traffic(mix, d.vocab, seed).batch_at(0).items()}

    def prog_loss(p):
        return model_lib.loss_fn(p, cfg, mesh, b, use_lsh=use_lsh)[0]

    def ref_loss(p):
        return model.loss_fn(p, b["tokens"], b["labels"], d,
                             conf["mesh"]["model"])

    with jax.default_matmul_precision("highest"):
        with program.set_mesh(mesh):
            lp, gp = jax.jit(jax.value_and_grad(prog_loss,
                                                allow_int=True))(wide)
        lr, gr = jax.jit(jax.value_and_grad(ref_loss, allow_int=True))(wide)
    norms = reference.float_leaf_norms
    diff = norms(jax.tree.map(
        lambda a, c: a.astype(jnp.float32) - c.astype(jnp.float32)
        if jnp.issubdtype(c.dtype, jnp.floating) else c, gp, gr))
    ref_n = np.asarray(norms(gr))
    floor = max(float(np.median(ref_n)), 1e-30)
    return {"loss": float(lp), "reference_loss": float(lr),
            "loss_gap": abs(float(lp) - float(lr)) / abs(float(lr)),
            "grad_gap": float(np.max(np.asarray(diff)
                                     / np.maximum(ref_n, floor))),
            "devices": len(jax.devices())}


if __name__ == "__main__":
    print(json.dumps(gaps(sys.argv[1], sys.argv[2])))
