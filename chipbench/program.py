"""The benchmark's one door into the program under test (``src/repro``).

Everything the harness takes from the program goes through here: its
config, mesh, train state, train step and parameter shardings.  The
configuration file is the yardstick: the program's config must state the
same sizes and LSH parameters, or the run is refused.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[1]
ENV_PREFIX = "REPRO_"


class Refused(RuntimeError):
    """The run cannot be measured as asked: nothing is printed."""


def refuse_program_env() -> None:
    """``REPRO_*`` variables switch kernels, tiles, transports and fault
    injection inside the program; a measured run takes none."""
    found = sorted(k for k in os.environ if k.startswith(ENV_PREFIX))
    if found:
        raise Refused(f"program switches set in the environment: {found}")


def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro  # noqa: F401
    except ImportError as exc:
        raise Refused(f"the program (src/repro) is not in this checkout: "
                      f"{exc}") from None


_LSH_KEYS = ("hash_type", "num_hashes", "rotation_dim", "compression_rate",
             "wire_format", "wire_dtype", "error_compensation")


def _mismatches(cfg, conf: Dict, program_keys: Dict):
    out = []
    for key, get in program_keys.items():
        if key not in conf["config"]:
            continue
        try:
            have = get(cfg)
        except AttributeError:           # a field the program dropped
            continue
        if have != conf["config"][key]:
            out.append(f"{key}: program {have!r}, file "
                       f"{conf['config'][key]!r}")
    for key in _LSH_KEYS:
        if key in conf["lsh"] and hasattr(cfg.moe.lsh, key) \
                and getattr(cfg.moe.lsh, key) != conf["lsh"][key]:
            out.append(f"lsh.{key}: program {getattr(cfg.moe.lsh, key)!r}, "
                       f"file {conf['lsh'][key]!r}")
    return out


def model_config(conf: Dict, model, *, phases: bool):
    """The program's config for this configuration: its registered arch
    (``program.preset``: "full", or "smoke" for the CPU tests) with the
    file's cuts applied (the keys of ``reduced`` that the model module's
    ``CUTS`` can change), checked against its ``PROGRAM_KEYS``.
    ``phases`` turns on the ``obs/`` phase scopes (HLO metadata only).
    Refuses where any other size or LSH parameter the file states differs
    from the program's."""
    from repro.configs.registry import get_config, get_smoke_config

    get = {"full": get_config, "smoke": get_smoke_config}[
        conf["program"].get("preset", "full")]
    cfg = get(conf["program"]["arch"])
    for key in conf["reduced"]:
        if key in model.CUTS:
            cfg = model.CUTS[key](cfg, conf["config"][key])
    model_r = conf["mesh"]["model"]
    if cfg.moe.num_experts % model_r:
        raise Refused(f"{cfg.moe.num_experts} experts do not divide over "
                      f"{model_r} chips")
    bad = _mismatches(cfg, conf, model.PROGRAM_KEYS)
    if bad:
        raise Refused("the program's config differs from "
                      f"{conf['name']}: " + "; ".join(bad))
    if phases:
        obs = dataclasses.replace(cfg.moe.obs, enabled=True, phases=True,
                                  metrics=False)
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, obs=obs))
    return cfg


def optimizer_config(conf: Dict):
    from repro.configs.base import OptimizerConfig
    o = conf["optimizer"]
    return OptimizerConfig(lr=o["lr"], warmup_steps=o["warmup_steps"],
                           total_steps=o["total_steps"], b1=o["b1"],
                           b2=o["b2"], eps=o["eps"],
                           weight_decay=o["weight_decay"],
                           clip_norm=o["clip_norm"], moment_dtype="float32")


def mesh(conf: Dict):
    from repro.launch.mesh import make_host_mesh
    m = conf["mesh"]
    return make_host_mesh(m["data"], 1, m["model"])


def batch_spec(mesh_):
    """How the program shards a [batch, seq] input (``runtime/sharding``)."""
    from repro.runtime.sharding import resolve
    return tuple(resolve(mesh_, "batch", None))


def abstract_state(cfg, opt, mesh_):
    """Shapes of the train state, placed by the program's own sharding
    rules (``runtime/params.py``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.optim.adam import OptState
    from repro.runtime.params import moment_specs, param_shardings
    from repro.runtime.step import TrainState, init_train_state

    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh_))
    moments = jax.tree.map(lambda s: NamedSharding(mesh_, s),
                           moment_specs(shapes.params, mesh_,
                                        opt.moment_dtype))
    rep = NamedSharding(mesh_, P())
    shard = TrainState(param_shardings(shapes.params, mesh_),
                       OptState(rep, moments, moments, rep))
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shard)


def make_state(params, opt):
    """The program's train state around the benchmark's weights."""
    from repro.optim.adam import adamw_init
    from repro.runtime.step import TrainState
    return TrainState(params, adamw_init(params, opt))


def train_step(cfg, opt, mesh_, *, use_lsh: bool):
    """The step ``launch/train.py`` builds: one forward/backward and AdamW,
    no microbatching."""
    from repro.runtime.step import make_train_step
    return make_train_step(cfg, opt, mesh_, use_lsh=use_lsh, microbatch=0)


def set_mesh(mesh_):
    from repro.compat import set_mesh as _set
    return _set(mesh_)
