"""Every kernel-registry op's ``pallas_tpu`` implementation compiles for a
described (not attached) TPU v5e chip at granite-moe-3b-a800m widths.

Interpret mode checks what the kernels compute; only the TPU compiler
checks what Mosaic accepts (block-shape alignment, vector layouts, VMEM
limits).  Shapes are those of ``chip_smoke.py``'s one-chip train step:
8x1024 tokens over d_model 1536 and 40 experts, top-8, so capacity and
slot counts come from ``expert_capacity`` / ``num_lsh_slots`` exactly as
``core/moe.py`` derives them.  The routing pair is compiled besides at
the per-chip shapes of the ``granite.ep4.train-lsh`` benchmark cell, and
differentiated, so both of its backward kernels go through Mosaic too.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file."""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.moe import expert_capacity, num_lsh_slots
from repro.kernels import dispatch

TOKENS = 8 * 1024          # chip_smoke.py's batch x seq on one chip
EP4_TOKENS = 8 * 1024 // 4  # granite.ep4.train-lsh: one of four chips' share


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tokens=TOKENS):
    cfg = get_config("granite-moe-3b-a800m")
    m = cfg.moe
    E, H, k = m.num_experts, cfg.d_model, m.top_k
    C = expert_capacity(tokens, E, k, m.capacity_factor)
    S = num_lsh_slots(C, m.lsh.compression_rate)
    return dict(E=E, H=H, F=tokens * k, C=C, S=S, L=m.lsh.num_hashes,
                Dr=m.lsh.rotation_dim)


def _case(op, d):
    """(fn, [(shape, dtype)...]) for one op at the smoke run's shapes."""
    E, H, F, C, S = d["E"], d["H"], d["F"], d["C"], d["S"]
    i32, f32, bf16, i8 = jnp.int32, jnp.float32, jnp.bfloat16, jnp.int8
    impl = dispatch._REGISTRY[dispatch.PALLAS_TPU][op]
    cases = {
        "lsh_hash": (impl, [((E * C, H), f32), ((d["L"], H, d["Dr"]), f32)]),
        "segment_centroid": (lambda s, x: impl(s, x, S),
                             [((E, C), i32), ((E, C, H), bf16)]),
        "residual_apply": (impl, [((E, C), i32), ((E, S, H), f32),
                                  ((E, C, H), f32)]),
        "positions_in_expert": (lambda ids: impl(ids, E), [((F,), i32)]),
        "dispatch_scatter": (lambda i, p, x: impl(i, p, x, E, C),
                             [((F,), i32), ((F,), i32), ((F, H), bf16)]),
        "combine_gather": (impl, [((F,), i32), ((F,), i32),
                                  ((E, C, H), f32), ((F,), f32)]),
        "wire_quantize": (lambda x: impl(x, "int8"), [((E, S, H), f32)]),
        "wire_dequantize": (impl, [((E, S, H), i8), ((E, S), f32)]),
        "dispatch_scatter_quantize": (
            lambda i, p, x: impl(i, p, x, E, C, "int8"),
            [((F,), i32), ((F,), i32), ((F, H), bf16)]),
        "dequantize_combine_gather": (
            impl, [((F,), i32), ((F,), i32), ((E, C, H), i8), ((E, C), f32),
                   ((F,), f32)]),
        "dequantize_residual_apply": (
            impl, [((E, C), i32), ((E, S, H), i8), ((E, S), f32),
                   ((E, C, H), f32), ((E, S, H), f32)]),
    }
    return cases[op]


def test_cases_cover_every_registry_op():
    assert {op for op in dispatch.OPS if _case(op, _shapes())} \
        == set(dispatch.OPS)


def _compile(fn, specs, sharding):
    """Compiled HLO text of ``fn`` on shapes ``[(shape, dtype)...]``."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("op", dispatch.OPS)
def test_pallas_tpu_compiles_for_v5e(op, one_chip):
    fn, specs = _case(op, _shapes())
    assert "tpu_custom_call" in _compile(fn, specs, one_chip)


def test_ep4_cell_shapes():
    d = _shapes(EP4_TOKENS)
    assert (d["F"], d["E"], d["C"]) == (16384, 40, 512)


@pytest.mark.parametrize("op", ("dispatch_scatter", "combine_gather"))
def test_routing_compiles_at_ep4_cell_shapes(op, one_chip):
    fn, specs = _case(op, _shapes(EP4_TOKENS))
    assert "tpu_custom_call" in _compile(fn, specs, one_chip)


def test_routing_backward_compiles(one_chip):
    """jax.grad through combine_gather(dispatch_scatter(...)): the custom
    VJPs' backward calls (the gather that transposes the scatter, the
    scatter and unit-weight gather that transpose the gather) compile."""
    d = _shapes(EP4_TOKENS)
    E, C, F, H = d["E"], d["C"], d["F"], d["H"]
    ops = dispatch._REGISTRY[dispatch.PALLAS_TPU]

    def loss(src, w, ids, pos):
        buf = ops["dispatch_scatter"](ids, pos, src, E, C)
        return jnp.sum(ops["combine_gather"](ids, pos, buf, w) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)),
                    [((F, H), jnp.bfloat16), ((F,), jnp.float32),
                     ((F,), jnp.int32), ((F,), jnp.int32)], one_chip)
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = .*custom_call_target="
                       r'"tpu_custom_call"', text)
    # forward scatter + gather; backward gather, scatter, unit gather
    assert sorted(calls) == ["combine_gather_pallas"] * 3 \
        + ["dispatch_scatter_pallas"] * 2, calls
