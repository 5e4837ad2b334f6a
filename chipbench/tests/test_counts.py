"""Work counts kept with the benchmark: peaks, model FLOPs, kernel bytes."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts
from chipbench.models import granite_moe
from chipbench.trace import Op

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_peaks_are_the_published_v5e_numbers():
    p = counts.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9,
                                                              16e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("layers", [4, 16])
def test_model_flops_of_the_cut_granite(layers):
    m = json.loads((CONFIGS / "granite-moe-3b-a800m.ep4.json").read_text())[
        "config"]
    m = dict(m, num_hidden_layers=layers)
    active = granite_moe.active_matmul_params(m)
    # attention 6.29M + router 0.06M + 8 experts 18.87M per layer, and the
    # 75.5M LM head: at 4 layers the ~1.75e8 of PERF.md
    assert active == layers * (6_291_456 + 61_440 + 18_874_368) + 75_502_080
    if layers == 4:
        assert abs(active / 1.75e8 - 1) < 0.01
    per_token = granite_moe.train_flops_per_token(m, 1024)
    assert per_token == 6 * active + 6 * layers * 24 * 64 * 1024


def _registry(op, backend, *args, **kw):
    from repro.kernels import dispatch
    return jax.eval_shape(lambda *a: getattr(dispatch, op)(
        *a, backend=backend, **kw), *args)


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_routing_interface_bytes_do_not_depend_on_the_backend(backend):
    F, E, C, H = 4096, 40, 128, 1536
    ids = jax.ShapeDtypeStruct((F,), jnp.int32)
    src = jax.ShapeDtypeStruct((F, H), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((F,), jnp.float32)
    buf = _registry("dispatch_scatter", backend, ids, ids, src,
                    num_experts=E, capacity=C)
    out = _registry("combine_gather", backend, ids, ids, buf, w)
    assert counts.array_bytes(ids, ids, src, buf) == \
        4 * F * 2 + 2 * F * H + 4 * E * C * H
    assert counts.array_bytes(ids, ids, buf, w, out) == \
        4 * F * 3 + 4 * E * C * H + 4 * F * H


def test_a_traced_kernel_call_counts_its_operands_and_result_once():
    # A combine_gather call as the v5e trace names it (layouts and the
    # operand layout constraints repeat shapes that must not count twice)
    text = ("%combine_gather_pallas.3 = f32[65536,1536]{1,0:T(8,128)} "
            "custom-call(s32[1,65536]{1,0:T(1,128)} %a, s32[1,65536]"
            "{1,0:T(1,128)} %b, f32[1,65536]{1,0:T(1,128)} %c, "
            "f32[40,2048,1536]{2,1,0:T(8,128)} %d), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={s32[1,65536]"
            "{1,0}, s32[1,65536]{1,0}, f32[1,65536]{1,0}, "
            "f32[40,2048,1536]{2,1,0}}")
    op = Op(0, 0.0, 1.0, "combine_gather_pallas.3", "custom-call", "",
            text)
    F, E, C, H = 65536, 40, 2048, 1536
    ids = jax.ShapeDtypeStruct((F,), jnp.int32)
    assert op.interface_bytes() == counts.array_bytes(
        ids, ids, jax.ShapeDtypeStruct((F,), jnp.float32),
        jax.ShapeDtypeStruct((E, C, H), jnp.float32),
        jax.ShapeDtypeStruct((F, H), jnp.float32))
    assert op.kernel == "combine_gather_pallas"
