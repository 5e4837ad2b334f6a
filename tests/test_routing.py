"""DispatchPlan / positions_in_expert properties: stability, capacity
overflow, degenerate routings, and plan-level invariants shared by both
MoE paths.  Property tests run under hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import routing
from repro.kernels import dispatch

BACKENDS = ("reference", "pallas_interpret")


def _random_ids(seed, f, num_experts):
    return jax.random.randint(jax.random.PRNGKey(seed), (f,), 0,
                              num_experts).astype(jnp.int32)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(2, 40), st.integers(1, 9),
       st.integers(0, 10_000))
def test_positions_properties(num_experts, f, capacity, seed):
    """For every routing: positions are stable (token-major), collision-free
    among kept entries, and keep implements exact capacity truncation."""
    ids = np.asarray(_random_ids(seed, f, num_experts))
    pos, keep, counts = dispatch.positions_in_expert(
        jnp.asarray(ids), num_experts, capacity, backend="reference")
    pos, keep, counts = map(np.asarray, (pos, keep, counts))
    for e in range(num_experts):
        mine = np.where(ids == e)[0]
        # stability: earlier flat entries get smaller positions, 0..n-1
        np.testing.assert_array_equal(pos[mine], np.arange(len(mine)))
        # capacity: exactly the first `capacity` entries are kept
        np.testing.assert_array_equal(keep[mine],
                                      np.arange(len(mine)) < capacity)
        assert counts[e] == len(mine)          # uncapped demand
    assert int(counts.sum()) == f


@pytest.mark.parametrize("backend", BACKENDS)
def test_positions_all_tokens_one_expert(backend):
    """Degenerate hot-expert routing: positions must be 0..F-1 and keep
    truncates at capacity."""
    f, cap = 300, 17                # crosses the kernel's 128 tile boundary
    ids = jnp.zeros((f,), jnp.int32)
    pos, keep, counts = dispatch.positions_in_expert(ids, 4, cap,
                                                     backend=backend)
    np.testing.assert_array_equal(np.asarray(pos), np.arange(f))
    np.testing.assert_array_equal(np.asarray(keep), np.arange(f) < cap)
    np.testing.assert_array_equal(np.asarray(counts), [f, 0, 0, 0])


@pytest.mark.parametrize("backend", BACKENDS)
def test_positions_out_of_range_dropped(backend):
    """Ids outside [0, E) land in the overflow bin: pos == capacity,
    keep False, counted nowhere."""
    ids = jnp.array([0, -1, 1, 7, 0], jnp.int32)
    pos, keep, counts = dispatch.positions_in_expert(ids, 2, 4,
                                                     backend=backend)
    np.testing.assert_array_equal(np.asarray(pos), [0, 4, 0, 4, 1])
    np.testing.assert_array_equal(np.asarray(keep),
                                  [True, False, True, False, True])
    np.testing.assert_array_equal(np.asarray(counts), [2, 1])


def test_plan_counts_agree_with_gate_load(rng):
    """GateOut.load (standalone gating consumers) and DispatchPlan.counts
    (what the MoE paths report as expert_load) are two computations of the
    same physical-order metric — they must never diverge."""
    from repro.core.gating import top_k_gating

    x = jax.random.normal(rng, (32, 16))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (16, 4))
    perm = jnp.array([2, 0, 3, 1], jnp.int32)
    gate = top_k_gating(x, w, 2, placement=perm)
    plan = routing.build_dispatch_plan(gate.expert_ids, gate.weights,
                                       6, 8, backend="reference")  # E padded
    np.testing.assert_array_equal(np.asarray(plan.counts)[:4],
                                  np.asarray(gate.load))
    np.testing.assert_array_equal(np.asarray(plan.counts)[4:], 0)


def test_plan_occupancy_matches_scatter(rng):
    """plan.occupancy must mark exactly the dispatch-buffer rows that the
    scatter fills (the LSH compressor's `valid` input)."""
    T, k, E, C, H = 40, 2, 5, 8, 16
    ids = jax.random.randint(rng, (T, k), 0, E).astype(jnp.int32)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(rng, 1), (T, k)))
    plan = routing.build_dispatch_plan(ids, w, E, C, backend="reference")
    x = 1.0 + jax.random.uniform(jax.random.fold_in(rng, 2), (T, H))
    buf = routing.dispatch_tokens(plan, x, backend="reference")
    filled = np.abs(np.asarray(buf)).sum(-1) > 0          # [E, C]
    np.testing.assert_array_equal(np.asarray(plan.occupancy), filled)
    # occupancy rows are contiguous from 0 (stable positions)
    occ = np.asarray(plan.occupancy)
    for e in range(E):
        n = occ[e].sum()
        np.testing.assert_array_equal(occ[e], np.arange(C) < n)


@pytest.mark.parametrize("backend", BACKENDS)
def test_plan_roundtrip_identity_expert(backend, rng):
    """With no capacity drops and an identity expert, dispatch followed by
    the weighted combine reconstructs every token (weights sum to 1)."""
    T, k, E, H = 24, 2, 4, 16
    cap = T * k                     # no drops possible
    ids = jax.random.randint(rng, (T, k), 0, E).astype(jnp.int32)
    # distinct experts per token so the k contributions are k distinct rows
    ids = ids.at[:, 1].set((ids[:, 0] + 1) % E)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(rng, 1), (T, k)))
    x = jax.random.normal(jax.random.fold_in(rng, 2), (T, H))
    plan = routing.build_dispatch_plan(ids, w, E, cap, backend=backend)
    assert float(plan.drop_fraction()) == 0.0
    buf = routing.dispatch_tokens(plan, x, backend=backend)
    y = routing.combine_tokens(plan, buf, backend=backend)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)


def test_plan_full_overflow_yields_zero(rng):
    """A token whose every choice is dropped contributes a zero output row
    (the overflow-bin contract, with no explicit mask anywhere)."""
    T, k, E, H = 6, 2, 2, 8
    ids = jnp.zeros((T, k), jnp.int32)          # everyone wants expert 0
    w = jnp.full((T, k), 0.5)
    plan = routing.build_dispatch_plan(ids, w, E, 4, backend="reference")
    x = jax.random.normal(rng, (T, H))
    buf = routing.dispatch_tokens(plan, x, backend="reference")
    y = np.asarray(routing.combine_tokens(plan, buf, backend="reference"))
    np.testing.assert_array_equal(y[2:], np.zeros((T - 2, H)))  # cap 4 = 2 tok
    assert np.abs(y[:2]).sum() > 0


def test_per_op_backend_override():
    """resolve_backends layers per-op overrides over the default and
    rejects unknown op names."""
    m = dispatch.resolve_backends(
        "reference", (("dispatch_scatter", "pallas_interpret"),))
    assert m["*"] == "reference"
    assert dispatch.op_backend(m, "dispatch_scatter") == "pallas_interpret"
    assert dispatch.op_backend(m, "combine_gather") == "reference"
    with pytest.raises(ValueError):
        dispatch.resolve_backends("reference", (("no_such_op", "reference"),))


def test_off_tpu_fallback_resolution():
    """pallas_tpu off-TPU degrades to the fallback when one is given
    (the no-LSH baseline must trace TPU-targeted configs on CPU) but
    still raises without one; unknown names raise either way."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU behavior")
    m = dispatch.resolve_backends("pallas_tpu",
                                  off_tpu_fallback="reference")
    assert m["*"] == "reference"
    with pytest.raises(ValueError):
        dispatch.resolve_backends("pallas_tpu")
    with pytest.raises(ValueError):
        dispatch.resolve_backends("bogus", off_tpu_fallback="reference")
    # explicit non-TPU choices are honored, not degraded
    m = dispatch.resolve_backends("pallas_interpret",
                                  off_tpu_fallback="reference")
    assert m["*"] == "pallas_interpret"


def test_moe_backend_resolution_applies_without_lsh():
    """The routing ops run on every path now, so the configured backend
    (and override validation) must apply even with LSH off."""
    from repro.configs.base import LSHConfig, MoEConfig
    from repro.core.moe import _resolve_moe_backend

    cfg = MoEConfig(num_experts=4, top_k=2, expert_ffn_dim=32,
                    kernel_backend="pallas_interpret",
                    lsh=LSHConfig(enabled=False))
    m = _resolve_moe_backend(cfg, None, lsh_active=False)
    assert m["*"] == "pallas_interpret"
    bad = MoEConfig(num_experts=4, top_k=2, expert_ffn_dim=32,
                    kernel_backend_overrides=(("typo_op", "reference"),))
    with pytest.raises(ValueError):
        _resolve_moe_backend(bad, None, lsh_active=False)


def test_moe_config_per_op_override_plumbs(mesh, rng):
    """MoEConfig.kernel_backend_overrides reaches the hot path: overriding
    every routing op to pallas_interpret must reproduce the reference
    output exactly (ops are parity-exact)."""
    from repro.compat import set_mesh
    from repro.configs.base import LSHConfig, MoEConfig
    from repro.core.lsh_moe import lsh_moe_apply, lsh_moe_init

    def cfg_for(overrides=()):
        return MoEConfig(num_experts=4, top_k=2, expert_ffn_dim=32,
                         capacity_factor=2.0, kernel_backend="reference",
                         kernel_backend_overrides=overrides,
                         lsh=LSHConfig(enabled=True, num_hashes=3,
                                       rotation_dim=16,
                                       compression_rate=0.5))

    params = lsh_moe_init(rng, 16, cfg_for(), mesh, mlp_act="swiglu",
                          dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 7), (1, 32, 16))
    ov = tuple((op, "pallas_interpret")
               for op in ("positions_in_expert", "dispatch_scatter",
                          "combine_gather"))
    ys = {}
    with set_mesh(mesh):
        for name, cfg in (("base", cfg_for()), ("override", cfg_for(ov))):
            ys[name], _ = jax.jit(lambda p, x, c=cfg: lsh_moe_apply(
                p, x, c, mesh, mlp_act="swiglu"))(params, x)
    np.testing.assert_allclose(np.asarray(ys["base"]),
                               np.asarray(ys["override"]), atol=1e-6)
