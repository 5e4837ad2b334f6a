"""Backend parity: the kernel dispatch registry (kernels/dispatch.py) must
produce identical results (fp32 tolerance) under ``reference`` and
``pallas_interpret`` for every registered op, for the compress/decompress
hot path built on them, and for the gradients the custom VJPs define —
including empty slots and fully-invalid groups."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LSHConfig, MoEConfig
from repro.core import clustering
from repro.core.hashing import make_rotations
from repro.kernels import dispatch

BACKENDS = ("reference", "pallas_interpret")


def _group_inputs(rng, g=3, c=40, h=64, num_slots=8, dtype=jnp.float32):
    """[G, C, H] groups incl. a partially-valid and a fully-invalid group."""
    tokens = jax.random.normal(rng, (g, c, h), jnp.float32).astype(dtype)
    n_valid = jnp.array([c, c // 3, 0])[:g]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]
    tokens = tokens * valid[..., None].astype(tokens.dtype)
    slots = jax.random.randint(jax.random.fold_in(rng, 1), (g, c), 0,
                               num_slots)
    slots = jnp.where(valid, slots, num_slots)    # overflow bin
    return tokens, valid, slots


def test_resolve_backend_order(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.resolve_backend("reference") == "reference"
    assert dispatch.resolve_backend(None) in dispatch.available_backends()
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas_interpret")
    assert dispatch.resolve_backend("auto") == "pallas_interpret"
    # explicit name beats the env var
    assert dispatch.resolve_backend("reference") == "reference"
    with pytest.raises(ValueError):
        dispatch.resolve_backend("no_such_backend")


def test_lsh_hash_parity(rng):
    x = jax.random.normal(rng, (100, 64), jnp.float32)
    rot = jax.random.normal(jax.random.fold_in(rng, 1), (4, 64, 32),
                            jnp.float32)
    ref = dispatch.lsh_hash(x, rot, backend="reference")
    pal = dispatch.lsh_hash(x, rot, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


def test_segment_centroid_parity(rng):
    tokens, valid, slots = _group_inputs(rng)
    outs = {b: dispatch.segment_centroid(slots, tokens, 8, backend=b)
            for b in BACKENDS}
    # the overflow bin (invalid tokens) must hit no slot on either backend
    assert float(outs["reference"][1].sum()) == float(valid.sum())
    for a, b in zip(outs["reference"], outs["pallas_interpret"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_residual_apply_parity(rng):
    # slots keep the overflow bin (== num_slots): the uniform contract says
    # out-of-range ids gather zero on EVERY backend
    tokens, valid, slots = _group_inputs(rng)
    eout = jax.random.normal(rng, (3, 8, 64), jnp.float32)
    resid = jax.random.normal(jax.random.fold_in(rng, 2), (3, 40, 64),
                              jnp.float32)
    got = {b: dispatch.residual_apply(slots, eout, resid, backend=b)
           for b in BACKENDS}
    np.testing.assert_allclose(np.asarray(got["reference"]),
                               np.asarray(got["pallas_interpret"]),
                               atol=1e-5)
    invalid = ~np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got["reference"])[invalid],
                               np.asarray(resid)[invalid], atol=1e-6)


@pytest.mark.parametrize("hash_type", ["cross_polytope", "spherical"])
@pytest.mark.parametrize("compensation", [True, False])
def test_compress_parity(rng, hash_type, compensation):
    tokens, valid, _ = _group_inputs(rng)
    rot = make_rotations(jax.random.fold_in(rng, 3), 4, 64, 32, jnp.float32)
    comps = {b: clustering.compress(tokens, valid, rot, 8, hash_type,
                                    compensation, backend=b)
             for b in BACKENDS}
    for field in ("centroids", "residuals", "slots", "counts"):
        a = np.asarray(getattr(comps["reference"], field), np.float32)
        b = np.asarray(getattr(comps["pallas_interpret"], field), np.float32)
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=field)
    eout = jax.random.normal(jax.random.fold_in(rng, 4), (3, 8, 64))
    recon = {b: clustering.decompress(eout, comps[b], backend=b)
             for b in BACKENDS}
    np.testing.assert_allclose(np.asarray(recon["reference"]),
                               np.asarray(recon["pallas_interpret"]),
                               atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_roundtrip_exact_when_slots_equal_capacity(rng, backend):
    """slots == capacity: with residual compensation and an identity expert
    the compress→decompress pair reconstructs every token exactly."""
    c = 24
    tokens = jax.random.normal(rng, (2, c, 64), jnp.float32)
    valid = jnp.ones((2, c), bool)
    rot = make_rotations(jax.random.fold_in(rng, 5), 4, 64, 32, jnp.float32)
    comp = clustering.compress(tokens, valid, rot, c, "cross_polytope", True,
                               backend=backend)
    recon = clustering.decompress(comp.centroids.astype(jnp.float32), comp,
                                  backend=backend)
    np.testing.assert_allclose(np.asarray(recon), np.asarray(tokens),
                               atol=1e-5)


def test_compress_gradient_parity(rng):
    """The Pallas custom VJPs must match the reference backward pass."""
    tokens, valid, _ = _group_inputs(rng)
    rot = make_rotations(jax.random.fold_in(rng, 6), 4, 64, 32, jnp.float32)

    def f(t, backend):
        comp = clustering.compress(t, valid, rot, 8, backend=backend)
        out = clustering.decompress(comp.centroids.astype(jnp.float32) * 2.0,
                                    comp, backend=backend)
        return jnp.sum(out ** 2) + jnp.sum(comp.centroids ** 2)

    grads = {b: jax.jit(jax.grad(f), static_argnums=1)(tokens, b)
             for b in BACKENDS}
    assert float(jnp.abs(grads["reference"]).sum()) > 0
    np.testing.assert_allclose(np.asarray(grads["reference"]),
                               np.asarray(grads["pallas_interpret"]),
                               atol=1e-4)


def _routing_inputs(rng, f=300, e=5, c=16, h=32, dtype=jnp.float32,
                    unused_expert=False, drop=None):
    """Flattened routing ids incl. out-of-range entries, plus src/weights.
    f=300 crosses the kernels' 128-entry tile.  ``unused_expert`` routes
    nothing to the last expert, ``drop=(a, b)`` sends entries a..b-1 to
    the overflow bin."""
    ids = jax.random.randint(rng, (f,), 0, e - unused_expert)
    ids = ids.astype(jnp.int32).at[3].set(-1).at[60].set(e + 2)
    pos, keep, _ = dispatch.positions_in_expert(ids, e, c,
                                                backend="reference")
    flat_ids = jnp.where(keep, ids, e)
    if drop is not None:
        flat_ids = flat_ids.at[drop[0]:drop[1]].set(e)
    src = jax.random.normal(jax.random.fold_in(rng, 1), (f, h),
                            jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(rng, 2), (f,), jnp.float32)
    return flat_ids, pos, src, w, e, c


# Routing shapes for the row-moving kernels (kernels/scatter_gather.py).
# At h=32 an entry tile is 128 entries and a DMA block 8 rows (f32) or
# 16 (bf16).
ROUTING_CASES = {
    "base": {},
    # F = 203 and E·C = 39: neither a whole number of entry tiles nor of
    # 8- or 16-row blocks
    "ragged": dict(f=203, e=3, c=13),
    "empty_expert": dict(unused_expert=True),
    # the second entry tile, entries 128..255, dropped whole
    "dropped_tile": dict(drop=(128, 256)),
    "bf16": dict(dtype=jnp.bfloat16),
}


def test_positions_in_expert_parity(rng):
    """Integer outputs: reference and pallas_interpret must be identical,
    including overflow-bin handling and multi-tile inputs."""
    ids = jax.random.randint(rng, (300,), 0, 5).astype(jnp.int32)
    ids = ids.at[0].set(-3).at[200].set(9)
    outs = {b: dispatch.positions_in_expert(ids, 5, 16, backend=b)
            for b in BACKENDS}
    for a, b in zip(outs["reference"], outs["pallas_interpret"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # uncapped totals count exactly the in-range entries
    assert int(outs["reference"][2].sum()) == 298


@pytest.mark.parametrize("case", list(ROUTING_CASES))
def test_dispatch_scatter_combine_gather_parity(rng, case):
    """Values bit-for-bit across backends for both routing directions."""
    flat_ids, pos, src, w, e, c = _routing_inputs(rng,
                                                  **ROUTING_CASES[case])
    bufs = {b: dispatch.dispatch_scatter(flat_ids, pos, src, e, c, backend=b)
            for b in BACKENDS}
    np.testing.assert_array_equal(np.asarray(bufs["reference"]),
                                  np.asarray(bufs["pallas_interpret"]))
    gbuf = bufs["reference"].astype(src.dtype)
    outs = {b: dispatch.combine_gather(flat_ids, pos, gbuf, w, backend=b)
            for b in BACKENDS}
    np.testing.assert_array_equal(np.asarray(outs["reference"]),
                                  np.asarray(outs["pallas_interpret"]))
    # overflow-bin entries gather exactly zero
    dropped = np.asarray(flat_ids) == e
    assert dropped.any()
    np.testing.assert_array_equal(
        np.asarray(outs["reference"])[dropped], 0.0)
    if case == "empty_expert":
        np.testing.assert_array_equal(
            np.asarray(bufs["pallas_interpret"])[e - 1], 0.0)


@pytest.mark.parametrize("case", list(ROUTING_CASES))
def test_routing_gradient_parity(rng, case):
    """The custom VJPs (reference and Pallas both use the mutual-transpose
    backward structure) must agree bit-for-bit on d_src, d_buf, d_w."""
    flat_ids, pos, src, w, e, c = _routing_inputs(rng,
                                                  **ROUTING_CASES[case])

    def f(src, w, backend):
        buf = dispatch.dispatch_scatter(flat_ids, pos, src, e, c,
                                        backend=backend)
        out = dispatch.combine_gather(flat_ids, pos, buf * 1.5, w,
                                      backend=backend)
        return jnp.sum(out ** 2)

    grads = {b: jax.jit(jax.grad(f, argnums=(0, 1)),
                        static_argnums=2)(src, w, b) for b in BACKENDS}
    for i, name in enumerate(("d_src", "d_weights")):
        a = np.asarray(grads["reference"][i])
        b = np.asarray(grads["pallas_interpret"][i])
        assert np.abs(a.astype(np.float32)).sum() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_moe_layer_backend_parity(mesh, rng):
    """End to end through the expert-parallel shard_map path: the full MoE
    layer output must agree across backends (cfg flag plumbing included)."""
    from repro.compat import set_mesh
    from repro.core.lsh_moe import lsh_moe_apply, lsh_moe_init

    def cfg_for(backend):
        return MoEConfig(num_experts=4, top_k=2, expert_ffn_dim=32,
                         capacity_factor=2.0, kernel_backend=backend,
                         lsh=LSHConfig(enabled=True, num_hashes=3,
                                       rotation_dim=16,
                                       compression_rate=0.5))

    params = lsh_moe_init(rng, 16, cfg_for("reference"), mesh,
                          mlp_act="swiglu", dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 7), (1, 32, 16))
    ys = {}
    with set_mesh(mesh):
        for b in BACKENDS:
            cfg = cfg_for(b)
            ys[b], _ = jax.jit(lambda p, x, c=cfg: lsh_moe_apply(
                p, x, c, mesh, mlp_act="swiglu"))(params, x)
    np.testing.assert_allclose(np.asarray(ys["reference"]),
                               np.asarray(ys["pallas_interpret"]),
                               atol=1e-4)
