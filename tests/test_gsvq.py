"""Unit tests: Group & Sliced VQ (Eq. 2-3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gsvq


def test_reduces_to_group_of_all(key):
    """n_groups=1, n_slices=1 quantizes to the weighted average of ALL atoms
    (one big group) — sanity of the degenerate case."""
    z = jax.random.normal(key, (10, 8))
    cb = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    out = gsvq.gsvq_quantize(z, cb, n_groups=1, n_slices=1)
    assert out.indices.shape == (10, 1)
    assert bool(jnp.all(out.indices == 0))


def test_group_index_picks_nearest_group(key):
    """Two well-separated groups: samples near group 1's atoms index group 1."""
    g0 = jnp.zeros((4, 8)) + jnp.array([10.0] * 8)
    g1 = jnp.zeros((4, 8)) - jnp.array([10.0] * 8)
    cb = jnp.concatenate([g0, g1]) + 0.1 * jax.random.normal(key, (8, 8))
    z = jnp.stack([jnp.full((8,), 9.5), jnp.full((8,), -9.5)])
    out = gsvq.gsvq_quantize(z, cb, n_groups=2)
    np.testing.assert_array_equal(np.asarray(out.indices[:, 0]), [0, 1])


def test_weighted_average_in_group_hull(key):
    """Eq. 3 output is a convex combination of the matched group's atoms."""
    z = jax.random.normal(key, (6, 4))
    cb = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    out = gsvq.gsvq_quantize(z, cb, n_groups=2)
    groups = cb.reshape(2, 4, 4)
    for i in range(6):
        g = np.asarray(groups[out.indices[i, 0]])
        q = np.asarray(out.quantized[i])
        assert q.min() >= g.min() - 1e-4 and q.max() <= g.max() + 1e-4


def test_sliced_indices_shape(key):
    z = jax.random.normal(key, (5, 3, 12))
    cb = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    out = gsvq.gsvq_quantize(z, cb, n_groups=4, n_slices=3)
    assert out.indices.shape == (5, 3, 3)
    assert int(out.indices.max()) < 4


def test_ste_gradient(key):
    z = jax.random.normal(key, (4, 8))
    cb = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    g = jax.grad(lambda z: jnp.sum(
        gsvq.gsvq_quantize(z, cb, n_groups=2).quantized))(z)
    np.testing.assert_allclose(np.asarray(g), np.ones_like(g), rtol=1e-6)


def test_dequantize_uniform_average(key):
    """Server reconstruction = uniform group mean of the indexed group."""
    cb = jax.random.normal(key, (8, 4))
    idx = jnp.array([[0], [1]])
    out = gsvq.gsvq_dequantize_indices(idx, cb, n_groups=2, n_slices=1)
    groups = cb.reshape(2, 4, 4)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(groups[0].mean(0)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(groups[1].mean(0)), rtol=1e-5)


def test_bits_per_position():
    assert gsvq.gsvq_bits_per_position(16, 1) == 4
    assert gsvq.gsvq_bits_per_position(16, 4) == 16
    assert gsvq.gsvq_bits_per_position(2, 2) == 2


@pytest.mark.parametrize("n_groups,n_slices", [(2, 1), (4, 2), (8, 4)])
def test_shapes_roundtrip(key, n_groups, n_slices):
    z = jax.random.normal(key, (3, 7, 16))
    cb = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    out = gsvq.gsvq_quantize(z, cb, n_groups=n_groups, n_slices=n_slices)
    assert out.quantized.shape == z.shape
    rec = gsvq.gsvq_dequantize_indices(out.indices, cb, n_groups=n_groups,
                                       n_slices=n_slices)
    assert rec.shape == z.shape
    assert bool(jnp.all(jnp.isfinite(rec)))


def _gather_form(z_e, codebook, n_groups, n_slices):
    """Eq. 2-3 in the gathered-atom form: gather each row's matched-group
    atoms as an (N, N_g, m) tensor and recompute their distances. The
    reference for ``gsvq_quantize``'s matmul form."""
    *lead, M = z_e.shape
    K = codebook.shape[0]
    m, ng = M // n_slices, K // n_groups
    zf = z_e.reshape(-1, n_slices, m)
    cb = codebook.reshape(K, n_slices, m).transpose(1, 0, 2)

    def per_slice(z_s, cb_s):
        d = jnp.sqrt(jnp.sum(jnp.square(z_s[:, None] - cb_s[None]), -1)
                     + 1e-12)                                    # (N, K)
        gidx = jnp.argmin(jnp.mean(d.reshape(-1, n_groups, ng), -1), -1)
        atoms = cb_s.reshape(n_groups, ng, m)[gidx]              # (N, N_g, m)
        da = jnp.sqrt(jnp.sum(jnp.square(z_s[:, None] - atoms), -1)
                      + 1e-12)
        w = 1.0 / (da + 1e-8)
        w = w / jnp.sum(w, -1, keepdims=True)
        return jnp.einsum("ng,ngm->nm", w, atoms), gidx.astype(jnp.int32)

    zq, gidx = jax.vmap(per_slice, in_axes=(1, 0), out_axes=(1, 1))(zf, cb)
    return zq.reshape(*lead, M), gidx.reshape(*lead, n_slices)


def _gather_terms(z, cb, n_groups, n_slices):
    """codebook_loss + 0.25 * commit_loss of the gather form."""
    zq = _gather_form(z, cb, n_groups, n_slices)[0]
    sg = jax.lax.stop_gradient
    return (jnp.mean(jnp.square(sg(z) - zq))
            + 0.25 * jnp.mean(jnp.square(z - sg(zq))))


def _matmul_terms(z, cb, n_groups, n_slices):
    q = gsvq.gsvq_quantize(z, cb, n_groups=n_groups, n_slices=n_slices)
    return q.codebook_loss + 0.25 * q.commit_loss


@pytest.mark.parametrize("n_groups,n_slices,K,M,exact_row", [
    (2, 1, 32, 16, False), (4, 2, 32, 16, False), (8, 4, 32, 16, False),
    (8, 4, 256, 64, False), (8, 4, 256, 64, True)])
def test_matmul_form_matches_gather_form(key, n_groups, n_slices, K, M,
                                         exact_row):
    """Eq. 3 taken from the Eq. 2 distance matrix by masked matmuls equals
    the gathered-atom form: same indices, quantized latents within 1e-5,
    and the gradient of the Eq. 6 quantizer terms w.r.t. z within 1e-5
    relative. With a row equal to an atom, everything stays finite and
    Eq. 3 puts (nearly) all weight on that atom."""
    k1, k2 = jax.random.split(key)
    z = jax.random.normal(k1, (4, 32, M))
    cb = jax.random.normal(k2, (K, M))
    ng = K // n_groups
    lo = 37 // ng * ng                   # first atom of atom 37's group
    if exact_row:
        # atom 37's group gathered round it, so Eq. 2 matches that group
        # in every slice, and one row of z set to the atom itself
        near = cb[37] + 0.5 * jax.random.normal(k1, (ng, M))
        cb = cb.at[lo:lo + ng].set(near.at[37 - lo].set(cb[37]))
        z = z.at[1, 5].set(cb[37])
    q = gsvq.gsvq_quantize(z, cb, n_groups=n_groups, n_slices=n_slices)
    grad = np.asarray(jax.grad(_matmul_terms)(z, cb, n_groups, n_slices))
    assert np.all(np.isfinite(np.asarray(q.quantized)))
    assert np.all(np.isfinite(grad))
    want_zq, want_idx = _gather_form(z, cb, n_groups, n_slices)
    np.testing.assert_array_equal(np.asarray(q.indices), np.asarray(want_idx))
    if exact_row:
        m = M // n_slices
        np.testing.assert_array_equal(np.asarray(q.indices[1, 5]),
                                      np.full(n_slices, 37 // ng))
        atom = np.asarray(cb[37]).reshape(n_slices, m)
        got = np.asarray(q.quantized[1, 5]).reshape(n_slices, m)
        others = np.asarray(cb[lo:lo + ng]).reshape(ng, n_slices, m)
        nearest = np.sort(np.linalg.norm(others - atom, axis=-1), 0)[1]
        assert np.all(np.linalg.norm(got - atom, axis=-1) < 0.05 * nearest)
        return
    np.testing.assert_allclose(np.asarray(q.quantized), np.asarray(want_zq),
                               rtol=0, atol=1e-5)
    want_grad = np.asarray(jax.grad(_gather_terms)(z, cb, n_groups,
                                                   n_slices))
    assert (np.linalg.norm(grad - want_grad)
            <= 1e-5 * np.linalg.norm(want_grad))
