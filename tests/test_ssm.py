"""Mamba2 SSD: chunked prefill vs naive recurrence, chunk-size invariance,
and prefill/decode state equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.ssm import _ssd_scan

KEY = jax.random.PRNGKey(3)


def naive_recurrence(x, dt, a, bmat, cmat):
    """Token-by-token SSM recurrence (the decode rule applied S times)."""
    b, s, nh, hd = x.shape
    ng, n = bmat.shape[2], bmat.shape[3]
    hpg = nh // ng
    state = np.zeros((b, nh, hd, n), np.float32)
    ys = np.zeros((b, s, nh, hd), np.float32)
    for t in range(s):
        da = np.exp(dt[:, t] * a[None, :])                      # (b, nh)
        bh = np.repeat(bmat[:, t], hpg, axis=1)                 # (b, nh, n)
        ch = np.repeat(cmat[:, t], hpg, axis=1)
        state = (state * da[..., None, None]
                 + (dt[:, t][..., None] * x[:, t])[..., None]
                 * bh[:, :, None, :])
        ys[:, t] = np.einsum("bnpq,bnq->bnp", state, ch)
    return ys


@pytest.mark.parametrize("ng", [1, 2])
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_ssd_matches_recurrence(ng, chunk):
    b, s, nh, hd, n = 2, 24, 4, 8, 16
    rng = np.random.default_rng(0)
    x = rng.standard_normal((b, s, nh, hd)).astype(np.float32)
    dt = np.abs(rng.standard_normal((b, s, nh))).astype(np.float32) * 0.5
    a = -np.abs(rng.standard_normal(nh)).astype(np.float32)
    bmat = rng.standard_normal((b, s, ng, n)).astype(np.float32) * 0.5
    cmat = rng.standard_normal((b, s, ng, n)).astype(np.float32) * 0.5
    y = np.asarray(_ssd_scan(jnp.asarray(x), jnp.asarray(dt),
                             jnp.asarray(a), jnp.asarray(bmat),
                             jnp.asarray(cmat), chunk))
    ref = naive_recurrence(x, dt, a, bmat, cmat)
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4)


def test_chunk_size_invariance():
    b, s, nh, hd, n = 1, 32, 2, 8, 8
    rng = np.random.default_rng(1)
    args = (rng.standard_normal((b, s, nh, hd)).astype(np.float32),
            np.abs(rng.standard_normal((b, s, nh))).astype(np.float32),
            -np.abs(rng.standard_normal(nh)).astype(np.float32),
            rng.standard_normal((b, s, 1, n)).astype(np.float32),
            rng.standard_normal((b, s, 1, n)).astype(np.float32))
    jargs = [jnp.asarray(a) for a in args]
    y8 = np.asarray(_ssd_scan(*jargs, 8))
    y16 = np.asarray(_ssd_scan(*jargs, 16))
    y32 = np.asarray(_ssd_scan(*jargs, 32))
    np.testing.assert_allclose(y8, y16, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y8, y32, rtol=1e-4, atol=1e-4)


def test_unrolled_matches_scan():
    b, s, nh, hd, n = 1, 16, 2, 4, 8
    rng = np.random.default_rng(2)
    jargs = [jnp.asarray(rng.standard_normal((b, s, nh, hd)), jnp.float32),
             jnp.asarray(np.abs(rng.standard_normal((b, s, nh))),
                         jnp.float32),
             jnp.asarray(-np.abs(rng.standard_normal(nh)), jnp.float32),
             jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32),
             jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)]
    a = np.asarray(_ssd_scan(*jargs, 8, unroll=False))
    bb = np.asarray(_ssd_scan(*jargs, 8, unroll=True))
    np.testing.assert_allclose(a, bb, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lo,hi", [(-40.0, 40.0), (-0.72, -0.67),
                                   (0.67, 0.72)])
def test_softplus_is_f32_exact_and_branch_free(lo, hi):
    """``layers.softplus`` (the program's dt, in the kernel and in
    ``ssm.py``) stays within a few ulp of ``log(1 + e^x)`` over the whole
    range and around ``|x| = ln 2``, where a TPU's ``log1p`` jumps."""
    from repro.models.layers import softplus
    x = np.linspace(lo, hi, 200_001).astype(np.float32)
    exact = np.logaddexp(x.astype(np.float64), 0.0)
    got = np.asarray(jax.jit(softplus)(jnp.asarray(x)), np.float64)
    assert np.max(np.abs(got - exact) / exact) < 4e-7
