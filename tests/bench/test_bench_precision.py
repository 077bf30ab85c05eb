"""The control's three-pass bf16 product, written out: its split rounds
as a cast to bf16 does, and its error lies between one bf16 pass and
f32 arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import precision


def test_split_rounds_like_a_bf16_cast():
    x = jax.random.normal(jax.random.key(1), (4096,), jnp.float32)
    head, tail = jax.jit(precision._split)(x)
    cast = x.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(head), np.asarray(cast))
    np.testing.assert_array_equal(
        np.asarray(tail),
        np.asarray((x - cast).astype(jnp.bfloat16).astype(jnp.float32)))
    assert np.abs(np.asarray(tail)).max() > 0


def test_high_error_sits_between_one_pass_and_f32():
    ka, kb = jax.random.split(jax.random.key(2))
    a = jax.random.normal(ka, (64, 512), jnp.float32)
    b = jax.random.normal(kb, (512, 256), jnp.float32)
    truth = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = np.abs(truth).max()

    def err(out):
        return np.abs(np.asarray(out, np.float64) - truth).max() / scale

    high = err(precision.einsum("ij,jk->ik", a, b, "high"))
    one_pass = err(jnp.einsum("ij,jk->ik", a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32))
    full = err(precision.einsum("ij,jk->ik", a, b, "highest"))
    assert full < high < one_pass / 30
