"""The mamba2 family's reference (``bench/models/mamba2.py``) against the
program, the comparison that decides ``correct`` under the
``mamba2-2.7b.L4`` limits, and the operations and bytes of a launch at
the cell's sizes, by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA

from bench import manifest, run, weights
from bench.models import mamba2
from bench.precision import einsum

SEED = 2 ** 31 + 211


@pytest.mark.parametrize("mix", ["tiny-closed", "tiny-open"])
def test_program_passes_and_the_control_fails(tiny_run, mix):
    """At the cell's depth of 4 layers the control's error passes the
    limit on about half the rows (at 2 layers on one in six), and a 3 s
    window keeps 14 rows or more even on a loaded CPU, so the control's
    verdict does not hang on how many rows a slow step lets it see."""
    res = tiny_run("tiny-mamba2", "mamba2-2.7b.L4", mix, seconds=3.0,
                   control=True)
    assert res["correct"] is True, res["checks"]
    assert res["control"]["correct"] is False, res["control"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_reference_equals_the_program_after_prefill_and_decode():
    """Prefill a prompt per slot through ``Program.prefill``, then decode
    through the state in the interpreted megakernel; every logits row
    equals the reference's full forward over the whole sequence."""
    from repro.api import compile as mpk_compile

    conf = manifest.config("tiny-mamba2", DATA)
    model, cfg = run.build_program(conf)
    sizes = conf["model"]
    w = weights.make(model.weight_spec(sizes), SEED)
    b, prompt, steps = 4, 6, 4
    toks = np.random.default_rng(SEED).integers(
        1, sizes["vocab_size"], size=(b, prompt + steps)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = mpk_compile(cfg, b, conf["serving"]["max_seq"],
                           backend="megakernel")
        prog.bind(w)
        got = [prog.prefill(toks[:, :prompt], np.zeros(b, np.int32))]
        for t in range(prompt, prompt + steps - 1):
            got.append(prog.step(toks[:, t], np.full(b, t, np.int32))
                       [:, None])
    got = np.concatenate(got, axis=1)                 # (b, L - 1, V)
    h = mamba2.hidden(w, sizes, jnp.asarray(toks[:, :-1]), "highest")
    ref = np.asarray(einsum("bld,dv->blv", h, mamba2.head(w), "highest"))
    scale = np.abs(ref).max(-1, keepdims=True)
    assert np.abs(got - ref).max() / scale.min() < 2e-6
    # the served token at each position is the reference's best
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_reference_blocks_carry_the_state():
    """The blocked pass equals one block over the whole sequence."""
    conf = manifest.config("tiny-mamba2", DATA)
    sizes = conf["model"]
    w = weights.make(mamba2.weight_spec(sizes), SEED)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        1, sizes["vocab_size"], size=(2, 48)), jnp.int32)
    assert mamba2._block(48) == 16
    blocked = mamba2.hidden(w, sizes, toks, "highest")
    whole = mamba2.hidden(w, sizes, toks[:, :32], "highest")  # one block
    np.testing.assert_allclose(np.asarray(blocked[:, :32]),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- operations, bytes

def test_mamba2_params_by_hand():
    s = manifest.config("mamba2-2.7b.L4")["model"]
    # in_proj: z, x (2 x 5120), B, C (2 x 128), dt (80); out_proj 5120
    layer = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560   # 40,181,760
    head = 2560 * 50280                                       # 128,716,800
    assert mamba2.matmul_params(s) == 4 * layer + head == 289_443_840
    conv = 4 * 4 * (5120 + 2 * 128)                           # 86,016
    assert mamba2.conv_params(s) == conv
    # per layer: ln 2560, conv biases 5376, A_log + D + dt_bias 240,
    # gated norm 5120; the final norm 2560
    assert mamba2.vector_params(s) == 4 * 13_296 + conv + 2560
    # per slot: 4 x (80 x 64 x 128 SSM + 4 x 5376 conv) words
    assert mamba2.state_words(s) == 4 * (655_360 + 21_504) == 2_707_456


def test_mamba2_decode_launch_by_hand():
    s = manifest.config("mamba2-2.7b.L4")["model"]
    positions = [99, 199, 299, 399, 499, 599, 699, 799]
    flops, nbytes = mamba2.decode_launch(s, positions)
    # two per weight multiply-add, plus 5 per SSM state element
    per_token = 2 * (289_443_840 + 86_016) + 4 * 655_360 * 5
    assert mamba2.token_flops(s, 0) == mamba2.token_flops(s, 10_000) \
        == per_token == 592_166_912
    assert flops == 8 * per_token
    weights_b = (289_443_840 + 4 * 13_296 + 86_016 + 2560) * 2
    embed = 8 * 2560 * 2
    state = 2 * 8 * 2_707_456 * 4
    logits = 8 * 50280 * 4
    assert nbytes == weights_b + embed + state + logits == 754_098_304
    # least time on a v5e: bound by bytes, 0.921 ms
    p = manifest.peaks()["TPU v5 lite"]
    assert nbytes / p["hbm_bytes_per_s"] == pytest.approx(0.9208e-3,
                                                          rel=1e-3)
    assert nbytes / p["hbm_bytes_per_s"] > flops / p["bf16_flops_per_s"]
