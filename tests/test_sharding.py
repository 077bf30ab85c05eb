"""ShardingPolicy: divisibility guards, spec trees match param trees, and
the dry-run spec builder lowers on a small in-process mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.distributed.sharding import ShardingPolicy
from repro.models import init_cache, init_params


def _mesh11():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def test_param_specs_cover_tree():
    mesh = _mesh11()
    for arch in ("deepseek-7b", "jamba-1.5-large-398b",
                 "llama4-maverick-400b-a17b", "mamba2-2.7b"):
        cfg = get_config(arch)
        policy = ShardingPolicy.for_shape(cfg, mesh, SHAPES["train_4k"])
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
        specs = policy.param_specs(params)
        assert (jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P))
                == jax.tree.structure(params))
        # every spec rank matches its leaf rank
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
        for p, s in zip(flat_p, flat_s):
            assert len(s) <= p.ndim, (s, p.shape)


def test_vocab_guard():
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("granite-moe-1b-a400m")   # vocab 49155, not /16
    policy = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"])
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    specs = policy.param_specs(params)
    assert specs["embed"][0] is None          # vocab axis not sharded
    cfg2 = get_config("deepseek-7b")          # vocab 102400 = 16·6400
    policy2 = ShardingPolicy.for_shape(cfg2, mesh, SHAPES["decode_32k"])
    assert policy2._vocab_ok


def test_decode_weight_layout_choice():
    """Small archs serve TP-only (no FSDP gathers); ≥100B fall back 2D."""
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    small = ShardingPolicy.for_shape(get_config("qwen2-vl-2b"), mesh,
                                     SHAPES["decode_32k"])
    big = ShardingPolicy.for_shape(get_config("qwen1.5-110b"), mesh,
                                   SHAPES["decode_32k"])
    assert small.fsdp_axes == ()
    assert big.fsdp_axes == ("data",)


def test_cache_specs_and_kv_seq_shard():
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("qwen2-vl-2b")            # kv=2, not /16
    policy = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"])
    assert policy.kv_seq_shard == "tp"         # flash-decode over model
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 1024, jnp.bfloat16))
    specs = policy.cache_specs(cache)
    assert specs["k"][3] == "model"            # S axis sharded
    cfg2 = get_config("mistral-nemo-12b")      # kv=8... not /16 either
    cfg3 = get_config("deepseek-7b")           # kv=32 = 16·2
    p3 = ShardingPolicy.for_shape(cfg3, mesh, SHAPES["decode_32k"])
    assert p3.kv_seq_shard is None


def test_long500k_policy():
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("jamba-1.5-large-398b")
    policy = ShardingPolicy.for_shape(cfg, mesh, SHAPES["long_500k"])
    assert not policy.shard_batch                  # batch 1
    assert policy.kv_seq_shard == "dp"             # seq over data axes


def test_pure_dp_override():
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("qwen1.5-110b")
    pol = ShardingPolicy.for_shape(cfg, mesh, SHAPES["train_4k"],
                                   overrides={"pure_dp": True})
    assert pol.tp is None and pol.tp_size == 1
    assert "model" in pol.dp_axes
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    specs = pol.param_specs(params)
    # weights fully sharded over the joint data axes, no TP dimension
    assert specs["blocks"]["attn"]["wq"] == P(None, None,
                                              ("data", "model"), None)
    # activations never reference the model axis as TP
    assert pol.act_spec("mlp_hidden", 4)[-1] is None
    # MoE archs must refuse pure-DP (experts need the EP axis)
    import pytest as _pytest
    with _pytest.raises(AssertionError):
        ShardingPolicy.for_shape(get_config("granite-moe-1b-a400m"), mesh,
                                 SHAPES["train_4k"],
                                 overrides={"pure_dp": True})


def test_batch_smaller_than_dp_disables_batch_sharding():
    """decode batch < dp size: shard_batch turns off, batch axes drop the
    data spec, and the flash-decode KV-seq fallback lands on the *data*
    axes (the model axis keeps the heads whenever they divide)."""
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    small = dataclasses.replace(SHAPES["decode_32k"],
                                global_batch=3)          # 3 !% 16
    cfg = get_config("deepseek-7b")                      # kv=32 = 16·2
    pol = ShardingPolicy.for_shape(cfg, mesh, small)
    assert not pol.shard_batch and pol.dp is None
    assert pol.batch_spec(2) == P(None, None)
    assert pol.act_spec("resid", 2) == (None, None)
    # non-dividing KV heads + unsharded batch → seq over the data axes
    cfg2 = get_config("qwen2-vl-2b")                     # kv=2, not /16
    pol2 = ShardingPolicy.for_shape(cfg2, mesh, small)
    assert pol2.kv_seq_shard == "dp"
    cache = jax.eval_shape(lambda: init_cache(cfg2, 3, 64, jnp.bfloat16))
    # PartitionSpec reads a one-axis tuple back as the bare axis name
    assert pol2.cache_specs(cache)["k"][3] == "data"


def test_kv_nondividing_flash_decode_specs():
    """KV heads not dividing the model axis: the cache's head axis stays
    replicated and the seq axis takes the model axis instead — and the
    kv activation spec drops its head sharding too."""
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("mistral-nemo-12b")                 # kv=8, not /16
    pol = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"])
    assert pol.kv_seq_shard == "tp"
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 64, jnp.bfloat16))
    spec = pol.cache_specs(cache)["k"]
    assert spec[3] == "model" and spec[4] is None        # seq, not heads
    assert pol.act_spec("kv", 3)[1] is None


def test_pure_dp_decode_policy():
    """pure-DP decode: the model axis folds into data parallelism — no
    TP anywhere (params, acts, caches), batch over the joint axes."""
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("deepseek-7b")
    pol = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"],
                                   overrides={"pure_dp": True})
    assert pol.tp_disabled and pol.tp_size == 1 and pol.tp is None
    assert pol.dp_axes == ("data", "model")
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    # the model axis may only appear folded inside the joint dp tuple,
    # never as a bare TP dimension
    flat = jax.tree.leaves(pol.param_specs(params),
                           is_leaf=lambda x: isinstance(x, P))
    for s in flat:
        assert all(part != "model" for part in s), \
            f"bare TP axis leaked into {s}"
    assert pol.act_spec("mlp_hidden", 3)[-1] is None


def test_kv_dtype_override_affects_layout_choice():
    import numpy as _np
    devs = _np.array(jax.devices()[:1] * 256).reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("deepseek-7b")
    base = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"])
    fp8 = ShardingPolicy.for_shape(cfg, mesh, SHAPES["decode_32k"],
                                   overrides={"kv_dtype_bytes": 1})
    # halving the cache cannot make the layout *more* gathered
    assert len(fp8.fsdp_axes) <= len(base.fsdp_axes)
