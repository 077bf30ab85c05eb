"""The kernel's ``state_row_copies`` counter counts the row copies that
read or write state, exactly as the plan's descriptors say: per SSM task
a ``HD_SSM``-row state tile loaded and stored for each row and head, per
conv task a ``W_CONV``-row conv-state tile loaded and stored for each
row, per cache update one K/V row stored for each row, and per attention
task each row's live K and V rows for each of its ``NG`` groups."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as mpk
from repro.configs import get_config
from repro.core.graph import OpKind
from repro.kernels.megakernel.desc import KIND_CODES
from repro.models.lm import init_params

B, S = 2, 16


def plan_state_rows(plan, live_lens) -> int:
    """State-row copies of one launch, counted from the descriptors."""
    st = plan.statics
    code = {k: KIND_CODES[k] for k in (
        OpKind.SSM_UPDATE, OpKind.CONV1D_UPDATE, OpKind.CACHE_UPDATE,
        OpKind.ATTENTION_DECODE)}
    n = 0
    for d in plan.descs.astype(np.int64):
        kind, m = d[0], d[1]
        if kind == code[OpKind.SSM_UPDATE]:
            n += m * st["NH_TILE"] * st["HD_SSM"] * 2
        elif kind == code[OpKind.CONV1D_UPDATE]:
            n += m * st["W_CONV"] * 2
        elif kind == code[OpKind.CACHE_UPDATE]:
            n += m
        elif kind == code[OpKind.ATTENTION_DECODE]:
            r0 = d[12] - plan.layout["live_lens"].offset
            n += 2 * st["NG"] * int(np.sum(live_lens[r0:r0 + m]))
    return int(n)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "deepseek-7b"])
def test_state_row_copies_match_the_plan(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prog = mpk.compile(cfg, B, S, backend="megakernel")
    prog.bind(params).init_state()
    lens = np.zeros((B,), np.int32)
    seen = []
    for i in range(3):
        prog.step(np.full((B,), i + 1, np.int32), lens)
        c = prog.executor.pipeline_counters()
        assert c["state_row_copies"] == plan_state_rows(prog.plan, lens + 1)
        assert 0 < c["state_row_copies"] <= c["row_copies"]
        seen.append(c["state_row_copies"])
        lens += 1 + np.arange(B, dtype=np.int32)   # ragged lengths
    # K/V rows grow with the live length; SSM and conv state do not
    assert (len(set(seen)) > 1) == (cfg.family != "ssm")
