"""Megakernel matmul tasks take the kernel's own TM × TN tile.

The kernel copies and multiplies TN-wide weight rows whatever a matmul
task's width, so ``compile_decode_megakernel`` cuts every matmul into
``min(rows, max_rows)``-row tiles of at most TN columns, while every
other kind keeps ``DecomposeConfig``'s partition.  Plans only: no heap,
no kernel run.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.decompose import DecomposeConfig, _partition_primary
from repro.core.graph import OpKind
from repro.kernels.megakernel.desc import KIND_CODES
from repro.kernels.megakernel.ops import compile_decode_megakernel

MM = KIND_CODES[OpKind.MATMUL]


def _matmul_tiles(plan):
    """{op_id: sorted output regions} of the plan's matmul tasks."""
    g, tg = plan.compiled.graph, plan.compiled.tg
    tiles = {}
    for t in tg.tasks.values():
        if not t.is_dummy and g.op(t.op_id).kind == OpKind.MATMUL:
            r = t.out_regions[g.op(t.op_id).outputs[0]]
            tiles.setdefault(t.op_id, []).append((r.starts, r.stops))
    return {k: sorted(v) for k, v in tiles.items()}


def test_published_widths_plan_cuts_matmuls_to_the_kernel_tile():
    """deepseek-7b at published widths, one layer, 8 slots × 1024: the
    benchmark cell's plan.  Each weight row is copied about once."""
    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=1)
    plan = compile_decode_megakernel(cfg, 8, 1024)
    st = plan.statics
    assert (st["TN"], st["TM"], st["TK"]) == (4096, 8, 11008)
    mm = plan.descs[plan.descs[:, 0] == MM].astype(np.int64)
    assert len(mm) <= 40
    assert plan.pipeline_stats()["tile_fill"] >= 0.95
    g = plan.compiled.graph
    weight_words = sum(math.prod(g.spec(op.inputs[1]).shape)
                       for op in g.ops if op.kind == OpKind.MATMUL)
    assert weight_words <= mm[:, 3].sum() * st["TN"] <= 1.05 * weight_words
    assert plan.compiled_layout_errors() == []
    w2 = compile_decode_megakernel(cfg, 8, 1024, num_workers=2)
    assert _matmul_tiles(w2) == _matmul_tiles(plan)


@pytest.mark.parametrize("arch,batch,max_rows", [
    ("deepseek-7b", 4, 8),
    ("deepseek-7b", 8, 2),
    ("mamba2-2.7b", 2, 8),
    ("granite-moe-1b-a400m", 2, 8),
])
def test_reduced_plan_tiles(arch, batch, max_rows):
    """A matmul wider than TN gets ceil(cols / TN) column tiles of
    min(rows, max_rows) rows; every other kind keeps the partition
    ``DecomposeConfig`` gives it."""
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=1)
    plan = compile_decode_megakernel(cfg, batch, 16, max_rows=max_rows)
    tn = plan.statics["TN"]
    g = plan.compiled.graph
    tiles = _matmul_tiles(plan)
    default = DecomposeConfig(max_rows=max_rows)
    wide = 0
    for op in g.ops:
        spec = g.spec(op.outputs[0])
        if op.kind == OpKind.MATMUL:
            rows, cols = spec.shape
            row = min(rows, max_rows)
            n_col = math.ceil(cols / tn)
            wide += n_col > 1
            assert len(tiles[op.op_id]) == math.ceil(rows / row) * n_col
            for starts, stops in tiles[op.op_id]:
                assert stops[0] - starts[0] == min(row, rows - starts[0])
                assert stops[1] - starts[1] <= tn
        else:
            got = sorted((r.starts, r.stops)
                         for t in plan.compiled.tg.tasks.values()
                         if t.op_id == op.op_id and not t.is_dummy
                         for r in [t.out_regions[op.outputs[0]]])
            want = sorted((r.starts, r.stops)
                          for r in _partition_primary(op, spec, default))
            assert got == want, op.kind
    assert wide, "no matmul wider than TN: the column cut went untested"
