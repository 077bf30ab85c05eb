"""The comparison that decides ``correct`` passes the program and fails
its control: the same reference at three-pass bf16 (``high``), one
precision below the configuration's f32 at ``highest``.  At a tiny size on
the CPU, under the cell's own limits, in a closed and an open loop."""
import pytest

CASES = [("tiny-llama", "deepseek-7b.L1", "tiny-closed"),
         ("tiny-llama", "deepseek-7b.L1", "tiny-open")]


@pytest.mark.parametrize("tiny,real,mix", CASES)
def test_program_passes_and_the_control_fails(tiny_run, tiny, real, mix):
    res = tiny_run(tiny, real, mix, control=True)
    assert res["correct"] is True, res["checks"]
    assert res["control"]["correct"] is False, res["control"]
    assert res["attempted"] > 0 and res["failed"] == 0
