"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in ``PallasProgram.step``, the decode launch the
window drives, on the CPU at a tiny size; the rest of the run is the
harness's own (the look for a chip is the only part skipped)."""
import numpy as np
import pytest

from repro.api.program import PallasProgram


def state_unchanged(orig):
    def step(self, tokens, seq_lens, positions=None):
        saved = self.executor.read_state()
        out = orig(self, tokens, seq_lens, positions)
        self.executor.write_state(saved)
        return out
    return step


def half_batch(orig):
    def step(self, tokens, seq_lens, positions=None):
        out = np.array(orig(self, tokens, seq_lens, positions))
        half = out.shape[0] // 2
        out[half:] = out[:half]         # the second half left out
        return out
    return step


def token_altered(orig):
    def step(self, tokens, seq_lens, positions=None):
        out = np.array(orig(self, tokens, seq_lens, positions))
        top = out.argmax(-1)
        out[np.arange(out.shape[0]), top] = out.min() - 1.0
        return out
    return step


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(tiny_run, monkeypatch, fault):
    monkeypatch.setattr(PallasProgram, "step", fault(PallasProgram.step))
    res = tiny_run("tiny-llama", "deepseek-7b.L1", "tiny-closed")
    assert res["correct"] is False, res["checks"]


def test_unbroken_run_is_correct(tiny_run):
    res = tiny_run("tiny-llama", "deepseek-7b.L1", "tiny-closed", seed=7)
    assert res["correct"] is True, res["checks"]
