"""chip_smoke.py's phases at a tiny width on the CPU.

The script itself refuses to run without a TPU; these tests call its phase
functions directly, with the Pallas kernel in interpret mode, so the
comparison and the train-run checks cannot rot between chip runs.
"""
import dataclasses
import os
import sys
import time

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402


def _small_qwen3_4b(**widths):
    w = dict(d_model=512, num_heads=8, num_kv_heads=2, head_dim=64,
             d_ff=1024, vocab_size=4096)
    w.update(widths)
    return dataclasses.replace(chip_smoke.qwen3_4b(3), **w)


def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys):
    # keep the persistent cache setting out of this process's later tests
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "")
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert '"ok"' not in out.out


@pytest.mark.timeout(240)
def test_chip_smoke_logits_within_tolerance_and_controls_outside():
    cfg = _small_qwen3_4b()
    params = chip_smoke.get_api(cfg).init(jax.random.PRNGKey(chip_smoke.SEED))
    tokens = chip_smoke.compare_tokens(cfg.vocab_size)
    ref = chip_smoke.reference_logits(cfg, params, tokens)
    errs = chip_smoke.paged_logits_errors(cfg, params, tokens, ref,
                                          kv_quant="int8",
                                          attn_impl="kernel_interpret")
    failures = []
    chip_smoke.check_logits("int8", errs, chip_smoke.TOL_INT8, failures)
    assert not failures
    assert errs["control_wrong_pages"] > chip_smoke.TOL_INT8
    assert errs["control_no_scales"] > chip_smoke.TOL_INT8


@pytest.mark.timeout(240)
def test_chip_smoke_train_run_checks_pass():
    failures = []
    pipe = chip_smoke.train_run(_small_qwen3_4b(d_model=64, num_heads=4,
                                                head_dim=16, d_ff=128,
                                                vocab_size=512), failures,
                                attn_impl="kernel_interpret",
                                expect_attn="kernel_interpret",
                                max_new_tokens=16, max_seq_len=32, steps=2)
    assert not failures
    assert len(pipe.trainer.history) == 2


@pytest.mark.timeout(240)
def test_chip_smoke_train_run_fails_fast_on_a_proxy_thread_exception(
        monkeypatch):
    """An exception on the proxy thread is reported as a failure at once,
    not after the controller's batch timeout."""
    from repro.rollout.paged_engine import PagedDecodeEngine

    def broken_step(self):
        raise RuntimeError("injected engine fault")

    monkeypatch.setattr(PagedDecodeEngine, "step", broken_step)
    failures = []
    t0 = time.monotonic()
    chip_smoke.train_run(_small_qwen3_4b(d_model=64, num_heads=4, head_dim=16,
                                         d_ff=128, vocab_size=512), failures,
                         attn_impl="ref", expect_attn="ref",
                         max_new_tokens=16, max_seq_len=32, steps=2)
    assert time.monotonic() - t0 < chip_smoke.BATCH_TIMEOUT_S / 2
    assert any("thread failed" in f and "injected engine fault" in f
               for f in failures), failures
    assert any("train steps completed" in f for f in failures), failures
