"""Faults planted under the timed path, at a tiny size on the CPU: each
must turn ``correct`` false.  The cells run on one chip, so no exchange
between chips can be left out; the nearest exchange, the weight sync from
the trainer to the rollout engine, is left out instead."""
import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import benchtiny  # noqa: E402
from repro.rollout import paged_engine  # noqa: E402
from repro.train import trainer  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


def _unchanged_step(grads, opt_state, cfg, dtypes):
    params = jax.tree_util.tree_map(lambda p, d: p.astype(d),
                                    opt_state["master"], dtypes)
    return params, opt_state, {"grad_norm": 0.0, "lr": 0.0}


def _half_batch(build):
    def half(self, samples):
        return build(self, samples[:len(samples) // 2])
    return half


def _sync_skipped(update):
    """The engine goes through the sync (its weight epoch advances, its
    prefix cache is flushed) but keeps serving the weights it had."""
    def skipped(self, params):
        update(self, self.params)
    return skipped


def _altered_tokens(sample):
    def altered(key, logits, **kw):
        tokens, lp = sample(key, logits, **kw)
        return (tokens + 1) % logits.shape[-1], lp
    return altered


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "sync_skipped"])
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(trainer, "adamw_update", _unchanged_step)
        watch = ("grad_norm_gap", "change_norm_gap")
    elif fault == "half_batch":
        monkeypatch.setattr(trainer.HostTrainer, "build_batch",
                            _half_batch(trainer.HostTrainer.build_batch))
        watch = ("grad_norm_gap",)
    elif fault == "sync_skipped":
        monkeypatch.setattr(
            paged_engine.PagedDecodeEngine, "update_weights",
            _sync_skipped(paged_engine.PagedDecodeEngine.update_weights))
        watch = ("synced_change_gap",)
    else:
        monkeypatch.setattr(paged_engine, "sample_tokens",
                            _altered_tokens(paged_engine.sample_tokens))
        watch = ("served_logprob_gap",)
    out, line = benchtiny.run(root, "rl")
    assert not line["correct"], line["checks"]
    for name in watch:
        c = line["checks"][name]
        assert np.isfinite(c["value"]) and c["value"] > c["limit"], (name, c)
