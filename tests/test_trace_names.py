"""The program names the benchmark's trace readers look for: the engine's
jitted step and the trainer's.  A rename would silently null the metrics
that read them, so it fails here instead.  (The decode kernel's name is
pinned in ``test_chip_compile.py``, where it is compiled for the chip.)"""
import pathlib
import sys

import jax
import numpy as np

from conftest import tiny
from repro.algos import LossConfig
from repro.core.types import Sample, next_uid
from repro.models import get_api
from repro.rollout.paged_engine import PagedDecodeEngine
from repro.train.optimizer import OptConfig
from repro.train.trainer import HostTrainer, TrainerConfig

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "bench"))
from benchlib.readings import TRAIN_MODULE  # noqa: E402

ENGINE_MODULE = "jit__step_impl"     # the engine step's program in a trace


def _module_name(lowered) -> str:
    text = lowered.as_text()
    return text[text.index("module @") + len("module @"):].split()[0]


def test_engine_step_lowers_to_a_stably_named_module():
    cfg = tiny("qwen3-4b", vocab_size=32)
    api = get_api(cfg)
    eng = PagedDecodeEngine(api, api.init(jax.random.PRNGKey(0)),
                            num_slots=2, max_total_len=32, page_size=8,
                            prefill_chunk=8, temperature=0.0)
    jitted, seen = eng._step, []

    def capture(*args):
        seen.append(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args))
        return jitted(*args)

    eng._step = capture
    eng.add_request(0, np.asarray([3, 1, 4], np.int32), 4)
    eng.step()
    assert _module_name(jitted.lower(*seen[0])) == ENGINE_MODULE


def test_train_step_lowers_to_a_stably_named_module():
    cfg = tiny("qwen3-4b", vocab_size=32)
    trainer = HostTrainer(get_api(cfg), jax.random.PRNGKey(0),
                          LossConfig(), OptConfig(),
                          TrainerConfig(max_seq_len=8, group_size=2))
    samples = [Sample(sample_id=next_uid(), prompt_id=0, replica_idx=i,
                      prompt_tokens=np.asarray([3, 1], np.int32),
                      response_tokens=np.asarray([4, 1, 5], np.int32),
                      logprobs=np.zeros(3, np.float32), reward=float(i),
                      group_id=0) for i in range(2)]
    batch = trainer.build_batch(samples)
    rest = {k: v for k, v in trainer.state.items() if k != "params"}
    lowered = trainer._train_step.lower(trainer.state["params"], rest, batch)
    assert _module_name(lowered) == TRAIN_MODULE == "jit_split_step"
