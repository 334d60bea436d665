"""The comparison that decides ``correct``, at a tiny size on the CPU: a
sound run passes, and the control fails: the program's own float8
rollout path, and in the trainer's place the reference computed in
float8 or fed half of each batch.  ``kind`` names the driver.  The sound
run's record also carries the program's counters, with its spans left
off."""
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import benchtiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def sound(root):
    """One sound untraced run of each kind, with every call that would turn
    the program's spans on recorded."""
    from repro.core import tracing
    runs = {}

    def run(kind):
        if kind not in runs:
            enabled = []
            enable = tracing.enable
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(tracing, "enable",
                           lambda: (enabled.append(True), enable()))
                out, line = benchtiny.run(root, kind)
            runs[kind] = out, line, enabled
        return runs[kind]
    return run


@pytest.mark.parametrize("kind", ["rl"])
def test_sound_run_is_correct(sound, kind):
    out, line, _ = sound(kind)
    assert line["correct"], line["checks"]
    assert out.attempted > 0 and out.failed == 0
    assert list(line)[-1] == "checks"
    e2e = line["metrics"]
    assert e2e["setup_s"]["value"] > 0
    assert e2e["rl_step_s"]["value"] > 0


@pytest.mark.parametrize("kind", ["rl"])
def test_untraced_record_carries_the_program_counters(sound, kind):
    from benchlib import program_trace
    from repro.core import tracing
    out, _, enabled = sound(kind)
    rec = out.record
    # the queue wait of exactly the samples sample_p90_s counted
    assert len(rec["queue_waits"]) == out.attempted
    assert all(0 <= w < math.inf for w in rec["queue_waits"])
    assert program_trace.queue_wait_p90(rec) >= 0
    # the window's growth of every total_* counter of the engines
    totals = rec["engine_totals"]
    assert totals["total_tokens_decoded"] == rec["tokens_decoded"] > 0
    assert totals["total_decode_steps"] == rec["decode_steps"] > 0
    assert totals["total_decode_context"] == rec["decode_context"] > 0
    assert isinstance(rec["trainer_totals"], dict)
    # spans stayed off: none enabled, none read
    assert enabled == []
    assert tracing.span("roll.a") is tracing.span("roll.b")
    assert "program_spans" not in rec and "program_idle" not in rec


@pytest.mark.parametrize("kind", ["rl"])
def test_float8_control_is_not_correct(root, kind):
    out, line = benchtiny.run(root, kind, control="fp8")
    assert not line["correct"], line["checks"]
    c = line["checks"]
    # the engine in float8; in the trainer's place the reference in
    # float8, and fed half of each batch
    failing = ["served_logprob_gap", "grad_norm_gap.fp8",
               "change_norm_gap.fp8", "grad_norm_gap.half_batch"]
    for name in failing:
        assert c[name]["value"] > c[name]["limit"], (name, c[name])
