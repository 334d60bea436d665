"""The comparison that decides ``correct``, at a tiny size on the CPU: a
sound run passes, and the control fails: the program's own float8
rollout path, and in the trainer's place the reference computed in
float8 or fed half of each batch.  ``kind`` names the driver."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import benchtiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("kind", ["rl"])
def test_sound_run_is_correct(root, kind):
    out, line = benchtiny.run(root, kind)
    assert line["correct"], line["checks"]
    assert out.attempted > 0 and out.failed == 0
    assert list(line)[-1] == "checks"
    e2e = line["metrics"]
    assert e2e["setup_s"]["value"] > 0
    assert e2e["rl_step_s"]["value"] > 0


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
