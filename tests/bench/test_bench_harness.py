"""The harness on the CPU: cells, configurations and metrics are found by
file name alone; traffic is reproducible from the seed; and the run
refuses a machine without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import benchtiny  # noqa: E402

REPO = benchtiny.REPO


def test_new_cell_config_and_metric_need_only_new_files(tmp_path):
    root = benchtiny.make_root(tmp_path)
    (root / "bench/metrics/echo_decode_steps.rl.py").write_text(
        "def read(rec):\n    return rec['decode_steps']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "echo_decode_steps.rl", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "paged engine",
        "moves": "rl_step_s", "workloads": ["tiny-rl"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    h = benchtiny.harness(root)
    ctx = h.cell_context("tiny-rl", seed=1, seconds=1, trace=True, t_start=0.0,
                         devices=[], peaks=None, root=root)
    assert ctx.config["hidden_size"] == 64 and ctx.mix["batch"] == 8
    assert "echo_decode_steps.rl" in h.layer_names(ctx.bench, "tiny-rl")
    assert "echo_decode_steps.rl" not in h.layer_names(ctx.bench,
                                                      "rlvr_colocated-qwen3-4b")
    assert h.e2e_names(ctx.bench, "tiny-rl") == ["rl_step_s", "sample_p90_s",
                                                 "setup_s"]
    out = h.Outcome(e2e={}, record={"decode_steps": 7, "tokens_decoded": 56},
                    checks={}, attempted=0, failed=0, memory_peak_bytes=0)
    got = h.read_layer_metrics(ctx, out, root=root)
    assert got["echo_decode_steps.rl"] == {"value": 7.0, "unit": "steps"}
    assert got["decode_batch.rl"] == {"value": 8.0, "unit": "tokens/step"}
    # a reader with nothing to read is left out of the line, never 0
    assert "idle_share.rl" not in got


def test_traffic_is_reproducible_and_seeds_share_their_sizes():
    sys.path.insert(0, str(REPO / "bench"))
    from benchlib import traffic
    mix = {"pool": 64, "prompt_len": {"dist": "uniform", "min": 128, "max": 512},
           "new_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 64, "max": 1536}}

    def draw(seed, n=64):
        s = traffic.GroupStream(mix, 151936, seed)
        return [next(s) for _ in range(n)]

    a, b, c = draw(2 ** 31 + 5), draw(2 ** 31 + 5), draw(2 ** 31 + 6)
    for (ga, pa, na), (gb, pb, nb) in zip(a, b, strict=True):
        assert ga == gb and na == nb and np.array_equal(pa, pb)
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, c, strict=True))
    # one pass over the pool: the same lengths and budgets in another order
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in c)
    assert sorted(n for *_, n in a) == sorted(n for *_, n in c)
    assert all(128 <= len(p) <= 512 and 64 <= n <= 1536 for _, p, n in a)
    assert min(int(p.min()) for _, p, _ in a) >= traffic.FIRST_ID


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rlvr_colocated-qwen3-4b",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_backend():
    r = _run(REPO)
    assert r.returncode == 2, r.stderr[-2000:]
    assert "not a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_run_fails_without_the_program(tmp_path):
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
