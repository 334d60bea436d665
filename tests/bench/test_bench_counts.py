"""The peaks table and the analytic counts, against hand-worked numbers
for qwen3-4b (Qwen/Qwen3-4B config.json at 4 of 36 layers), its sizes
read by its architecture module, ``bench/arch/Qwen3ForCausalLM.py``."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import counts, harness, peaks  # noqa: E402


@pytest.fixture(scope="module")
def dims():
    c = json.loads((BENCH / "configs/qwen3-4b.json").read_text())
    return harness.load_arch(c).dims(c)


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bytes_per_s"] == 200e9          # 1,600 Gbit/s
    with pytest.raises(KeyError, match="not in the peaks table"):
        peaks.peaks_for("TPU v4")


def test_qwen3_4b_parameter_counts(dims):
    # per layer: q 2560x4096 + k, v 2560x1024 each + o 4096x2560 + 3 x 2560x9728
    assert dims.layer_matmul_params == 100_925_440
    assert dims.trunk_params == 4 * 100_925_440
    assert dims.head_params == 2560 * 151_936
    # with the norms (4 x (2x2560 + 2x128) + 2560 = 24,064) this is the
    # 792,681,984 parameters the program holds at 4 layers
    assert dims.matmul_params + 24_064 == 792_681_984


def test_decode_prefill_and_train_flops(dims):
    n = 792_657_920
    assert counts.decode_token_flops(dims, 100) == 2 * n + 4 * 4 * 32 * 128 * 100
    # 3 prompt tokens at positions 5, 6, 7 attend over 6, 7, 8 positions
    assert counts.prefill_flops(dims, 5, 3) == (
        2 * dims.trunk_params * 3 + 4 * 4 * 32 * 128 * (6 + 7 + 8)
        + 2 * dims.head_params)
    # 2 real tokens: 6N each, and 3x the attention over 1 and 2 positions
    assert counts.train_flops(dims, [2]) == 6 * n * 2 + 3 * 4 * 4 * 32 * 128 * 3


def test_paged_attention_work_counts_written_lengths_only(dims):
    flops, nbytes = counts.paged_attn_work(dims, [10, 20])
    assert flops == 4 * 32 * 128 * 30
    # q and out: 2 slots x 32 heads x 128 x 2 bytes each; K and V: 30
    # positions x 8 kv heads x 128 x 2 bytes each
    assert nbytes == 2 * 2 * 32 * 128 * 2 + 2 * 30 * 8 * 128 * 2
    t, bound = counts.roofline_time(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
