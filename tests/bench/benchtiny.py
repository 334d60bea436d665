"""A tiny copy of the benchmark for CPU tests: the real ``bench/`` tree
and ``BENCHMARK.json``, plus a tiny configuration and a tiny mix added as
new files, exactly as a later change adds a cell.  The configuration keeps
qwen3-4b's ``architectures``, so its program configuration, weights,
reference block and counts come from ``bench/arch/Qwen3ForCausalLM.py``."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY = dict(name="tiny", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_hidden_layers=2, vocab_size=512)

# sound readings of the tiny cell on the CPU: served ~0.027, loss
# 0.002-0.005, gradient 0.0017-0.0035, change 0.0034-0.0068, synced
# weights 0.027-0.076 (few elements cross a bfloat16 rounding at this
# size); the float8 control reads 0.34, 0.034, 0.047 and 0.020, half of
# the batch 0.26 (loss) and 0.10 (gradient), a skipped sync 1
MIXES = {
    "rl": ("rlvr_grpo8_short", dict(
        group_size=4, batch=8, slots=8, max_seq_len=48, pool=8,
        prompt_len={"dist": "uniform", "min": 8, "max": 16},
        new_tokens={"dist": "fixed", "value": 24},
        limits={"served_logprob_gap": 0.1,
                "grad_norm_gap": 0.02, "change_norm_gap": 0.012,
                "synced_change_gap": 0.2})),
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/qwen3-4b.json").read_text())
    cfg.update(TINY)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    for kind, (base, over) in MIXES.items():
        mix = json.loads((root / f"bench/traffic/{base}.json").read_text())
        mix.update(over)
        (root / f"bench/traffic/tiny_{kind}.json").write_text(json.dumps(mix))
        bench["workloads"].append({"name": f"tiny-{kind}", "config": "tiny",
                                   "traffic": f"tiny_{kind}", "chips": 1,
                                   "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rlvr_colocated-qwen3-4b" in m.get("workloads", []):
            m["workloads"].append("tiny-rl")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def harness(root: pathlib.Path):
    for p in (str(REPO / "bench"), str(REPO / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import harness as h
    return h


def run(root: pathlib.Path, kind: str, *, seed: int = 2 ** 31 + 7,
        seconds: float = 1.0, control=None):
    """One run of a tiny cell on the CPU, skipping the look for a chip;
    returns ``(outcome, result line)``."""
    import jax
    h = harness(root)
    ctx = h.cell_context(f"tiny-{kind}", seed=seed, seconds=seconds,
                         trace=False, t_start=time.monotonic(),
                         devices=jax.devices()[:1],
                         peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                         root=root, control=control)
    out = h.run_driver(ctx, root=root)
    return out, h.result_line(ctx, out)
