"""Shared benchmark utilities: CSV emission + calibrated workloads."""
from __future__ import annotations

from repro.core import simulator as S

# Paper setup (§3.2): Qwen3-8B, 32k context, rollout 256, group 32.
# Base model: ~2k mean response length; Think: ~11k mean, heavy tail.
BASE_LengthS = S.lognormal_lengths(2_000, sigma=1.0, max_tokens=32_768)
THINK_LENGTHS = S.lognormal_lengths(11_000, sigma=0.9, max_tokens=32_768)

ROWS: list[tuple] = []


def emit(name: str, value: float, derived: str = "") -> None:
    ROWS.append((name, value, derived))
    print(f"{name},{value:.6g},{derived}")


def pipeline_base(**overrides) -> S.PipelineConfig:
    # paper setup: 256 prompts x 16 returns = 4096 sequences per step
    # (scaled to 2048 to keep the event heap fast), decode slots 16/GPU,
    # rollout:train cost ratio ~3:1 at 32 GPUs (rollout >70% of step time).
    base = dict(rollout_batch_size=2048, group_size=16, gpus=32,
                slots_per_gpu=16, per_token_time=0.004,
                mu_train_per_sample=0.15, train_overhead=20.0,
                weight_sync_time=3.0, alpha=2.0)
    base.update(overrides)
    return S.PipelineConfig(**base)


def flush_csv(path: str | None = None) -> None:
    if path:
        with open(path, "w") as f:
            f.write("name,value,derived\n")
            for n, v, d in ROWS:
                f.write(f"{n},{v},{d}\n")

