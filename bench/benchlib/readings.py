"""Per-layer readings from a run's record: the arithmetic the metric
readers in ``bench/metrics/`` share.  A reading that has nothing to read
returns None, never 0."""
from __future__ import annotations

from statistics import mean
from typing import Optional

from benchlib import counts

TRAIN_MODULE = "jit_split_step"      # the trainer's jitted step, in the trace
KERNEL_OP = "paged_decode_attention"  # the Pallas decode kernel, in the trace


def step_mean(rec: dict, key: str) -> Optional[float]:
    steps = rec.get("steps") or []
    return mean(s[key] for s in steps) if steps else None


def window_calls(rec: dict) -> list:
    t0, t1 = rec["window"]
    return [c for c in rec.get("kernel_calls", []) if t0 <= c[0] <= t1]


def kernel_roofline(rec: dict) -> Optional[float]:
    """Least time of the kernel's algorithmic work at the chip's peaks (per
    call, the larger of compute and memory time) over its device time in
    the trace, in %.  Memory sets it: the work does H / KV = 4 FLOPs per
    byte of K/V, far under the v5e's ~240 FLOPs per byte of HBM."""
    tr = rec.get("trace")
    if not tr or not tr["op_total_s"].get(KERNEL_OP):
        return None
    calls = [c for c in window_calls(rec) if c[1]]
    if not calls:
        return None
    dims: counts.Dims = rec["dims"]
    least = 0.0
    for _, lengths, _ in calls:
        flops, nbytes = counts.paged_attn_work(dims, lengths)
        least += counts.roofline_time(flops, nbytes, rec["peaks"])[0]
    least *= dims.layers
    return 100.0 * least / tr["op_total_s"][KERNEL_OP]


def rollout_flops(rec: dict) -> float:
    """Model FLOPs the engine did in the window: each decoded token at its
    context, and each prefill chunk."""
    dims: counts.Dims = rec["dims"]
    total = 0
    for _, lengths, chunk in window_calls(rec):
        total += sum(counts.decode_token_flops(dims, n) for n in lengths)
        if chunk is not None and chunk[1]:
            total += counts.prefill_flops(dims, chunk[0], chunk[1])
    return float(total)


def train_flops(rec: dict) -> float:
    return float(sum(counts.train_flops(rec["dims"], ls)
                     for ls in rec.get("train_lengths", [])))


def window_mfu(rec: dict, flops_fn) -> Optional[float]:
    if not rec.get("trace"):
        return None
    flops = flops_fn(rec)
    if flops <= 0:
        return None
    t0, t1 = rec["window"]
    return 100.0 * flops / ((t1 - t0) * rec["chips"] * rec["peaks"]["bf16_flops"])


def train_mfu(rec: dict) -> Optional[float]:
    tr = rec.get("trace")
    busy = tr["module_s"].get(TRAIN_MODULE) if tr else None
    flops = train_flops(rec) if busy else 0
    if not busy or flops <= 0:
        return None
    return 100.0 * flops / (busy * rec["peaks"]["bf16_flops"])


def idle_share(rec: dict) -> Optional[float]:
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def engine_step_ms(rec: dict) -> Optional[float]:
    d = rec.get("engine_step_s") or []
    return 1e3 * mean(d) if d else None


def decode_batch(rec: dict) -> Optional[float]:
    steps = rec.get("decode_steps")
    return rec["tokens_decoded"] / steps if steps else None
