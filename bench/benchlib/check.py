"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

- ``served_logprob_gap``: over every served token of the checked samples,
  the widest gap between the log-probability the engine recorded for the
  token it sampled and the reference's log-probability of that token at
  that position.  The traffic samples at temperature 1 (GRPO needs the
  behaviour policy's own log-probabilities), so the check reads the
  recorded log-probability, which the sampler computes from the same
  logits it samples from, rather than a greedy argmax.
- ``train_loss_gap`` (printed, not compared): the widest gap between the
  program's loss and the reference's over the first optimizer steps, in
  units of the mean |advantage| (see ``training``).  The float8 control
  reads it at no more than about twice the sound runs, so no limit
  between the two would hold.
- ``grad_norm_gap`` / ``change_norm_gap``: by the worst leaf, the gap
  between the program's norm and the reference's of the first gradient
  as the optimizer got it, and of the parameters' change after the first
  steps, over the larger of that leaf's reference norm and the median
  leaf's.  Leaves whose reference gradient is under a thousandth of the
  median leaf's are left out: Adam moves them by round-off alone.
- ``synced_change_gap``: the weight sync, read where it lands.  By the
  same worst-leaf measure, the gap between the change from the seed's
  weights of what each rollout engine serves after the first optimizer
  steps' syncs and the change of the reference's parameters after those
  steps, cast to the types the weights are served in.  An engine that
  keeps serving the seed's weights reads 1.
"""
from __future__ import annotations

import functools
import sys

import numpy as np

from benchlib import reference

ATTN_SCORE_BUDGET = 512 * 2 ** 20   # bytes of float32 scores per block of rows


def _check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def served(cfg: dict, arch, seed: int, samples, limits: dict,
           cast=None) -> dict:
    """``samples``: ``(prompt, served tokens, recorded logprobs)``; ``arch``
    the configuration's architecture module."""
    seqs = [np.concatenate([np.asarray(p, np.int32).ravel(),
                            np.asarray(r, np.int32).ravel()])
            for p, r, _ in samples]
    t = max(len(s) for s in seqs)
    rows = max(1, ATTN_SCORE_BUDGET // (t * t * arch.dims(cfg).heads * 4))
    ref = reference.served_logprobs(cfg, arch, seed, seqs,
                                    rows=min(rows, len(seqs)), cast=cast)
    gap = 0.0
    for (p, r, lp), rl in zip(samples, ref, strict=True):
        n = len(np.asarray(p).ravel())
        eng = np.asarray(lp, np.float64)[:len(r)]
        gap = max(gap, float(np.max(np.abs(eng - rl[n:n + len(r)]))))
    return {"served_logprob_gap": _check(gap, limits["served_logprob_gap"])}


def norm_gap(prog: dict, ref: dict, keep, label: str = "") -> float:
    """The worst leaf's gap; ``label`` prints which leaf it was."""
    med = float(np.median([ref[n] for n in keep]))
    gap, leaf = max((abs(prog[n] - ref[n]) / max(ref[n], med), n) for n in keep)
    if label:
        print(f"bench: {label} worst leaf {leaf} program {prog[leaf]!r} "
              f"reference {ref[leaf]!r} median {med!r}", file=sys.stderr)
    return gap


def _half(feed: dict) -> dict:
    """The fault "half of the batch left out, the mean taken over the
    rest": the first half of the rows."""
    n = len(feed["tokens"]) // 2
    return {k: v[:n] for k, v in feed.items()}


def training(cfg: dict, arch, seed: int, feeds: list, progress: dict,
             mix: dict, controls=()) -> dict:
    """The reference follows the first optimizer steps on the same feeds;
    ``progress`` holds the program's readings (losses, first-gradient leaf
    norms, float32 parameters after the last warm step).  Each name in
    ``controls`` adds the readings of the reference put in the program's
    place, suffixed with the name: ``fp8`` computed with float8 matmul
    operands, ``half_batch`` fed half of each batch."""
    limits = mix["limits"]
    run = functools.partial(reference.train_steps, cfg, arch, seed,
                            loss_cfg=mix["loss"], opt=mix["optimizer"])
    ref = run(feeds)
    ref_params = ref.pop("params")
    ref_change = reference.change_norms(cfg, arch, seed, ref_params)
    grads = ref["grad_norm"]
    med = float(np.median(list(grads.values())))
    keep = [n for n, v in grads.items() if v >= 1e-3 * med]
    # the surrogate's value is the advantages times the (near-1) ratio:
    # with GRPO's zero-mean advantages it is the advantage-weighted gap of
    # log-probabilities, so its gap is read in units of the mean |advantage|
    scale = float(np.mean([np.abs(f["advantages"]).max(-1).mean()
                           for f in feeds]))
    readings = {"": progress}
    for name in controls:
        prog = (run(feeds, cast=reference.fp8_cast) if name == "fp8"
                else run([_half(f) for f in feeds]))
        prog["master"] = prog.pop("params")
        readings["." + name] = prog
    out = {}
    for suffix, prog in readings.items():
        change = reference.change_norms(cfg, arch, seed, prog.pop("master"))
        loss_gap = max(abs(a - b) for a, b in
                       zip(prog["loss"], ref["loss"], strict=True)) / scale
        print(f"bench: train_loss_gap{suffix}={loss_gap!r} (not compared)",
              file=sys.stderr)
        out["grad_norm_gap" + suffix] = _check(
            norm_gap(prog["grad_norm"], grads, keep, "grad_norm_gap" + suffix),
            limits["grad_norm_gap"])
        out["change_norm_gap" + suffix] = _check(
            norm_gap(change, ref_change, keep, "change_norm_gap" + suffix),
            limits["change_norm_gap"])
    # the weight sync: what the engines serve against the reference's
    # parameters after as many steps, in the served types
    if progress["synced_after"] != len(feeds):
        raise ValueError(f"the engines' weights were read after "
                         f"{progress['synced_after']} optimizer steps, the "
                         f"reference follows {len(feeds)}")
    ref_served = reference.change_norms(cfg, arch, seed, ref_params,
                                        served=True)
    del ref_params
    gap = max(norm_gap(reference.change_norms(cfg, arch, seed, w, served=True),
                       ref_served, keep, "synced_change_gap")
              for w in progress["synced"])
    out["synced_change_gap"] = _check(gap, limits["synced_change_gap"])
    return out
