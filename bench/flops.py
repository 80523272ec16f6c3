"""Model FLOPs of the tokens a run served, from the configuration's shapes.

Only the work a served token needs is counted: the weight matmuls of
every prompt token granted and of every decoding slot, attention over
each token's live context, and the vocabulary head once per sampled
token.  Padded lanes, dead slots and the head over prompt lanes the
sampler never reads do not count, so removing them raises the share of
peak; the count can never exceed what the device computed.  What one
token, one key and one head call cost is the architecture's: its family
module gives ``matmul_per_token``, ``attention_per_key`` and ``head``.
"""
from __future__ import annotations

from collections import defaultdict


def prompt_flops(family, cfg: dict, plen: int) -> int:
    """A whole prompt: position p attends to p + 1 keys; one head call
    for the token its last lane samples."""
    return (family.matmul_per_token(cfg) * plen
            + family.attention_per_key(cfg) * plen * (plen + 1) // 2
            + family.head(cfg))


def decode_flops(family, cfg: dict, ctx: int) -> int:
    """One decoding lane attending ``ctx`` keys."""
    return (family.matmul_per_token(cfg) + family.attention_per_key(cfg) * ctx
            + family.head(cfg))


def step_flops(rec) -> dict[int, tuple[str, float]]:
    """Dispatch index -> (program, model FLOPs) for every dispatch
    harvested in the window.

    A dispatch is the mixed program when some request is prefilling
    (between its ``admit`` and its ``first_token``), else the decode
    program.  The engine reports no per-step grant, so a prompt's FLOPs
    are spread evenly over its prefill dispatches.
    """
    cfg, fam = rec.config, rec.family
    span = {}                       # uid -> [first, last] prefill dispatch
    for kind, uid, step, _, _ in rec.events:
        if kind == "admit":
            span[uid] = [step, None]
        elif kind == "first_token" and uid in span:
            span[uid][1] = step - 1
    per_step = defaultdict(float)
    prefilling = defaultdict(bool)
    for uid, (a, f) in span.items():
        if f is None:
            continue
        share = prompt_flops(fam, cfg, rec.prompt_len[uid]) / (f - a + 1)
        for s in range(a, f + 1):
            per_step[s] += share
            prefilling[s] = True
    last: dict[int, int] = defaultdict(int)
    for kind, uid, step, _, data in rec.events:
        if kind == "preempt":
            last[uid] = 0
        if kind != "progress":
            continue
        c0, c = last[uid], data["count"]
        last[uid] = c
        if c0 >= 1 and c > c0:
            per_step[step - 1] += sum(
                decode_flops(fam, cfg, rec.prompt_len[uid] + k)
                for k in range(c0, c))
    out = {}
    for s in {ev[2] - 1 for ev in rec.of("progress")
              if rec.t0 <= ev[3] <= rec.t1}:
        out[s] = ("mixed" if prefilling[s] else "decode", per_step[s])
    return out
