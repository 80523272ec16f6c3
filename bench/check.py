"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample
of the requests finished in the window, drawn from the seed and always
holding the longest of them, is run through the reference once, each
prompt with its served tokens.  For every served token the number read
is how far its reference logit lies below the reference's best logit
at that position (0 where the greedy choices agree).  The widest such
gap over the sample, and the mean gap, are held to the limits the
configuration states; how each limit was set is in PERF.md.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

SEED_CHECK = 0xC4EC


@dataclass(frozen=True)
class Served:
    uid: int
    prompt: list[int]
    tokens: list[int]       # what the program generated


def draw(done: list[Served], seed: int, min_tokens: int,
         max_requests: int) -> list[Served]:
    """The longest request, then others in a seeded order until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not done:
        return []
    done = sorted(done, key=lambda s: s.uid)
    longest = max(done, key=lambda s: (len(s.prompt) + len(s.tokens), -s.uid))
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng([seed % 2**64, SEED_CHECK]).permutation(
        len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def pack(sample: list[Served], row_len: int) -> list[dict]:
    """First-fit rows of ``row_len`` positions.  Each request occupies
    ``prompt + tokens[:-1]``; the position before each served token is
    where its logits are read."""
    rows: list[dict] = []
    for s in sorted(sample, key=lambda s: -(len(s.prompt) + len(s.tokens))):
        seq = s.prompt + s.tokens[:-1]
        if len(seq) > row_len:
            raise ValueError(f"request {s.uid}: {len(seq)} positions do not "
                             f"fit a reference row of {row_len}")
        row = next((r for r in rows if r["used"] + len(seq) <= row_len), None)
        if row is None:
            row = {"used": 0, "tokens": np.zeros(row_len, np.int32),
                   "pos": np.zeros(row_len, np.int32),
                   "seg": np.full(row_len, -1, np.int32),
                   "targets": np.zeros(row_len, np.int32), "read": []}
            rows.append(row)
        lo = row["used"]
        row["tokens"][lo:lo + len(seq)] = seq
        row["pos"][lo:lo + len(seq)] = np.arange(len(seq))
        row["seg"][lo:lo + len(seq)] = s.uid
        first = lo + len(s.prompt) - 1
        row["targets"][first:first + len(s.tokens)] = s.tokens
        row["read"] += [(first + k, s.uid) for k in range(len(s.tokens))]
        row["used"] += len(seq)
    return rows


def read_gaps(rows: list[dict], gap_fn) -> dict[int, list[float]]:
    """uid -> the gap of each of its served tokens.  ``gap_fn(tokens,
    pos, seg, targets)`` returns the reference's gap per position."""
    out: dict[int, list[float]] = {}
    for row in rows:
        gap = np.asarray(gap_fn(row["tokens"], row["pos"], row["seg"],
                                row["targets"]))
        for p, uid in row["read"]:
            out.setdefault(uid, []).append(float(gap[p]))
    return out


def summary(gaps: dict[int, list[float]]) -> dict[str, float | None]:
    """The numbers a limit can hold: the widest gap of any served token,
    the mean gap over all of them, and the share of served tokens that
    are not the reference's best (``None`` where none was read)."""
    flat = [g for gs in gaps.values() for g in gs]
    if not flat:
        return dict.fromkeys(("max_logit_gap", "mean_logit_gap",
                              "off_best_share"))
    return {"max_logit_gap": max(flat),
            "mean_logit_gap": sum(flat) / len(flat),
            "off_best_share": sum(g > 0 for g in flat) / len(flat)}
