"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the window served is drawn from the seed: the one with the
most served tokens, then one from every other slot that served a token,
a finished one where the slot has one. A fault confined to one slot
thus reaches the sample. A request still running at the close adds the
tokens it had been served. The plain reference of the configuration's
architecture (``architectures/<name>.py``) then runs once over each
prompt with its served tokens, and at each position where a token was
served reads how far the reference's logit of that token lies below its
best logit, in standard deviations of the reference's logits there. The widest such
gap over the sample is compared with the configuration's limit.

The control reads the same gap for the token that the float8 control
ranks first at each of those positions.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from . import arch

#: sequences the reference runs at once: every run of a cell reuses one
#: compiled reference of (CHECK_BATCH, max_len)
CHECK_BATCH = 8
_TAG_SAMPLE = 5


def sample(served, seed: int) -> list:
    """Requests to check: the most-served first, then one from each other
    slot, drawn from the seed, finished requests before running ones."""
    pool = [s for s in served if s.stamps]
    if not pool:
        return []
    first = max(pool, key=lambda s: len(s.stamps))
    order = np.random.default_rng([int(seed), _TAG_SAMPLE]).permutation(
        len(pool))
    drawn = sorted((pool[i] for i in order), key=lambda s: not s.finished)
    picked, slots = [first], {first.slot}
    for s in drawn:
        if s.slot not in slots:
            picked.append(s)
            slots.add(s.slot)
    return picked


def served_rows(picked):
    """Reference inputs: each prompt with its served tokens but the last,
    the (sequence, position) rows where a served token was predicted, and
    those tokens."""
    seqs, seq_idx, pos, toks = [], [], [], []
    for i, s in enumerate(picked):
        out = [int(t) for t in s.req.out[:len(s.stamps)]]
        P = len(s.prompt)
        seqs.append(np.concatenate([np.asarray(s.prompt, np.int32),
                                    np.asarray(out[:-1], np.int32)]))
        seq_idx += [i] * len(out)
        pos += range(P - 1, P - 1 + len(out))
        toks += out
    return seqs, (np.asarray(seq_idx), np.asarray(pos)), np.asarray(toks)


def compare(conf: dict, seed: int, picked, max_len: int,
            control: bool = False) -> dict:
    """Readings of the served sample against the reference (and, with
    `control`, of the float8 control at the same positions), in blocks of
    CHECK_BATCH sequences padded to (CHECK_BATCH, max_len)."""
    if not picked:
        return {"max_gap_sd": float("inf"), "tokens": 0}
    reference = arch.of(conf)
    t = time.perf_counter()
    wts = reference.derive_weights(conf, seed)
    t_w = time.perf_counter() - t
    g, gc = [], []
    for b in range(0, len(picked), CHECK_BATCH):
        seqs, rows, toks = served_rows(picked[b:b + CHECK_BATCH])
        gb, gcb = reference.gaps(conf, wts, seqs, rows, toks, control,
                                 shape=(CHECK_BATCH, max_len))
        g.append(gb)
        gc.append(gcb)
    g = np.concatenate(g)
    longest = max(len(s.prompt) + len(s.stamps) - 1 for s in picked)
    print(f"reference seconds: weights {t_w:.1f}, forward "
          f"{time.perf_counter() - t - t_w:.1f} over {len(picked)} "
          f"sequences of {longest} tokens at most", file=sys.stderr,
          flush=True)
    out = {"max_gap_sd": float(np.max(g)), "tokens": int(len(g)),
           "requests": len(picked),
           "exact_share": float(np.mean(g <= 0.0))}
    if control:
        out["control_max_gap_sd"] = float(np.max(np.concatenate(gc)))
    return out
