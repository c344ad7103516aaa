"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the window served is drawn from the seed: the one with the
most served tokens, then others at random until the sample holds
CHECK_TOKENS served tokens or CHECK_REQUESTS requests. Finished requests
come first; a request still running at the close adds the tokens it had
been served. The plain reference then runs once over each prompt with
its served tokens, and at each position where a token was served reads
how far the reference's logit of that token lies below its best logit,
in standard deviations of the reference's logits there. The widest such
gap over the sample is compared with the configuration's limit.

The control reads the same gap for the token that the float8 control
ranks first at each of those positions.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from . import reference

CHECK_TOKENS = 512
CHECK_REQUESTS = 8
_TAG_SAMPLE = 5


def sample(served, seed: int) -> list:
    """Requests to check: the most-served first, then a seeded draw."""
    pool = [s for s in served if s.stamps]
    if not pool:
        return []
    first = max(pool, key=lambda s: len(s.stamps))
    rest = [s for s in pool if s is not first]
    order = np.random.default_rng([int(seed), _TAG_SAMPLE]).permutation(
        len(rest))
    finished = [rest[i] for i in order if rest[i].finished]
    running = [rest[i] for i in order if not rest[i].finished]
    picked, n_tok = [first], len(first.stamps)
    for s in finished + running:
        if n_tok >= CHECK_TOKENS or len(picked) >= CHECK_REQUESTS:
            break
        picked.append(s)
        n_tok += len(s.stamps)
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
    `control`, of the float8 control at the same positions). Sequences are
    padded into one (CHECK_REQUESTS, max_len) batch, so every run of a
    cell reuses one compiled reference."""
    if not picked:
        return {"max_gap_sd": float("inf"), "tokens": 0}
    t = time.perf_counter()
    wts = reference.derive_weights(conf, seed)
    t_w = time.perf_counter() - t
    seqs, rows, toks = served_rows(picked)
    g, gc = reference.gaps(conf, wts, seqs, rows, toks, control,
                           shape=(CHECK_REQUESTS, max_len))
    print(f"reference seconds: weights {t_w:.1f}, forward "
          f"{time.perf_counter() - t - t_w:.1f} over {len(seqs)} sequences "
          f"of {max(len(s) for s in seqs)} tokens at most",
          file=sys.stderr, flush=True)
    out = {"max_gap_sd": float(np.max(g)), "tokens": int(len(g)),
           "requests": len(picked),
           "exact_share": float(np.mean(g <= 0.0))}
    if control:
        out["control_max_gap_sd"] = float(np.max(gc))
    return out
