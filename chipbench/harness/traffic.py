"""The one traffic generator: every mix is a data file of its parameters.

Every mix is a closed loop: one client per engine slot, each sending its
next request the moment its last one finishes, with no think time. A mix
file (``traffic/<mix>.json``) gives

    max_len           the engine's max_len; prompt + output never exceed it
    prompt, output    lognormal lengths {"median", "sigma", "min", "max"},
                      clipped to [min, max]

Lengths are not sampled freely: each distribution is a fixed grid of
GRID quantiles, walked in one fixed shuffled order, and the k-th
follow-up request sent (by whichever client) takes the k-th sizes. A
window sees only a few follow-ups, so sizes drawn per seed would change
how much work a window holds; here every seed gets the same sizes, and
the seed draws the token ids (uniform over the vocabulary) and deals the
initial requests to the clients. In a closed loop the order in which
clients finish depends only on token counts, so a seed fixes every input
of a run.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_TAG_INITIAL, _TAG_FOLLOW, _TAG_ORDER, _TAG_WARM = 1, 2, 3, 4
#: fixes the pairing of the initial requests' prompt, output and progress
#: quantiles and the order of the follow-ups' sizes, for every seed
_SIZES_SEED = 20250117
#: quantile points per length distribution
GRID = 64


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (S,) int32
    max_new: int
    served_before: int = 0      # answer tokens folded into the prompt


def length_grid(dist: dict, n: int) -> np.ndarray:
    """``n`` quantiles (i + 0.5) / n of a lognormal clipped to [min, max]."""
    nd = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)


class Traffic:
    def __init__(self, params: dict, vocab: int, n_slots: int, seed: int):
        self.p = params
        self.vocab = vocab
        self.seed = int(seed)
        self.max_len = params["max_len"]
        self.n_clients = n_slots
        self.prompts = length_grid(params["prompt"], GRID)
        self.outputs = length_grid(params["output"], GRID)
        self.k = 0                                  # follow-ups handed out

    def _rng(self, *tags) -> np.random.Generator:
        return np.random.default_rng([self.seed, *tags])

    def _request(self, tag: int, k: int, prompt_len: int, out_len: int,
                 served_before: int = 0) -> Request:
        out_len = max(1, min(out_len, self.max_len - prompt_len))
        toks = self._rng(tag, k).integers(0, self.vocab, size=prompt_len,
                                          dtype=np.int32)
        return Request(toks, int(out_len), served_before)

    def initial(self) -> list[Request]:
        """One request per client, as if the loop had run for a while:
        prompt P + g tokens, budget O - g, with g uniform over [0, O).
        The C (P, O, g) triples are the same for every seed; the seed
        only deals them to the clients."""
        C = self.n_clients
        fixed = np.random.default_rng(_SIZES_SEED)
        P = length_grid(self.p["prompt"], C)
        O = length_grid(self.p["output"], C)[fixed.permutation(C)]
        u = (fixed.permutation(C) + 0.5) / C
        O = np.minimum(O, self.max_len - P)
        g = np.floor(u * O).astype(np.int64)
        deal = self._rng(_TAG_INITIAL).permutation(C)
        return [self._request(_TAG_INITIAL, c, int(P[i] + g[i]),
                              int(O[i] - g[i]), int(g[i]))
                for c, i in enumerate(deal)]

    def next(self) -> Request:
        """The next request any client sends."""
        n = len(self.prompts)
        block, i = divmod(self.k, n)
        rng = np.random.default_rng([_SIZES_SEED, _TAG_ORDER, block])
        p_idx, o_idx = rng.permutation(n)[i], rng.permutation(n)[i]
        req = self._request(_TAG_FOLLOW, self.k, int(self.prompts[p_idx]),
                            int(self.outputs[o_idx]))
        self.k += 1
        return req

    def warmup(self, lengths) -> list[Request]:
        """Requests of the given prompt lengths and a 2-token budget, for
        compiling every shape the cell uses before the window."""
        return [self._request(_TAG_WARM, j, int(n), 2)
                for j, n in enumerate(lengths)]
