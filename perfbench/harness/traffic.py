"""The one general traffic generator: reads a mix's parameters from its
data file and makes the run's inputs from the seed.

Every seed gives the same *multiset* of sizes in another order, with other
token ids: every run offers the same work.

Serving mixes (``clients``, ``prompt_tokens``, ``output_tokens``,
``per_client``): a base list of ``per_client`` (prompt, output) lengths,
prompts spread evenly from ``min`` to ``max`` and outputs likewise, paired
by a fixed stride so that long prompts do not all meet long outputs. Every
client works through the whole base list, each in an order of its own
drawn from the seed, and starts over when it reaches the end. With
``stagger`` each client first sends one short request (``stagger_prompt``
tokens in, ``(i + 1) / clients`` of ``stagger_output`` out) so that the
clients' phases are spread by construction when the window opens.

Training mixes (``rows``, ``seq_len``): ``rows`` sequences of uniform
random token ids, all different.
"""
from __future__ import annotations

import numpy as np


def _spread(lo: int, hi: int, n: int):
    return [int(round(x)) for x in np.linspace(lo, hi, n)]


def base_lengths(mix: dict):
    """The fixed multiset of (prompt, output) lengths of one client."""
    n = int(mix["per_client"])
    prompts = _spread(mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"], n)
    outputs = _spread(mix["output_tokens"]["min"], mix["output_tokens"]["max"], n)
    stride = next(s for s in range(max(n // 2, 1), n + 1)
                  if np.gcd(s, n) == 1) if n > 1 else 1
    return [(prompts[i], outputs[(i * stride + 1) % n]) for i in range(n)]


class ServeTraffic:
    """Per client, an endless stream of ``(prompt ids, max_new_tokens)``."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab_size)
        self.clients = int(mix["clients"])
        self.base = base_lengths(mix)
        root = np.random.SeedSequence(int(seed))
        self._rngs = [np.random.default_rng(s) for s in root.spawn(self.clients)]
        self._orders = [list(r.permutation(len(self.base))) for r in self._rngs]
        self._sent = [0] * self.clients

    def stagger_request(self, client: int):
        """The short first request that sets this client's phase."""
        out = max(1, round(self.mix["stagger_output"] * (client + 1) / self.clients))
        return self._ids(client, int(self.mix["stagger_prompt"])), int(out)

    def next_request(self, client: int):
        k = self._sent[client]
        self._sent[client] += 1
        prompt, out = self.base[self._orders[client][k % len(self.base)]]
        return self._ids(client, prompt), out

    def _ids(self, client: int, n: int):
        return self._rngs[client].integers(0, self.vocab, n, dtype=np.int32)

    def longest_timeline(self) -> int:
        return max(p + o for p, o in self.base)


def train_rows(mix: dict, vocab_size: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    return rng.integers(0, int(vocab_size),
                        (int(mix["rows"]), int(mix["seq_len"])), dtype=np.int32)
