"""The one traffic generator.  A mix is a JSON file under ``traffic/`` that
names its loop and sizes; this module turns it and ``--seed`` into prompts.

Closed loop, one caller: a call is one batch of ``streams_per_slot * slots``
prompts of ``prompt_len`` tokens, each to be answered with ``gen_len``
greedy tokens; the next call starts when the previous one returns.  Token
ids are uniform over the published vocabulary.  Every seed gets the same
sizes, so the seed changes the token values and not the work."""

from __future__ import annotations

import numpy as np

LOOPS = ("closed",)


class Traffic:
    def __init__(self, mix: dict, *, slots: int, vocab: int, seed: int):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"unknown loop {mix['loop']!r}; known: {LOOPS}")
        self.slots = slots
        self.vocab = vocab
        self.seed = seed
        self.prompt_len = int(mix["prompt_len"])
        self.gen_len = int(mix["gen_len"])
        self.streams = int(mix["streams_per_slot"]) * slots
        self.check_requests = int(mix["check_requests"])
        self.check_block_rows = int(mix["check_block_rows"])

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng((self.seed, *stream))

    def _prompts(self, rng, n: int) -> np.ndarray:
        return rng.integers(0, self.vocab, (n, self.prompt_len)
                            ).astype(np.int32)

    def call_prompts(self, i: int) -> np.ndarray:
        """The prompts of the window's ``i``-th call."""
        return self._prompts(self._rng(0, i), self.streams)

    def warmup_prompts(self) -> np.ndarray:
        """One wave: a prompt per slot."""
        return self._prompts(self._rng(1), self.slots)

    def check_sample(self, n_calls: int) -> list:
        """(call, stream) pairs of finished requests the check compares:
        streams drawn from the seed, taken in turn from the first call and
        from the last, so the sample does not hang on how many calls the
        window held."""
        streams = self._rng(2).choice(self.streams, replace=False,
                                      size=min(self.check_requests,
                                               self.streams))
        return [(0 if i % 2 == 0 else n_calls - 1, int(s))
                for i, s in enumerate(streams)]
