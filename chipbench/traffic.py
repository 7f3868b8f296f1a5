"""The one traffic generator: a mix's parameters plus a seed give the
contexts and every client's requests.

A mix (``traffic/<name>.json``) gives the context lengths, the question
and answer lengths, the number of closed-loop clients and how many
requests each client has ready. It gives the context lengths under one
of two keys, which says what becomes of a context: ``documents`` are
stored, and every request reuses its document's KV; ``contexts`` are
stored nowhere, and every request sends its context whole to be
prefilled. Client ``c`` asks of the contexts in a fixed rotation
starting at context ``c``, the same for every seed: the seed draws only
the tokens of the contexts and questions, so every seed sends the same
sizes in the same order and the work of a window does not change with
it. The draws do not depend on the key: two mixes of the same lengths
send the same prompts.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

#: what every mix file must give
KEYS = ("question_tokens", "answer_tokens", "clients", "loop",
        "requests_per_client", "check_requests", "grace_seconds")
#: the keys that give a mix's context lengths, stored or not: a mix
#: gives exactly one
LENGTHS = ("documents", "contexts")


@dataclasses.dataclass(frozen=True)
class Ask:
    """One request: a context (a stored document where the mix reuses
    it) followed by a fresh question."""
    client: int
    doc: int
    question: np.ndarray
    answer_tokens: int


@dataclasses.dataclass
class Traffic:
    documents: List[np.ndarray]  # the contexts, stored or not
    asks: List[List[Ask]]  # per client, in the order it sends them
    mix: dict
    vocab_size: int

    def prompt(self, ask: Ask) -> np.ndarray:
        return np.concatenate([self.documents[ask.doc], ask.question])

    @property
    def reuses(self) -> bool:
        """Whether the contexts are stored and every request reuses its
        context's KV."""
        return "documents" in self.mix

    @property
    def request_lengths(self) -> List[int]:
        """Prompt plus answer tokens of a request, per context."""
        return [len(d) + self.mix["question_tokens"]
                + self.mix["answer_tokens"] for d in self.documents]

    @property
    def longest_request(self) -> int:
        return max(self.request_lengths)


def check_mix(mix: dict) -> None:
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    given = [k for k in LENGTHS if k in mix]
    if len(given) != 1:
        raise ValueError(f"traffic mix gives {given}: exactly one of "
                         f"{list(LENGTHS)}")
    if mix["loop"] != "closed":
        raise ValueError(f"loop {mix['loop']!r}: only closed-loop clients "
                         "are generated")


def lengths(mix: dict) -> List[int]:
    """The mix's context lengths, under whichever key it gives them."""
    return next(mix[k] for k in LENGTHS if k in mix)


def generate(mix: dict, seed: int, vocab_size: int) -> Traffic:
    check_mix(mix)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7a11]))
    docs = [rng.integers(0, vocab_size, n, dtype=np.int64)
            for n in lengths(mix)]
    n_docs = len(docs)
    asks = [[Ask(c, (c + k) % n_docs,
                 rng.integers(0, vocab_size, mix["question_tokens"],
                              dtype=np.int64), mix["answer_tokens"])
             for k in range(mix["requests_per_client"])]
            for c in range(mix["clients"])]
    return Traffic(docs, asks, mix, vocab_size)


def warmup_asks(traffic: Traffic, seed: int) -> List[Ask]:
    """One request per context with a one-token answer, all sent at
    once: each document is fetched, decoded on the host, restored and
    its question prefilled, or, where nothing is reused, each whole
    prompt is prefilled, as in the window, with no decode step.
    Questions are drawn apart from the window's."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3a4f]))
    return [Ask(i, i, rng.integers(0, traffic.vocab_size,
                                   traffic.mix["question_tokens"],
                                   dtype=np.int64), 1)
            for i in range(len(traffic.documents))]
