"""The one traffic generator: expands a traffic file into a replayed trace.

A serving mix is data: two length distributions, an optional shared document
in front of every prompt, the number of clients and the mix's own
``trace_seed``. The trace (every length, in order) is a pure function of the
file, so it is the same in every run and on every commit; ``--seed`` only
makes the token ids. Clients take the next session from the list when their
last one ends, and every request decodes to its full output length, so the
sequence of batch compositions never depends on weights or on the clock.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Ask:
    prompt_len: int   # tokens after the document (the whole prompt without one)
    output_len: int


@dataclasses.dataclass(frozen=True)
class Session:
    index: int        # position in the trace; names the document's token ids
    document_len: int  # shared tokens in front of every ask of the session, 0 for none
    asks: tuple


def _draw(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
        lo = hi = int(spec["value"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


def expand(traffic: dict) -> list:
    """The trace: ``trace_sessions`` sessions, the same for every ``--seed``."""
    rng = np.random.default_rng(int(traffic["trace_seed"]))
    n = int(traffic["trace_sessions"])
    per = int(traffic.get("asks_per_session", 1))
    prompts = _draw(rng, traffic["prompt"], n * per).reshape(n, per)
    outputs = _draw(rng, traffic["output"], n * per).reshape(n, per)
    doc = int(traffic.get("document_tokens", 0))
    return [Session(i, doc, tuple(Ask(int(p), int(o)) for p, o in zip(prompts[i], outputs[i])))
            for i in range(n)]


def stagger(session: Session, client: int, clients: int) -> Session:
    """A client's first session, cut so that the population starts spread
    over its phases and the warm-in can be short. A one-ask session gets
    (clients - client)/clients of its output: admission is serial, so the
    first client admitted keeps its whole output and the last one a sliver,
    and the first round's requests end one after another from the moment
    the last of them is admitted (cutting the first clients instead sends
    them to the back of the queue while the rest still wait in it). A
    longer session loses its first ``client mod asks`` asks."""
    asks = session.asks
    if len(asks) == 1:
        out = max(1, math.ceil(asks[0].output_len * (clients - client) / clients))
        asks = (Ask(asks[0].prompt_len, out),)
    else:
        asks = asks[client % len(asks):]
    return dataclasses.replace(session, asks=asks)


def document_tokens(seed: int, session: Session, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 1, session.index])
    return rng.integers(0, vocab, session.document_len, dtype=np.int32)


def ask_tokens(seed: int, session: Session, ask_index: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 2, session.index, ask_index])
    return rng.integers(0, vocab, session.asks[ask_index].prompt_len, dtype=np.int32)


def longest_request(traffic: dict, max_cache_len: int) -> int:
    """Tokens of the trace's longest request, prompt and output; it has to fit a slot."""
    longest = max(s.document_len + a.prompt_len + a.output_len for s in expand(traffic) for a in s.asks)
    if longest > max_cache_len:
        raise ValueError(f"{traffic.get('name')}: a request of {longest} tokens exceeds max_cache_len {max_cache_len}")
    return longest
