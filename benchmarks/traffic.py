"""The one traffic generator.  A mix is a data file under ``traffic/``
(lengths, clients or rate, bursts, sharing); nothing here knows a
cell's or a mix's name.

Every ``--seed`` gets the SAME set of sizes: the lengths come from the
mix's own ``schedule_seed``; the run's seed deals the callers' (or the
arrivals') sequences out in another order and draws every token id.
So two seeds give the program different inputs and the same work.
"""

import math

import numpy as np


def draw_lengths(rng, dist, n):
    """``n`` whole lengths from ``dist``: ``loguniform`` / ``uniform``
    over ``[lo, hi]``, ``lognormal`` (``median``, ``sigma``) clipped to
    ``[lo, hi]``, or ``fixed`` (``value``)."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full((n,), int(dist["value"]), np.int64)
    if kind not in ("loguniform", "uniform", "lognormal"):
        raise ValueError("unknown length distribution %r" % (kind,))
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if kind == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n))
    elif kind == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        x = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def token_ids(seed, stream, index, n, vocab, shared=None):
    """``n`` ids in ``[1, vocab)`` for request ``index`` of ``stream``;
    ``shared`` (ids) replaces the head of the prompt where a mix shares
    prefixes."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    ids = rng.integers(1, vocab, n, dtype=np.int64).astype(np.int32)
    if shared is not None:
        k = min(len(shared), n)
        ids[:k] = shared[:k]
    return ids


def _shared_prefixes(mix, seed, vocab):
    sharing = mix.get("sharing") or {"kind": "none"}
    if sharing["kind"] == "none":
        return None
    if sharing["kind"] != "prefix_pool":
        raise ValueError("unknown sharing kind %r" % (sharing["kind"],))
    rng = np.random.default_rng([int(seed), 0x5EED])
    return [
        rng.integers(1, vocab, int(sharing["tokens"])).astype(np.int32)
        for _ in range(int(sharing["pool"]))
    ]


class ClosedLoop(object):
    """``clients`` callers, each with a fixed sequence of (prompt,
    answer) lengths; a caller sends its next request when its last one
    returned.  ``first_wave: "residual"`` cuts caller ``c``'s first
    answer to the share ``(c + 0.5) / clients`` of its length, as if
    the callers had been running before the run began, so completions
    are spread from the start instead of arriving as one herd."""

    def __init__(self, mix, seed, vocab):
        if mix["loop"] != "closed":
            raise ValueError("not a closed-loop mix: %r" % (mix["loop"],))
        self.clients = n = int(mix["clients"])
        self.per_client = k = int(mix["requests_per_client"])
        self.seed, self.vocab = int(seed), int(vocab)
        sched = np.random.default_rng(int(mix["schedule_seed"]))
        self.prompt_len = draw_lengths(
            sched, mix["prompt_tokens"], n * k).reshape(n, k)
        self.answer_len = draw_lengths(
            sched, mix["answer_tokens"], n * k).reshape(n, k)
        if mix.get("first_wave") == "residual":
            share = (np.arange(n) + 0.5) / n
            self.answer_len[:, 0] = np.maximum(
                1, np.ceil(self.answer_len[:, 0] * share)
            ).astype(np.int64)
        # the seed deals the sequences to the callers in another order
        self.deal = np.random.default_rng(
            [self.seed, 0xDEA1]).permutation(n)
        self._prefixes = _shared_prefixes(mix, seed, vocab)
        self._sent = np.zeros((n,), np.int64)

    def next_request(self, client):
        """The next ``(prompt_ids, answer_tokens)`` of ``client``."""
        k = int(self._sent[client])
        if k >= self.per_client:
            raise RuntimeError(
                "caller %d ran out of its %d requests: raise "
                "requests_per_client in the mix" % (client, k)
            )
        self._sent[client] = k + 1
        seq = int(self.deal[client])
        shared = None
        if self._prefixes is not None:
            shared = self._prefixes[(seq + k) % len(self._prefixes)]
        ids = token_ids(
            self.seed, seq, k, int(self.prompt_len[seq, k]), self.vocab,
            shared,
        )
        return ids, int(self.answer_len[seq, k])

    def prompt_buckets(self, multiple):
        """The plan's prompt lengths rounded up to ``multiple``: the
        shapes a program that pads so has to have compiled."""
        return sorted({
            int(-(-int(v) // multiple) * multiple)
            for v in self.prompt_len.ravel()
        })


def open_schedule(mix, seed, vocab, seconds):
    """An open loop's requests for ``seconds``: ``(due_s, prompt_ids,
    answer_tokens)`` in due order.  Arrivals are Poisson at
    ``rate_per_s``; with ``burst`` (``every_s``, ``size``) a burst of
    ``size`` extra arrivals lands at each multiple of ``every_s``.  The
    gaps and lengths come from ``schedule_seed``; the seed rotates the
    sequence and draws the ids."""
    if mix["loop"] != "open":
        raise ValueError("not an open-loop mix: %r" % (mix["loop"],))
    sched = np.random.default_rng(int(mix["schedule_seed"]))
    rate = float(mix["rate_per_s"])
    n = max(1, int(math.ceil(rate * seconds * 1.5)) + 8)
    due = np.cumsum(sched.exponential(1.0 / rate, n))
    burst = mix.get("burst")
    if burst:
        at = np.arange(1, int(seconds // burst["every_s"]) + 1)
        due = np.concatenate([
            due, np.repeat(at * float(burst["every_s"]), burst["size"])
        ])
    due = np.sort(due[due < seconds])
    m = len(due)
    prompts = draw_lengths(sched, mix["prompt_tokens"], m)
    answers = draw_lengths(sched, mix["answer_tokens"], m)
    shift = int(np.random.default_rng([int(seed), 0xDEA1]).integers(0, m))
    order = np.roll(np.arange(m), shift)
    prefixes = _shared_prefixes(mix, seed, vocab)
    out = []
    for i, j in enumerate(order):
        shared = prefixes[i % len(prefixes)] if prefixes else None
        out.append((
            float(due[i]),
            token_ids(seed, 0, i, int(prompts[j]), vocab, shared),
            int(answers[j]),
        ))
    return out


class OpenLoopSource(object):
    """Iterator over an :func:`open_schedule`: hands a request out no
    sooner than it is due, stamps the due moment (latencies count from
    it, not from when the program pulled the row) and records how late
    the generator itself ran."""

    def __init__(self, schedule, clock, sleep, t0=None):
        self._schedule = list(schedule)
        self._clock, self._sleep = clock, sleep
        self._t0 = clock() if t0 is None else t0
        self._i = 0
        self.due_at = []       # absolute due moment per request
        self.late_s = []       # hand-out moment minus due moment

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= len(self._schedule):
            raise StopIteration
        due, ids, answer = self._schedule[self._i]
        self._i += 1
        wait = self._t0 + due - self._clock()
        if wait > 0:
            self._sleep(wait)
        self.due_at.append(self._t0 + due)
        self.late_s.append(max(0.0, self._clock() - (self._t0 + due)))
        return {"prompt": ids, "max_new": answer}


def packed_row(mix, seed, index, vocab):
    """Row ``index`` of a training feed: documents of lengths drawn
    from ``mix["documents"]``, each opened by ``bos_id``, packed end to
    end into ``seq_len`` tokens (the last one cut).  Every row differs:
    its key is folded from the seed and its index."""
    rng = np.random.default_rng([int(seed), 0x7A11, int(index)])
    n = int(mix["seq_len"])
    ids = rng.integers(2, vocab, n, dtype=np.int64).astype(np.int32)
    at = 0
    while at < n:
        ids[at] = int(mix.get("bos_id", 1))
        at += int(draw_lengths(rng, mix["documents"], 1)[0])
    return ids
