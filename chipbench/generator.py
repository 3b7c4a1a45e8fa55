"""Traffic and training rows, made from data files and a seed.

A serve mix is a fixed table of (prompt length, answer length, gap)
rows built from the quantiles of the distributions its file names.  The
seed decides the ORDER in which the rows are sent and the token
contents, never the multiset: the work offered per period of the table
is identical in every run.  (The first benchmark drew lengths i.i.d.;
its tokens/s was then the sample mean of whatever lengths happened to
complete, 3 % from run to run.)
"""

from __future__ import annotations

import math
import statistics
import threading
import time

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(spec: dict, n: int) -> list[float]:
    """The n mid-point quantiles ((i + 0.5) / n) of one distribution,
    clipped to the file's ``min`` / ``max``, ascending."""
    kind = spec["dist"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "lognormal":
            x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
        elif kind == "exponential":
            x = -spec["mean"] * math.log1p(-u)
        elif kind == "uniform":
            x = spec["min"] + u * (spec["max"] - spec["min"])
        elif kind == "constant":
            x = spec["value"]
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        out.append(min(spec.get("max", x), max(spec.get("min", x), x)))
    return out


def _paired(values: list, pair, n: int) -> list:
    """Column order relative to the ascending prompt column: ``same``,
    ``anti`` (long prompts get the short end) or a fixed shuffle named
    by an integer in the traffic file — never the run's seed."""
    if pair == "same":
        return values
    if pair == "anti":
        return values[::-1]
    order = np.random.default_rng(int(pair)).permutation(n)
    return [values[j] for j in order]


def build_table(mix: dict) -> list[tuple[int, int, float]]:
    """The mix's rows (prompt tokens, answer tokens, gap seconds), in
    the file's canonical order.  A mix with a ``rate_rps`` has its gaps
    rescaled so that one period of the table lasts rows / rate."""
    n = int(mix["rows"])
    prompts = [int(round(x)) for x in quantiles(mix["prompt"], n)]
    answers = _paired([int(round(x)) for x in quantiles(mix["answer"], n)],
                      mix["answer"].get("pair", "same"), n)
    if mix.get("gap"):
        gaps = quantiles(mix["gap"], n)
        scale = (n / float(mix["rate_rps"])) / sum(gaps)
        gaps = _paired([g * scale for g in gaps],
                       mix["gap"].get("pair", "same"), n)
    else:
        gaps = [0.0] * n
    return list(zip(prompts, answers, gaps))


def seeded_order(table: list, seed: int, strata: int = 8) -> list[int]:
    """A permutation of the table's rows from the seed, stratified by
    tokens per row: every run of ``strata`` consecutive requests holds
    one row of each weight class, so a window that ends part-way through
    a period still saw close to the period's mean."""
    n = len(table)
    rng = np.random.default_rng(seed)
    by_weight = sorted(range(n), key=lambda i: (table[i][0] + table[i][1], i))
    strata = max(1, min(strata, n))
    classes = [by_weight[k * n // strata:(k + 1) * n // strata]
               for k in range(strata)]
    for c in classes:
        rng.shuffle(c)
    order = []
    for j in range(max(len(c) for c in classes)):
        block = [c[j] for c in classes if j < len(c)]
        rng.shuffle(block)
        order.extend(block)
    return [int(i) for i in order]


class RequestStream:
    """The endless request sequence of one run: the table in the seed's
    order, replayed cyclically, with token contents from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.table = build_table(mix)
        self.order = seeded_order(self.table, seed,
                                  int(mix.get("strata", 8)))
        self._rng = np.random.default_rng([seed, 1])
        self._vocab = int(vocab)
        self._i = 0

    def next(self) -> tuple[np.ndarray, int, float]:
        prompt_len, answer_len, gap = self.table[
            self.order[self._i % len(self.order)]]
        self._i += 1
        tokens = self._rng.integers(0, self._vocab, size=prompt_len,
                                    dtype=np.int32)
        return tokens, answer_len, gap


class Sent:
    """One request as the generator saw it."""

    __slots__ = ("req", "due", "sent", "prompt_len", "answer_len")

    def __init__(self, req, due, sent, prompt_len, answer_len):
        self.req, self.due, self.sent = req, due, sent
        self.prompt_len, self.answer_len = prompt_len, answer_len


class LoadThread(threading.Thread):
    """Offers the stream to ``submit(tokens, answer_len)`` until stopped.

    Open loop (``backlog`` None): each request is due one gap after the
    previous one's due time, whatever the server does; ``Sent.sent -
    Sent.due`` is how late the generator ran.  Closed backlog: keeps
    ``backlog`` requests queued (``queued()`` reads the server's queue
    depth), so no arrival schedule exists and the scheduler never
    starves."""

    def __init__(self, stream: RequestStream, submit, *, backlog=None,
                 queued=None, clock=time.monotonic, sleep=time.sleep):
        super().__init__(name="chipbench-load", daemon=True)
        self.stream, self.submit = stream, submit
        self.backlog, self.queued = backlog, queued
        self.clock, self.sleep = clock, sleep
        self.sent: list[Sent] = []
        self.error = None
        self._halt = threading.Event()

    def stop(self) -> None:
        self._halt.set()

    def run(self) -> None:
        try:
            if self.backlog is None:
                self._open_loop()
            else:
                self._closed_backlog()
        except BaseException as e:   # noqa: BLE001 - read by the main thread
            self.error = e

    def _send(self, tokens, answer_len, due) -> None:
        req = self.submit(tokens, answer_len)
        self.sent.append(Sent(req, due, self.clock(), len(tokens),
                              answer_len))

    def _open_loop(self) -> None:
        due = self.clock()
        while not self._halt.is_set():
            tokens, answer_len, gap = self.stream.next()
            due += gap
            wait = due - self.clock()
            while wait > 0 and not self._halt.is_set():
                self.sleep(min(wait, 0.05))
                wait = due - self.clock()
            if self._halt.is_set():
                return
            self._send(tokens, answer_len, due)

    def _closed_backlog(self) -> None:
        while not self._halt.is_set():
            for _ in range(self.backlog - self.queued()):
                tokens, answer_len, _gap = self.stream.next()
                self._send(tokens, answer_len, self.clock())
            self.sleep(0.005)


def lm_rows(n: int, block_size: int, vocab: int, seed: int):
    """Token rows with learnable structure, (inputs, targets) int32
    ``[n, block_size]`` — a copy of ``models/gpt.py synthetic_lm_dataset``
    (next token = a fixed permutation of the previous one, 10 % noise),
    so the yardstick's data does not move when the program's does."""
    rng = np.random.default_rng(seed)
    perm = np.random.default_rng(7).permutation(vocab)
    seqs = [rng.integers(0, vocab, size=(n, 1))]
    for _ in range(block_size):
        nxt = perm[seqs[-1]]
        noise = rng.integers(0, vocab, size=(n, 1))
        seqs.append(np.where(rng.random((n, 1)) < 0.1, noise, nxt))
    toks = np.concatenate(seqs, axis=1).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
