"""The open loop (serve): single-row requests arrive at ``rate_per_s``
on a Poisson schedule (``arrivals``: only ``poisson``) and go to
``ForestServeEngine.submit`` when due, whether or not earlier ones have
been answered.  Each is timed from when it was due.  The gaps between
arrivals are the same set for every seed (the exponential distribution's
quantiles), in an order the seed draws, so that a seed changes which
rows are sent and in what rhythm, not how much work a window holds.

Request ``i`` sends row ``i`` modulo ``pool_rows`` of a pool of rows
drawn from the seed.  The comparison reads every answered request of the
window against the reference's score of its row.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax.numpy as jnp
import numpy as np

from chipbench import check, data, loads, reference


@dataclasses.dataclass
class Requests:
    """The window's requests; times in seconds from the window's start,
    NaN where a request never got that far."""

    due_s: np.ndarray
    sent_s: np.ndarray
    admitted_s: np.ndarray
    finished_s: np.ndarray
    gave_up_s: float               # when the wait for answers ended
    ok: np.ndarray                 # answered without an error
    pool_index: np.ndarray         # the pool row each request sent
    predictions: np.ndarray        # [n] the answers (NaN where none)

    @property
    def latency_s(self) -> np.ndarray:
        """Due to answered; a request never answered counts as answered
        when the wait ended, so it lands in the tail."""
        end = np.where(self.ok, self.finished_s, self.gave_up_s)
        return end - self.due_s


def arrival_gaps(rate_per_s: float, n: int, seed: int) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate_per_s``: the
    midpoint quantiles of the exponential distribution, shuffled by
    ``seed``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    return np.random.default_rng([int(seed) % (1 << 62), n]).permutation(gaps)


def _requests(mix: dict, seconds: float) -> int:
    return max(1, round(float(mix["rate_per_s"]) * seconds))


class OpenServe:
    """Open-loop requests into the serve plane's coalescer."""

    MODEL = "tenant"

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.serve.forest import ForestServeEngine
        if mix.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self.mix = dict(mix)
        self.seed = seed
        self.pool = data.request_pool(seed, int(mix["pool_rows"]),
                                      cfg["dataset"]["features"])
        self.serve = ForestServeEngine(algorithm=cfg["algorithm"],
                                       plan=cfg["plans"]["serve"])
        # compiles one plan per rung of the bucket ladder
        self.serve.register_model(self.MODEL,
                                  loads.program_forest(seed, cfg))
        self.serve.start()

    def run(self, seconds: float, annotate: bool) -> Requests:
        """Offer ``seconds`` of the mix and wait for every answer."""
        n = _requests(self.mix, seconds)
        gaps = arrival_gaps(float(self.mix["rate_per_s"]), n, self.seed)
        due = np.cumsum(gaps) - gaps[0]
        index = np.arange(n) % self.pool.shape[0]
        sent, admitted, finished = (np.full(n, np.nan) for _ in range(3))
        ok = np.zeros(n, bool)
        preds = np.full(n, np.nan, np.float32)
        # requests not yet answered, oldest first.  Answers are read off
        # and the request dropped as soon as it is done, so that the
        # generator holds no more live objects than the serve plane's
        # queue: a client holding every request would make each of the
        # interpreter's full garbage collections, and so the latency
        # tail, grow with the window.
        outstanding: deque = deque()

        def record(i, r):
            ok[i] = r.error is None
            if r.admitted_at:
                admitted[i] = r.admitted_at - t0
            if ok[i]:
                finished[i] = r.finished_at - t0
                preds[i] = r.predictions[0]

        def harvest():
            while outstanding and outstanding[0][1].done.is_set():
                record(*outstanding.popleft())

        submit, pool = self.serve.submit, self.pool
        t0 = time.perf_counter()
        i = 0
        while i < n:
            now = time.perf_counter() - t0
            if now < due[i]:
                harvest()
                now = time.perf_counter() - t0
                if now < due[i]:
                    time.sleep(due[i] - now)
                continue
            with loads.annotation("bench.submit", annotate):
                while i < n and due[i] <= now:
                    r = submit(self.MODEL, pool[index[i]:index[i] + 1])
                    sent[i] = time.perf_counter() - t0
                    outstanding.append((i, r))
                    i += 1
        gave_up = time.perf_counter() - t0 + loads.ANSWER_WAIT_S
        with loads.annotation("bench.wait", annotate):
            while outstanding:
                i, r = outstanding.popleft()
                if r.done.wait(max(0.0, gave_up - (time.perf_counter() - t0))):
                    record(i, r)
        return Requests(due_s=due, sent_s=sent, admitted_s=admitted,
                        finished_s=finished, gave_up_s=gave_up, ok=ok,
                        pool_index=index, predictions=preds)

    def warm(self) -> None:
        """A second of the mix's own traffic: the ticker, the coalescer
        and the compiled plans of the rungs it fills."""
        self.run(loads.WARM_S, False)

    def measure(self, seconds: float, annotate: bool) -> loads.Window:
        before = loads.counter_values()
        r = self.run(seconds, annotate)
        n = len(r.due_s)
        return loads.Window(seconds=float(np.nanmax(r.sent_s)), attempted=n,
                            failed=int(n - r.ok.sum()),
                            counters=loads.counter_deltas(before),
                            requests=r)

    def close(self) -> None:
        self.serve.stop()
        self.serve = None


build = OpenServe


def readings(cfg: dict, mix: dict, seed: int, window: loads.Window, *,
             narrow: bool = False) -> dict[str, float]:
    r = window.requests
    pool = jnp.asarray(data.request_pool(seed, int(mix["pool_rows"]),
                                         cfg["dataset"]["features"]))
    forest = check.forest(cfg, seed)
    want = reference.predict(forest, pool)
    answered = np.flatnonzero(r.ok)
    idx = jnp.asarray(r.pool_index[answered])
    got = reference.predict(forest, pool, narrow=True)[idx] if narrow \
        else jnp.asarray(r.predictions[answered])
    gap, nonfinite = check.gap(got, want[idx]) if answered.size \
        else (0.0, 0)
    return {"max_prob_gap": gap, "nonfinite": nonfinite,
            "missing": int(len(r.ok) - answered.size)}


def positions(cfg: dict, mix: dict, seconds: float) -> loads.Window:
    n = _requests(mix, seconds)
    nan = np.full(n, np.nan)
    r = Requests(due_s=nan, sent_s=nan, admitted_s=nan, finished_s=nan,
                 gave_up_s=0.0, ok=np.ones(n, bool),
                 pool_index=np.arange(n) % int(mix["pool_rows"]),
                 predictions=np.zeros(n, np.float32))
    return loads.Window(seconds=seconds, attempted=n, failed=0, counters={},
                        requests=r)
