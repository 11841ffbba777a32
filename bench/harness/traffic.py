"""One general load generator, driven by a traffic file's parameters.

A traffic file (``bench/traffic/<mix>.json``) holds only data:

  * ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
    or ``"closed"`` (``clients`` callers, each sending its next image only
    after the previous reply);
  * ``rate_per_s`` (open): mean arrival rate of single images;
  * ``clients`` (closed): callers in the loop;
  * ``image_pool``: distinct images drawn from the seed; each request
    sends one of them;
  * ``lead_in_s``: unmeasured traffic before the window opens;
  * ``stragglers``: ``{"count": c, "delay_s": d}`` — c workers, drawn
    from the seed, add d seconds to every subtask.

Every seed gets the same work: an open loop's inter-arrival gaps are the
same set of exponential quantiles in every run, in a seeded order, scaled
to fill the window exactly; only which image each request sends, which
workers straggle and the order of the gaps change with the seed.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64,
                                                          stream]))


def poisson_gaps(rate_per_s: float, seconds: float, seed: int,
                 stream: int) -> np.ndarray:
    """``round(rate * seconds)`` gaps of an exponential distribution (its
    mid-quantiles), shuffled by the seed, summing to ``seconds``."""
    count = max(int(round(rate_per_s * seconds)), 1)
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate_per_s
    gaps = rng(seed, stream).permutation(gaps)
    return gaps * (seconds / gaps.sum())


def arrival_offsets(gaps: np.ndarray) -> np.ndarray:
    """Arrival k at the sum of the first k gaps (the first at 0)."""
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def straggler_delays(n: int, traffic: dict, seed: int) -> np.ndarray:
    """Per-worker added seconds: ``count`` workers drawn from the seed."""
    spec = traffic.get("stragglers") or {}
    delays = np.zeros(n)
    count = int(spec.get("count", 0))
    if count:
        idx = rng(seed, 2).choice(n, size=count, replace=False)
        delays[idx] = float(spec["delay_s"])
    return delays


@dataclasses.dataclass
class Sent:
    """One request as the generator saw it (host ``perf_counter`` times)."""

    image: int
    due_t: float
    sent_t: float
    handle: object

    def done(self) -> bool:
        return self.handle.done()

    def wait(self, timeout: float) -> None:
        """Until answered or ``timeout`` seconds, whichever comes first."""
        try:
            self.handle.result(timeout=timeout)
        except Exception:  # a failed or late answer: ok() tells which
            pass

    def ok(self) -> bool:
        if not self.handle.done():
            return False
        try:
            self.handle.result(timeout=0)
        except Exception:
            return False
        return True

    @property
    def finish_t(self) -> float:
        """Completion on the generator's clock: sent + the handle's
        end-to-end seconds."""
        return self.sent_t + self.handle.latency_s


class OpenLoop:
    """Submits on a schedule from one thread: ``offsets`` (seconds after
    ``t0``) with the image index of each."""

    def __init__(self, submit, offsets, images, t0: float):
        self._submit = submit
        self._offsets = offsets
        self._images = images
        self._t0 = t0
        self.sent: list[Sent] = []
        self.errors: list[BaseException] = []
        self._thread = threading.Thread(target=self._run, name="bench-open",
                                        daemon=True)

    def start(self) -> "OpenLoop":
        self._thread.start()
        return self

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("open-loop generator did not finish")

    def _run(self) -> None:
        try:
            for off, img in zip(self._offsets, self._images):
                due = self._t0 + off
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent = time.perf_counter()
                self.sent.append(Sent(int(img), due, sent,
                                      self._submit(int(img))))
        except BaseException as err:  # reported by the harness
            self.errors.append(err)


class ClosedLoop:
    """``clients`` callers, each with its own seeded image sequence, driven
    from one thread: from ``t_start`` until ``t_stop`` a finished request
    is replaced at once by that caller's next one.  ``completion`` is a
    condition the server notifies on every finish (None: poll)."""

    def __init__(self, submit, clients: int, pool: int, seed: int,
                 completion, t_start: float, t_stop: float):
        self._submit = submit
        self._clients = clients
        self._completion = completion
        self._draws = [rng(seed, 10 + c) for c in range(clients)]
        self._pool = pool
        self._t_start, self._t_stop = t_start, t_stop
        self.sent: list[Sent] = []
        self.errors: list[BaseException] = []
        self._thread = threading.Thread(target=self._run, name="bench-closed",
                                        daemon=True)

    def start(self) -> "ClosedLoop":
        self._thread.start()
        return self

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("closed-loop generator did not finish")

    def _send(self, client: int) -> Sent:
        img = int(self._draws[client].integers(self._pool))
        t = time.perf_counter()
        s = Sent(img, t, t, self._submit(img))
        self.sent.append(s)
        return s

    def _run(self) -> None:
        try:
            while time.perf_counter() < self._t_start:
                time.sleep(0.001)
            live = [self._send(c) for c in range(self._clients)]
            while time.perf_counter() < self._t_stop:
                for c, s in enumerate(live):
                    if s.done():
                        live[c] = self._send(c)
                if self._completion is None:
                    time.sleep(0.0005)
                    continue
                with self._completion:
                    if not any(s.done() for s in live):
                        self._completion.wait(0.002)
        except BaseException as err:  # reported by the harness
            self.errors.append(err)
