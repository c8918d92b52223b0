"""The one generator behind every traffic mix.

A mix is a JSON file under ``bench/traffic/``:

- ``arrivals``: ``{"kind": "closed", "clients": n}`` (n clients, each
  sending its next image as soon as its last one is answered) or
  ``{"kind": "poisson", "rate_per_s": r}`` (open loop, optionally in
  on/off bursts: ``"burst": {"period_s", "on_s", "mult"}`` multiplies
  the rate by ``mult`` for the first ``on_s`` seconds of every period);
- ``buckets``, ``queue_limit``: the server's batch buckets and queue bound;
- ``deadline_ms``: each request's deadline, or null for none;
- ``pool``: how many distinct images the seed draws (request i sends
  image i mod pool).

Every time is on the benchmark's own clock.  A request's latency runs
from its arrival (closed loop: the moment it is submitted; open loop:
its scheduled arrival, so time spent behind a late generator counts) to
the moment its answer is on the host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any integer, negative or beyond 64 bits
    included) and an optional stream number."""
    return np.random.default_rng([seed % 2 ** 64, *stream])


def image_pool(shape, n: int, seed: int) -> np.ndarray:
    """``n`` N(0, 1) float32 images of ``shape``, from ``seed``."""
    return seed_rng(seed).standard_normal((n, *shape), dtype=np.float32)


def arrival_times(arrivals: dict, seconds: float, seed: int) -> np.ndarray:
    """Open-loop arrival offsets in ``[0, seconds)``: a Poisson process at
    the mix's peak rate, thinned to the rate of each instant."""
    rate = arrivals["rate_per_s"]
    burst = arrivals.get("burst")
    peak = rate * (burst["mult"] if burst else 1)
    rng = seed_rng(seed, 1)
    t = np.cumsum(rng.exponential(1 / peak, int(peak * seconds * 2) + 64))
    t = t[t < seconds]
    if burst:
        on = (t % burst["period_s"]) < burst["on_s"]
        t = t[on | (rng.random(t.size) < 1 / burst["mult"])]
    return t


@dataclasses.dataclass
class Record:
    rid: int
    image: int              # index into the pool
    arrived: float          # benchmark clock, s
    done: float | None = None
    code: str | None = None
    logits: np.ndarray | None = None

    @property
    def latency_s(self) -> float:
        return self.done - self.arrived


def drive(server, new_request, mix: dict, pool: np.ndarray, seconds: float,
          seed: int, *, clock=time.perf_counter, span=None):
    """Offer the mix to ``server`` for ``seconds`` and wait for every
    request that arrived in that time.

    ``server`` has ``submit(request)``, ``tick()`` and ``queue``;
    ``new_request(rid, image, deadline_s)`` makes a request that carries
    ``terminal``, ``code`` and ``logits``.  ``span(name)`` is a context
    manager around each submit and tick (a host span in a trace).
    Returns ``(records, t0, t_end)``: every request, the window's start,
    and the moment the last answer arrived.
    """
    span = span or (lambda name: contextlib.nullcontext())
    deadline = mix.get("deadline_ms")
    deadline = deadline / 1e3 if deadline is not None else None
    records: list[Record] = []
    live: list = []

    def offer(arrived: float) -> None:
        rid = len(records)
        rec = Record(rid, rid % len(pool), arrived)
        req = new_request(rid, pool[rec.image], deadline)
        records.append(rec)
        with span("bench.submit"):
            server.submit(req)
        live.append((rec, req))

    def collect(now: float, closed: bool) -> None:
        for entry in list(live):
            rec, req = entry
            if req.terminal:
                live.remove(entry)
                rec.done, rec.code, rec.logits = now, req.code, req.logits
                if closed and now < t0 + seconds:
                    offer(clock())

    t0 = clock()
    if mix["arrivals"]["kind"] == "closed":
        for _ in range(mix["arrivals"]["clients"]):
            offer(clock())
        while live:
            collect(clock(), True)      # requests shed at submit
            if live:
                with span("bench.tick"):
                    server.tick()
                collect(clock(), True)
    else:
        due = list(t0 + arrival_times(mix["arrivals"], seconds, seed))
        while due or live:
            now = clock()
            while due and due[0] <= now:
                offer(due.pop(0))
            if server.queue:
                with span("bench.tick"):
                    server.tick()
            elif due:
                time.sleep(max(0.0, min(due[0] - clock(), 1e-3)))
            collect(clock(), False)
    t_end = max((r.done for r in records), default=t0)
    return records, t0, t_end
