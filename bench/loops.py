"""The measured window: an open or a closed loop through the frontend.

Both drive `RetrievalFrontend.submit` -> `pump` -> `take_results`, the
served path, on one host thread.  With `trace=True` the calls are wrapped
in `jax.profiler.TraceAnnotation` spans (bench/submit, bench/pump,
bench/take_results, bench/sleep), so the profiler's trace can say what
the host was doing in each device gap; with `trace=False` no span is
made.

Latency is timed from each query's scheduled arrival (open loop) or its
send (closed loop) to the host seeing its result, so a stall that delays
later submissions counts against them.  The arithmetic is in the pure
functions at the bottom.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

GRACE_S = 60.0  # how long past the window an answer is waited for


@dataclasses.dataclass
class Served:
    """Every query of one window, in send order."""

    due: np.ndarray       # scheduled (open) or send (closed) time, s
    done: np.ndarray      # time the result was seen, s (nan: never)
    shed: np.ndarray      # refused at submit
    ids: np.ndarray       # [n, m] int32 answers (-1 where none)
    scores: np.ndarray    # [n, m] float32
    window_s: float
    sent_at: np.ndarray   # when each query was handed to submit, s
    gc_pauses: list       # (start s, seconds) of each collection in it

    @property
    def lag_s(self) -> float:
        """How late the generator sent, at worst."""
        late = self.sent_at - self.due
        return float(np.nanmax(late)) if late.size else 0.0

    @property
    def sent(self) -> int:
        return int(self.due.size)


class _gc_watch:
    """Records the interpreter's garbage collections while installed: a
    full collection stalls the one serving thread, and its time shows
    up as latency."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.seen: list[tuple[float, float]] = []
        self._start = 0.0
        gc.callbacks.append(self.callback)

    def callback(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif info.get("generation") == 2:
            self.seen.append((self._start - self.t0, now - self._start))


def span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def open_loop(fe, queries: np.ndarray, arrivals: np.ndarray, seconds: float,
              m: int, *, trace: bool = False) -> Served:
    """Send queries[i] at arrivals[i] (s from the start) whatever the
    server's state; wait for every answer, at most GRACE_S past the
    window."""
    from repro.serve import SubmitReject

    n = len(arrivals)
    done = np.full(n, np.nan)
    sent_at = np.full(n, np.nan)
    shed = np.zeros(n, bool)
    ids = np.full((n, m), -1, np.int32)
    scores = np.full((n, m), -np.inf, np.float32)
    owner: dict[int, int] = {}
    i = 0
    t0 = time.perf_counter()
    pauses = _gc_watch(t0)
    while True:
        now = time.perf_counter() - t0
        if i < n and arrivals[i] <= now:
            with span(trace, "bench/submit"):
                while i < n and arrivals[i] <= now:
                    sent_at[i] = now
                    t = fe.submit(queries[i])
                    if isinstance(t, SubmitReject):
                        shed[i] = True
                    else:
                        owner[t] = i
                    i += 1
        with span(trace, "bench/pump"):
            fe.pump()
        got = fe.take_results()
        if got:
            with span(trace, "bench/take_results"):
                now = time.perf_counter() - t0
                for t, (ri, rs) in got.items():
                    j = owner.pop(t)
                    done[j] = now
                    ids[j] = ri
                    scores[j] = rs
        if i >= n and (not owner or now > seconds + GRACE_S):
            break
        if i < n:
            gap = arrivals[i] - (time.perf_counter() - t0)
            if gap > 0.0002 and fe.pending < fe.cfg.max_batch:
                with span(trace, "bench/sleep"):
                    time.sleep(min(gap - 0.0001, 0.002))
    gc.callbacks.remove(pauses.callback)
    return Served(np.asarray(arrivals, float), done, shed, ids, scores,
                  float(seconds), sent_at, pauses.seen)


def closed_loop(fe, queries: np.ndarray, clients: int, seconds: float,
                m: int, *, trace: bool = False) -> Served:
    """`clients` callers, each sending its next fresh query as soon as
    its last one is answered, for `seconds`; then every outstanding
    answer is waited for."""
    from repro.serve import SubmitReject

    pool = len(queries)
    due = np.full(pool, np.nan)
    done = np.full(pool, np.nan)
    shed = np.zeros(pool, bool)
    ids = np.full((pool, m), -1, np.int32)
    scores = np.full((pool, m), -np.inf, np.float32)
    sent_at = np.full(pool, np.nan)
    owner: dict[int, int] = {}
    sent = 0

    def send(now: float, count: int) -> None:
        nonlocal sent
        if sent + count > pool:
            raise RuntimeError(f"fresh-query pool of {pool} ran out; raise "
                               "the cell's max_qps")
        for _ in range(count):
            due[sent] = now
            sent_at[sent] = now
            t = fe.submit(queries[sent])
            if isinstance(t, SubmitReject):
                shed[sent] = True
            else:
                owner[t] = sent
            sent += 1

    t0 = time.perf_counter()
    pauses = _gc_watch(t0)
    with span(trace, "bench/submit"):
        send(0.0, clients)
    while True:
        with span(trace, "bench/pump"):
            fe.pump()
        got = fe.take_results()
        now = time.perf_counter() - t0
        if got:
            with span(trace, "bench/take_results"):
                for t, (ri, rs) in got.items():
                    j = owner.pop(t)
                    done[j] = now
                    ids[j] = ri
                    scores[j] = rs
            if now < seconds:
                with span(trace, "bench/submit"):
                    send(now, len(got))
        if now >= seconds and (not owner or now > seconds + GRACE_S):
            break
    gc.callbacks.remove(pauses.callback)
    return Served(due[:sent], done[:sent], shed[:sent], ids[:sent],
                  scores[:sent], float(seconds), sent_at[:sent], pauses.seen)


# -- the arithmetic of the end-to-end metrics --------------------------------


def latencies_ms(s: Served) -> np.ndarray:
    """Latency of every query due in the window, from its due time.  A
    shed or never-answered query is infinitely late, so shedding can
    only raise a percentile; it also counts in `failed`."""
    inwin = s.due < s.window_s
    lat = (s.done[inwin] - s.due[inwin]) * 1e3
    lat[s.shed[inwin] | np.isnan(lat)] = np.inf
    return lat


def percentile_ms(lat: np.ndarray, p: float) -> float:
    """The p-th percentile over the requests, without interpolation."""
    return float(np.percentile(lat, p, method="inverted_cdf"))


def completed_qps(s: Served) -> float:
    """Queries answered inside the window, over the window."""
    return float(np.sum(s.done <= s.window_s) / s.window_s)


def failed(s: Served) -> int:
    """Queries due in the window that were shed or never answered."""
    inwin = s.due < s.window_s
    return int(np.sum(inwin & (s.shed | np.isnan(s.done))))


def unanswered(s: Served) -> int:
    """Admitted queries whose answer never came."""
    return int(np.sum(~s.shed & np.isnan(s.done)))
