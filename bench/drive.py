"""The one traffic generator: reads a traffic mix (``bench/traffic/*.json``)
and drives a ``StreamServer`` from the calling thread.

``closed``  ``sessions`` long-lived sessions stream ``chunk``-token chunks
            back to back; a session's next chunk is offered as soon as its
            admission queue has room.  Inputs cycle through a seeded pool of
            ``pool_frames`` frames of ``frame_tokens`` samples, each session
            from its own seeded offset.
``open``    sessions arrive at ``rate_per_s``; each submits one
            ``session_tokens`` clip whole at its due time and closes.  Every
            seed gets the same set of inter-arrival gaps (the quantiles of
            the exponential distribution) in a seeded order, so seeds change
            the order and the data, not the amount of work.  Clips come from
            a seeded pool of ``pool_sessions`` clips.

Both start ``lead_in_s`` before the window so that it opens on a steady
state.  Latencies count from each session's due time.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from bench import stats


@dataclass
class Checked:
    """One session whose outputs are compared with the reference."""

    session: object
    pool: np.ndarray         # float64 rows the session's inputs come from
    rows: np.ndarray         # which rows it submitted, in order
    complete: bool           # closed and all of it due: output must be whole

    def inputs(self) -> np.ndarray:
        return self.pool[self.rows].reshape(-1)


@dataclass
class Window:
    seconds: float
    values: Dict[str, float]             # end-to-end quantities measured
    checked: List[Checked]
    notes: Dict[str, float] = field(default_factory=dict)
    gc_passes: List[tuple] = field(default_factory=list)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _samples(rng, config, shape) -> np.ndarray:
    lo, hi = config["value_range"]
    return rng.integers(lo, hi + 1, size=shape).astype(np.float64)


def _delivered(sessions, port: str) -> int:
    return sum(len(s.results[port]) for s in sessions)


class Closed:
    def __init__(self, mix: Dict, config: Dict, seed: int):
        rng = np.random.default_rng(seed)
        self.mix, self.config = mix, config
        self.chunk = mix["chunk"]
        pool = mix["frame_tokens"] * mix["pool_frames"]
        if pool % self.chunk:
            raise ValueError(f"pool of {pool} tokens is not whole chunks "
                             f"of {self.chunk}")
        self.pool = _samples(rng, config, (pool // self.chunk, self.chunk))
        self.chunks = self.pool.tolist()
        self.starts = rng.integers(0, len(self.chunks), mix["sessions"])
        self.sent = [0] * mix["sessions"]
        self.sessions: List = []

    def _feed(self, port: str) -> bool:
        """Offer every session chunks while its queue has room."""
        fed = False
        n = len(self.chunks)
        for i, s in enumerate(self.sessions):
            q = s.queues[port]
            q.snapshot_writer()
            while q.space() >= self.chunk:
                s.submit(self.chunks[(self.starts[i] + self.sent[i]) % n])
                self.sent[i] += 1
                fed = True
                q.snapshot_writer()
        return fed

    def _run_until(self, server, port: str, deadline: float) -> None:
        while time.perf_counter() < deadline:
            with _annotate("bench.submit"):
                fed = self._feed(port)
            if not fed:
                with _annotate("bench.wait"):
                    server.wait_for_space(deadline)

    def lead_in(self, server) -> None:
        port = self.config["ingress"]
        self.sessions = [server.open_session()
                         for _ in range(self.mix["sessions"])]
        self._run_until(server, port,
                        time.perf_counter() + self.mix["lead_in_s"])

    def window(self, server, seconds: float) -> Window:
        port, collect = self.config["ingress"], self.config["collect"]
        t0 = time.perf_counter()
        d0 = _delivered(self.sessions, collect)
        with _annotate("bench.window"):
            self._run_until(server, port, t0 + seconds)
            d1 = _delivered(self.sessions, collect)
            t1 = time.perf_counter()
        return Window(seconds=t1 - t0,
                      values={"tokens_per_s": (d1 - d0) / (t1 - t0)},
                      checked=[])

    def finish(self, server, win: Window) -> None:
        """Stop offering; the caller stops the server, which delivers what
        is in flight.  Every session is then checked on its prefix."""
        n = len(self.chunks)
        for i, s in enumerate(self.sessions):
            idx = (self.starts[i] + np.arange(self.sent[i])) % n
            win.checked.append(Checked(s, self.pool, idx, False))


class Open:
    def __init__(self, mix: Dict, config: Dict, seed: int,
                 seconds: float):
        rng = np.random.default_rng(seed)
        self.mix, self.config = mix, config
        self.rate = float(mix["rate_per_s"])
        self.pool = _samples(rng, config,
                             (mix["pool_sessions"], mix["session_tokens"]))
        self.clips = self.pool.tolist()
        self.gaps = self._gaps(rng, seconds)
        self.lead_gaps = self._gaps(rng, mix["lead_in_s"])
        self.pick = rng.integers(0, len(self.clips), size=1 << 16)
        self.arrived = 0
        self._next_due = time.perf_counter_ns()
        self.records: List[Dict] = []   # every arrival: session, due, late

    def _gaps(self, rng, seconds: float) -> np.ndarray:
        """Inter-arrival gaps of ``round(rate * seconds)`` arrivals: the
        exponential distribution's quantiles in a seeded order, scaled to
        fill ``seconds`` exactly."""
        n = round(self.rate * seconds)
        if n < 1:
            return np.zeros(0)
        q = -np.log1p(-(np.arange(n) + 0.5) / n) / self.rate
        return rng.permutation(q * (seconds / q.sum()))

    def _arrive(self, server, due_ns: int) -> Dict:
        port = self.config["ingress"]
        now = time.perf_counter_ns()
        if due_ns > now:
            with _annotate("bench.sleep"):
                time.sleep((due_ns - now) / 1e9)
        with _annotate("bench.submit"):
            start = time.perf_counter_ns()
            c = int(self.pick[self.arrived % len(self.pick)])
            s = server.open_session()
            s.submit(self.clips[c], port, block=False)
            s.close()
        self.arrived += 1
        rec = {"session": s, "clip": c, "due": due_ns,
               "late": start - due_ns}
        self.records.append(rec)
        return rec

    def lead_in(self, server) -> None:
        t = time.perf_counter_ns()
        for g in self.lead_gaps:
            self._arrive(server, t)
            t += int(g * 1e9)
        self._next_due = t

    def window(self, server, seconds: float) -> Window:
        collect = self.config["collect"]
        # the window opens where the lead-in's last gap ends, with the
        # first window arrival due then (now, if the generator is late)
        t0_ns = max(time.perf_counter_ns(), self._next_due)
        time.sleep(max(t0_ns - time.perf_counter_ns(), 0) / 1e9)
        d0 = _delivered(server.sessions(), collect)
        due = t0_ns
        self.window_recs: List[Dict] = []
        with _annotate("bench.window"):
            for g in self.gaps:
                self.window_recs.append(self._arrive(server, due))
                due += int(g * 1e9)
            end_ns = t0_ns + int(seconds * 1e9)
            now = time.perf_counter_ns()
            if end_ns > now:
                time.sleep((end_ns - now) / 1e9)
            d1 = _delivered(server.sessions(), collect)
            t1_ns = time.perf_counter_ns()
        self._next_due = due
        win_s = (t1_ns - t0_ns) / 1e9
        return Window(seconds=win_s,
                      values={"tokens_per_s": (d1 - d0) / win_s},
                      checked=[])

    def finish(self, server, win: Window) -> None:
        """Keep arrivals going until every window session has finished (at
        most ``drain_s``), then take the latencies."""
        deadline = time.perf_counter() + self.mix["drain_s"]
        due, i = self._next_due, 0
        while (time.perf_counter() < deadline
               and not all(r["session"].finished.is_set()
                           for r in self.window_recs)):
            self._arrive(server, due)
            due += int(self.gaps[i % len(self.gaps)] * 1e9)
            i += 1
        ttfo, whole = [], []
        for r in self.window_recs:
            s = r["session"]
            ok = s.finished.is_set() and s.error is None
            first, last = s.first_delivery_ns, s.last_delivery_ns
            ttfo.append((first - r["due"]) / 1e6
                        if ok and first is not None else math.inf)
            whole.append((last - r["due"]) / 1e6
                         if ok and last is not None else math.inf)
            win.checked.append(Checked(s, self.pool, np.array([r["clip"]]),
                                       ok))
        win.values["ttfo_p95_ms"] = stats.percentile(ttfo, 95)
        win.values["stream_p95_ms"] = stats.percentile(whole, 95)
        late = [r["late"] / 1e6 for r in self.window_recs]
        win.notes.update({"sessions_due": len(self.window_recs)})
        for name, vals in (("ttfo", ttfo), ("stream", whole)):
            win.notes.update({f"{name}_p{p}_ms": stats.percentile(vals, p)
                              for p in (50, 90, 99)})
            win.notes[f"{name}_mean_ms"] = sum(vals) / len(vals)
        win.notes.update({
            "generator_late_p50_ms": stats.percentile(late, 50),
            "generator_late_p95_ms": stats.percentile(late, 95),
            "generator_late_max_ms": max(late),
            "arrivals_after_window": self.arrived - len(self.window_recs)
            - len(self.lead_gaps),
        })


def make(mix: Dict, config: Dict, seed: int, seconds: float):
    if mix["loop"] == "closed":
        return Closed(mix, config, seed)
    if mix["loop"] == "open":
        return Open(mix, config, seed, seconds)
    raise ValueError(f"unknown traffic loop {mix['loop']!r}")
