"""Drive the engine through its public entry points, stamping every
output token as the client sees it (when ``Engine.step`` returns with it
observed).

The loop is the only client, and it starts in set-up.  First
``warm_chunks`` prefills one prompt as long as each chunk bucket beside a
decoding lane, which runs every step program that a returning client's
prompt can end on; then ``warm`` drives the loop
until every request of a closed loop's opening round has its first token
and a stretch of engine steps has dispatched no program the process had
not run before (no compile, no load from the persistent cache).  A
closed loop's window then opens on clients already in their stride.  An
open loop warms up on a stream of its own, is drained, and the seed's
stream begins at the window's start, so a backlog built while programs
loaded does not carry into the window and the window's requests depend
on the seed alone.  Open loop: each request is due at its arrival time and
goes to ``Engine.submit`` when the loop reaches it; its TTFT counts from
the due time, so a late generator shows as latency and is reported as
``lag``.  Closed loop: each client sends its next request the moment its
last one completes.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from traffic import Generator

QUIET_STEPS = 16        # steps in a row with no new program end the warm-up
WARM_CAP_S = 600.0      # the warm-up gives up here; the window counts compiles
WARM_STREAM = 0x5EED    # warm-up requests draw from seed ^ this
WARM_UID = 1 << 30      # uids of warm_chunks' requests, apart from the mix's
WARM_NEW = 8            # tokens each of them decodes: the lane beside the next
GRACE_S = 60.0          # past the window, wait this long for first tokens


@dataclasses.dataclass
class Tracked:
    req: object
    due: float                  # when the client meant to send it
    stamps: list[float] = dataclasses.field(default_factory=list)
    done_at: float | None = None


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class Load:
    """One run's client: submits, steps, stamps."""

    def __init__(self, engine, request_cls, mix: dict, seed: int, vocab: int,
                 clients: int):
        self.eng = engine
        self.Request = request_cls
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.closed = mix["loop"] == "closed"
        self.gen = (Generator(mix, seed, vocab, first=clients) if self.closed
                    else Generator(mix, seed ^ WARM_STREAM, vocab))
        self.clients = clients
        self.live: list[Tracked] = []
        self.all: list[Tracked] = []
        self.lag: list[float] = []
        self.nxt = None                 # open loop: the next arrival
        self.next_due = 0.0

    def _submit(self, due: float, it=None) -> None:
        it = it or self.gen.next()
        req = self.Request(uid=it.uid, prompt=it.prompt, max_new_tokens=it.max_new)
        now = time.perf_counter()
        with span("submit"):
            self.eng.submit(req)
        t = Tracked(req=req, due=due)
        self.live.append(t)
        self.all.append(t)
        self.lag.append(now - due)

    def _stamp(self) -> list[Tracked]:
        now = time.perf_counter()
        finished = []
        for t in self.live:
            n = len(t.req.out_tokens)
            if n > len(t.stamps):
                t.stamps += [now] * (n - len(t.stamps))
            if t.req.done:
                t.done_at = now
                finished.append(t)
        if finished:
            self.live = [t for t in self.live if t.done_at is None]
        return finished

    def start(self, t: float) -> None:
        """Offer load from ``t``: a closed loop's clients send their
        first requests, an open loop's first arrival is drawn."""
        if self.closed:
            for _ in range(self.clients):
                self._submit(t)
        else:
            self.nxt = self.gen.next()
            self.next_due = t + self.nxt.gap_s

    def _tick(self) -> bool:
        """One turn: submit what is due, then step the engine once (True)
        or wait for the next arrival (False)."""
        if not self.closed:
            now = time.perf_counter()
            while self.next_due <= now:
                self._submit(self.next_due, self.nxt)
                self.nxt = self.gen.next()
                self.next_due += self.nxt.gap_s
        if self.live:
            with span("engine.step"):
                self.eng.step()
            for _ in self._stamp():
                if self.closed:
                    self._submit(time.perf_counter())
            return True
        wait = self.next_due - time.perf_counter()
        if wait > 0:
            with span("generator wait"):
                time.sleep(min(wait, 0.05))
        return False

    def warm_chunks(self, lengths: list[int]) -> int:
        """Prefill one prompt of each length in turn, each beside the lane
        of the one before, which is still decoding (a first prompt of the
        last length opens that lane), and serve them all out; returns the
        engine steps taken.  Given the chunk buckets as the lengths, every
        step program that a client's prompt, returning inside the window,
        runs beside decoding lanes is then in memory: a chunk's program is
        keyed by its bucket alone.  The requests are not tracked: they
        belong to no client and no result."""
        rng = np.random.default_rng(self.seed ^ WARM_STREAM)
        reqs, steps = [], 0
        for i, n in enumerate([lengths[-1], *lengths]):
            req = self.Request(uid=WARM_UID + i,
                               prompt=rng.integers(1, self.vocab, n, dtype=np.int32),
                               max_new_tokens=WARM_NEW)
            self.eng.submit(req)
            reqs.append(req)
            while not req.out_tokens:
                self.eng.step()
                steps += 1
        while not all(r.done for r in reqs):
            self.eng.step()
            steps += 1
        return steps

    def warm(self, new_programs) -> int:
        """Run the load until the opening round has its first tokens and
        ``QUIET_STEPS`` engine steps in a row ran no new program
        (``new_programs()`` counts programs compiled or loaded so far), or
        until ``WARM_CAP_S``; an open loop then stops arriving and is
        served to the end.  Returns the engine steps taken."""
        cap = time.perf_counter() + WARM_CAP_S
        opening = list(self.all)
        steps = calm = 0
        seen = new_programs()
        while time.perf_counter() < cap:
            if not self._tick():
                continue
            steps += 1
            n = new_programs()
            calm = calm + 1 if n == seen else 0
            seen = n
            if calm >= QUIET_STEPS and all(t.stamps for t in opening):
                break
        while not self.closed and self.live and time.perf_counter() < cap:
            with span("engine.step"):
                self.eng.step()
            self._stamp()
            steps += 1
        return steps

    def run(self, t0: float, t_end: float, on_tick=None) -> None:
        """Go on offering load through the window [t0, t_end]; stop once
        every request due by ``t_end`` has its first token, or
        ``GRACE_S`` later.  An open loop's arrivals from ``t0`` on are the
        seed's own stream."""
        if not self.closed:
            self.gen = Generator(self.mix, self.seed, self.vocab)
            self.start(t0)
        while True:
            now = time.perf_counter()
            if on_tick is not None:
                on_tick(now)
            if now >= t_end:
                waiting = [t for t in self.all if t.due <= t_end and not t.stamps]
                if not waiting or now >= t_end + GRACE_S:
                    break
            self._tick()
