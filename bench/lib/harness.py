"""Drive one cell through the program: set-up, the measured window, the record.

The program under test is the serving stack as its users run it: one
``ServeEngine`` and one ``ContinuousScheduler`` (continuous policy, chunked
prefill, the stripe KV pool, greedy sampling), every projection on the
"pallas-systolic" backend.  The harness owns the clock and the traffic: it
submits each request as it comes due (eligible at once), calls
``scheduler.step()`` in its own loop, and after every tick reads which
requests were admitted, which advanced their prefill, and which tokens
reached the host, all on its own clock.

Spans (``jax.profiler.TraceAnnotation``, all named ``bench.*``) are opened
from here around the calls into each layer, so that a trace can put device
time and idle gaps to the host's work: the harness's own loop (``tick``,
``generator``, ``wait``, ``harvest``) and, wrapped on the instances at set-up,
the engine's ``decode_slots``, ``prefill_chunk`` and sampling, the
scheduler's admission and the pool's slot operations.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.lib import spec, traffic, weights

# Host spans opened around the program's own calls (instance attributes).
WRAPPED = (
    ("engine", "decode_slots", "bench.decode"),
    ("engine", "prefill_chunk", "bench.prefill_chunk"),
    ("engine", "_sample", "bench.sample"),
    ("sched", "_admit", "bench.admit"),
    ("pool", "gather_slot", "bench.kv_pool"),
    ("pool", "write_slot", "bench.kv_pool"),
    ("pool", "free", "bench.kv_pool"),
)


def _annotate(obj, attr: str, label: str) -> None:
    fn = getattr(obj, attr, None)
    if fn is None:  # the program renamed it: the span is simply missing
        return

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with TraceAnnotation(label):
            return fn(*args, **kwargs)

    setattr(obj, attr, wrapped)


class CompileCounter:
    """Counts traces and backend compiles while armed (JAX monitoring)."""

    _instance = None

    def __init__(self):
        self.armed = False
        self.count = 0
        from jax._src import dispatch

        names = {dispatch.JAXPR_TRACE_EVENT, dispatch.BACKEND_COMPILE_EVENT}

        def listen(event, duration, **_):
            if self.armed and event in names:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


@dataclasses.dataclass
class Rec:
    """One request as the client saw it; times are host seconds."""

    item: traffic.Item
    req: object
    due: float
    submitted: float
    admitted: float | None = None
    finished: float | None = None
    times: list = dataclasses.field(default_factory=list)
    chunks_seen: int = 0


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    decoded: list  # per token decoded this tick: the keys its query saw
    chunks: list  # per prefill chunk this tick: (offset, length, last)


@dataclasses.dataclass
class Window:
    """What the window saw.  ``recs`` also holds the requests of the ramp
    that ran on into the window; ``ticks`` only the window's own."""

    t0: float
    t1: float
    recs: list
    ticks: list
    compiles: int
    refused: int  # requests the scheduler would not take, in the window
    traced_end: float | None = None  # host time the traced part ended

    def traced_ticks(self) -> list:
        """The ticks wholly inside the traced part of the window."""
        return [t for t in self.ticks if t.end <= self.traced_end]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def tokens(self) -> list:
        """Host times of the tokens delivered in the window."""
        return [t for r in self.recs for t in r.times if t >= self.t0]

    def ttft(self) -> list:
        """Seconds from due to first token, of first tokens in the window."""
        return [r.times[0] - r.due for r in self.recs if r.times and r.times[0] >= self.t0]

    def itl(self) -> list:
        """Gaps between consecutive tokens of one request, in the window."""
        return [b - a for r in self.recs for a, b in zip(r.times, r.times[1:]) if a >= self.t0]

    def finished(self) -> list:
        return [r for r in self.recs if r.finished is not None and r.finished >= self.t0]


class Cell:
    """Set-up: weights from the seed, the engine and scheduler, warmed.

    ``tamper(cell)``, when given, runs after the program is built and before
    it is warmed: the tests use it to plant a fault, and the precision
    control to swap in the program's int8 path.
    """

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float, *, tamper=None):
        from repro.models.registry import get_model
        from repro.serving import ContinuousScheduler, ServeConfig, ServeEngine

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.fam = spec.family(cfg["family"])
        self.arch = self.fam.arch_config(cfg)
        self.model = get_model(self.arch)
        self.abstract = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.weights = self.make_weights()
        self.gen = traffic.Generator(mix, cfg["vocab_size"], seed, seconds)
        self.max_len = self.gen.max_total_len()
        self.slots = mix["slots"]
        self.engine = ServeEngine(
            self.model, self.weights, ServeConfig(max_len=self.max_len, batch=self.slots)
        )
        self.sched = ContinuousScheduler(
            self.engine,
            chunked_prefill=True,
            chunk_size=mix["chunk_size"],
            chunk_budget=mix["chunk_budget"],
        )
        self.pool = self.sched.pool
        for owner, attr, label in WRAPPED:
            _annotate(getattr(self, owner), attr, label)
        if tamper is not None:
            tamper(self)
        self.warm()

    def make_weights(self):
        """The seed's weights, on the device (the same arrays each call)."""
        return jax.block_until_ready(
            weights.make(self.fam.weight_spec(self.cfg), self.abstract, self.seed)
        )

    def chunk_lengths(self) -> list:
        """Every chunk length the cell's prompts split into."""
        from repro.serving.engine import chunk_schedule

        size = self.sched.chunk_size
        return sorted(
            {n for it in self.gen.items for _, n in chunk_schedule(it.prompt_len, size)}
        )

    def warm(self) -> None:
        """Compile (or load) every program the window runs, and no other:
        the decode step at the slot count, each chunk length, the pool's slot
        operations and sampling; then one short request end to end, for what
        only a live request reaches (admission, the last chunk's sampling,
        freeing a slot)."""
        from repro.serving import Request

        self.sched.warmup()
        for n in self.chunk_lengths():
            view = self.pool.gather_slot(0)
            _, view = self.engine.prefill_chunk(jnp.zeros((1, n), jnp.int32), view, 0, last=False)
            jax.block_until_ready(view)
        shortest = min(self.gen.items, key=lambda it: it.prompt_len)
        self.sched.submit(
            Request(rid=-1, prompt={"tokens": shortest.tokens[None]}, max_new_tokens=2)
        )
        while self.sched.pending():
            self.sched.step()

    def drive(
        self, seconds: float, ramp: float = 0.0, on_open=None, traced: float = 0.0, on_traced=None,
        clock=time.perf_counter,
    ) -> Window:
        """Offer the mix for ``ramp`` seconds, then measure for ``seconds``.

        The ramp brings the traffic to its steady state (a closed loop's
        clients all start at once; an open loop's queue starts empty) and
        counts as set-up; ``on_open()`` runs as the window opens, and
        ``on_traced()`` once ``traced`` seconds of it have passed (a traced
        run records the profiler between the two, inside a ``bench.window``
        span).  Requests carry on across the edges; only what happens in the
        window is measured."""
        from repro.serving import Request
        from repro.serving.scheduler import FINISHED, QUEUED

        counter = CompileCounter.get()
        sched, gen = self.sched, self.gen
        recs, active, ticks, refused = [], [], [], 0
        start = clock()
        t0 = end = span = traced_end = None
        while True:
            now = clock()
            if t0 is None and now - start >= ramp:
                if on_open is not None:
                    on_open()
                span = TraceAnnotation("bench.window")  # made once tracing is on
                span.__enter__()
                counter.count, counter.armed = 0, True
                t0 = clock()
                end = t0 + seconds
            if span is not None and traced_end is None and (now >= end or (traced and now - t0 >= traced)):
                span.__exit__(None, None, None)
                traced_end = clock()
                if on_traced is not None:
                    on_traced()
            if end is not None and now >= end:
                break
            with TraceAnnotation("bench.generator"):
                for it in gen.due(now - start):
                    req = Request(
                        rid=it.idx, prompt={"tokens": it.tokens[None]}, max_new_tokens=it.out_len
                    )
                    try:
                        sched.submit(req)
                    except ValueError:
                        refused += t0 is not None
                        continue
                    rec = Rec(it, req, due=start + it.due, submitted=clock())
                    recs.append(rec)
                    active.append(rec)
            if not sched.pending():
                nxt = gen.next_due()
                if nxt is None:
                    if t0 is None:
                        continue
                    break
                with TraceAnnotation("bench.wait"):
                    stop = start + ramp if t0 is None else end
                    time.sleep(max(0.0, min(start + nxt, stop) - clock()))
                continue
            tb = clock()
            with TraceAnnotation("bench.tick"):
                sched.step()
            ta = clock()
            with TraceAnnotation("bench.harvest"):
                active = self._harvest(active, start, tb, ta, ticks if t0 is not None else [], QUEUED, FINISHED)
        t1 = clock()
        counter.armed = False
        return Window(t0, t1, recs, ticks, counter.count, refused, traced_end)

    def _harvest(self, active, start, tb, ta, ticks, QUEUED, FINISHED) -> list:
        decoded, chunks, still, done = [], [], [], 0
        for rec in active:
            req = rec.req
            if rec.admitted is None and req.state != QUEUED:
                rec.admitted = tb  # admission runs first in a tick
            while rec.chunks_seen < req.chunk_idx:
                off, n = req.chunks[rec.chunks_seen]
                rec.chunks_seen += 1
                chunks.append((off, n, rec.chunks_seen == len(req.chunks)))
            new = len(req.out) - len(rec.times)
            if new > 0:
                # A tick's decode gives a request at most one token; the tick
                # its last chunk lands also gives it its first.
                if new > (1 if not rec.times else 0):
                    decoded.append(rec.item.prompt_len + len(req.out) - 1)
                rec.times.extend([ta] * new)
            if req.state == FINISHED:
                rec.finished = ta
                done += 1
            else:
                still.append(rec)
        self.gen.finished(ta - start, done)
        ticks.append(Tick(tb, ta, decoded, chunks))
        return still

    def release(self) -> None:
        """Free the program's state on the device (the KV pool); the weights
        stay for the reference."""
        self.pool.cache = None
        self.engine.cache = None
