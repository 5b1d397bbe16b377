"""Continuous-batching engine for fold-in queries.

Variable-length query documents are packed into fixed-shape (B, L)
batches so XLA compiles a handful of programs (one per length bucket)
instead of one per request shape:

  * each length bucket owns a pool of B *slots*; a slot holds one
    in-flight document for the ``init + burnin`` sweeps it needs;
  * every engine step runs ONE frozen-Phi Gibbs sweep over a bucket's
    whole slot batch — documents admitted at different times coexist in
    one batch at different sweep counts (iteration-level continuous
    batching, the topic-model analogue of an LLM decode step);
  * a document that reaches ``burnin`` sweeps retires (its topic mixture
    is read out) and frees its slot, which the next queued request takes
    on the following step.

Correctness invariant: a document's mixture depends only on
(snapshot, base_key, its seed, its tokens) — the fold-in randomness
contract of serve/foldin.py — never on the slot index, the batch
composition, or admission timing. ``tests/test_serve.py`` asserts
engine output is bitwise-equal to a direct ``foldin_docs`` call.

The per-step device work is one z-sweep over (B, L) read-only tables;
empty slots carry all-False masks and are skipped by the sweep's
``live`` guard at zero cost beyond lane occupancy.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import conformance as C
from repro.data.stream import AsyncStage
from repro.serve import foldin as F
from repro.serve.snapshot import ModelSnapshot

DEFAULT_BUCKETS = (32, 64, 128, 256)


def _engine_step(snap, tokens, mask, z, seeds, sweeps, base_key, *,
                 impl, has_fresh):
    """One engine step on a (B, L) slot batch: initialize fresh slots
    (sweeps == 0) from the global term, then run one frozen z-sweep with
    each slot's own sweep-indexed uniforms.

    ``has_fresh`` is static (the host knows whether admission placed
    anything): the steady-state no-admissions variant skips the init
    uniforms + alias pass entirely instead of computing and discarding
    them every step.

    Returns ``(z, m)`` — the sweep-emitted (B, K) per-slot histogram is
    kept on the pool so retirement builds mixtures without recounting z
    (bitwise-equal to ``doc_topic_counts(z)``, hence to the direct
    fold-in path).
    """
    length = tokens.shape[1]
    if has_fresh:
        with jax.named_scope("init"):
            u0 = F.sweep_uniforms(base_key, seeds, jnp.zeros_like(seeds),
                                  length)
            z_init = F.init_z(tokens, mask, u0, snap.fpack, snap.ipack)
            z = jnp.where((sweeps == 0)[:, None], z_init, z)
    with jax.named_scope("uniforms"):
        u = F.sweep_uniforms(base_key, seeds, sweeps + 1, length)
    return C.z_step_conformant(
        impl, tokens, mask, z, u, snap.q_a, snap.fpack, snap.ipack,
        kk=snap.K,
    )


def _upload(host: np.ndarray) -> jax.Array:
    """Device copy of a host staging array that the engine mutates in
    place. The upload reads a private snapshot: a transfer that aliased
    (the CPU backend may zero-copy) or read the live array after the
    call returned would see the next admission's or step's writes."""
    return jnp.asarray(host.copy())


@dataclass
class _Slots:
    """One length bucket's slot pool. tokens/mask/seeds are host staging
    arrays, re-uploaded to their device twins ONLY when admission writes
    them (``dirty``); z lives device-resident for the pool's whole life
    (fresh slots are re-initialized in-kernel via the sweeps==0 path, so
    stale rows never need host zeroing) — the steady-state step transfers
    just the (B,) sweep counters."""
    length: int
    tokens: np.ndarray                    # (B, L) int32, host staging
    mask: np.ndarray                      # (B, L) bool, host staging
    seeds: np.ndarray                     # (B,) int32, host staging
    sweeps: np.ndarray                    # (B,) int32
    req: list                             # (B,) Optional[request id]
    z: jax.Array                          # (B, L) int32, device-resident
    m: Optional[jax.Array] = None         # (B, K) sweep-emitted histograms
    d_tokens: Optional[jax.Array] = None  # device twins (None = dirty)
    d_mask: Optional[jax.Array] = None
    d_seeds: Optional[jax.Array] = None
    steps: int = 0

    @classmethod
    def empty(cls, batch: int, length: int) -> "_Slots":
        return cls(
            length=length,
            tokens=np.zeros((batch, length), np.int32),
            mask=np.zeros((batch, length), bool),
            seeds=np.zeros((batch,), np.int32),
            sweeps=np.zeros((batch,), np.int32),
            req=[None] * batch,
            z=jnp.zeros((batch, length), jnp.int32),
        )

    def mark_dirty(self):
        self.d_tokens = self.d_mask = self.d_seeds = None

    def device_batch(self):
        if self.d_tokens is None:
            self.d_tokens = _upload(self.tokens)
            self.d_mask = _upload(self.mask)
            self.d_seeds = _upload(self.seeds)
        return self.d_tokens, self.d_mask, self.d_seeds


@dataclass
class _Pending:
    rid: int
    tokens: Optional[np.ndarray]      # dropped at admission
    submit_t: float
    # host packing output: the (bucket,)-padded row pair a slot admission
    # installs with two memcpys. Filled at submit time (sync path) or by
    # the admission packer daemon (async path) BEFORE the pending entry
    # becomes visible to ``_admit``.
    row_tokens: Optional[np.ndarray] = None
    row_mask: Optional[np.ndarray] = None
    admit_t: Optional[float] = None   # set at slot bind


@dataclass
class EngineStats:
    completed: int = 0
    steps: int = 0
    host_syncs: int = 0        # blocking device reads (retirement)
    live_slot_sweeps: int = 0  # occupied slots swept, summed over steps
    wall_s: float = 0.0
    latencies_s: list = field(default_factory=list)
    latencies_dropped: int = 0  # oldest samples evicted by the window cap
    shapes: set = field(default_factory=set)

    # Keep the raw-sample buffer bounded on a long-lived engine: evict the
    # oldest half past the cap, COUNTING what was evicted so summary()
    # can label its percentiles as computed over a recent window rather
    # than silently presenting them as all-time.
    _LAT_CAP = 65536

    def record_latency(self, dt_s: float):
        self.latencies_s.append(dt_s)
        if len(self.latencies_s) > self._LAT_CAP:
            drop = self._LAT_CAP // 2
            del self.latencies_s[:drop]
            self.latencies_dropped += drop

    def summary(self) -> dict:
        lat = np.asarray(self.latencies_s) * 1e3
        return {
            "completed": self.completed,
            "steps": self.steps,
            "host_syncs": self.host_syncs,
            "live_slot_sweeps": self.live_slot_sweeps,
            "docs_per_s": round(self.completed / max(self.wall_s, 1e-9), 2),
            "p50_latency_ms": round(float(np.percentile(lat, 50)), 2)
            if len(lat) else None,
            "p95_latency_ms": round(float(np.percentile(lat, 95)), 2)
            if len(lat) else None,
            # percentiles above cover the most recent `latency_window`
            # completions; `latencies_dropped` counts evicted samples.
            "latency_window": len(lat),
            "latencies_dropped": self.latencies_dropped,
            "compiled_shapes": sorted(self.shapes),
        }


class ServeEngine:
    """Slot-based continuous batching over a frozen ``ModelSnapshot``.

    ``submit`` enqueues documents; ``run`` drives steps until the queue
    drains and returns {request id: (K,) mixture}. Documents longer than
    the largest bucket are truncated to it (fold-in over a prefix — the
    mixture estimate simply sees fewer tokens).
    """

    def __init__(
        self, snap: ModelSnapshot, *, slots: int = 8, burnin: int = 16,
        impl: str = "sparse", buckets: Sequence[int] = DEFAULT_BUCKETS,
        base_key: Optional[jax.Array] = None, async_admit: bool = False,
        trace_tag: str = "",
    ):
        if slots <= 0:
            raise ValueError("slots must be positive")
        if burnin < 1:
            # the engine's step loop always runs >= 1 sweep before a doc
            # can retire; burnin=0 would silently diverge from
            # foldin_docs(burnin=0) (init only) and break the documented
            # bitwise engine == direct-fold-in invariant.
            raise ValueError("burnin must be >= 1")
        self.snap = snap
        self.slots = slots
        self.burnin = burnin
        self.impl = impl
        self.buckets = tuple(sorted(buckets))
        self.base_key = (jax.random.key(0) if base_key is None else base_key)
        self._pools: dict[int, _Slots] = {}
        self._queue: dict[int, list[_Pending]] = {b: [] for b in self.buckets}
        self._reqs: dict[int, _Pending] = {}       # in-flight only
        self._completed: dict[int, np.ndarray] = {}  # drained by run()
        self._next_rid = 0
        self.stats = EngineStats()
        # distinguishes this engine's async trace ids (and metric labels)
        # when several engines share a process — a fleet tags each with
        # "w{worker}.v{version}" so ensemble fan-out of one rid to many
        # versions cannot collide in the (cat, id) async-event keyspace.
        self.trace_tag = trace_tag
        # per-engine jit instances (not module-level): fleet workers on
        # different devices would otherwise alternate one shared
        # function's most-recent-call fast path and pay the python
        # dispatch slow path on every step. The underlying XLA
        # compilation cache is still shared process-wide.
        self._step_fn = jax.jit(
            _engine_step, static_argnames=("impl", "has_fresh")
        )
        self._theta_fn = jax.jit(F.topic_mixture_from_m)
        # async admission: host packing of queued documents into padded
        # bucket rows runs on a bounded daemon stage (the BlockWriteback
        # double-buffering idiom), overlapping the device sweeps driven
        # by the step loop. Packing is value-identical to the sync path,
        # so admission timing cannot change any mixture (the engine's
        # batching-invariance contract).
        self._packer: Optional[AsyncStage] = (
            AsyncStage(self._pack_and_enqueue, depth=4,
                       name="ServeEngine.admit")
            if async_admit else None
        )

    def _pack_and_enqueue(self, item):
        p, bucket = item
        self._pack(p, bucket)
        self._queue[bucket].append(p)  # GIL-atomic; visible to _admit

    def _pack(self, p: _Pending, bucket: int):
        n = min(p.tokens.size, bucket)
        row_t = np.zeros((bucket,), np.int32)
        row_m = np.zeros((bucket,), bool)
        row_t[:n] = p.tokens[:n]
        row_m[:n] = True
        p.row_tokens, p.row_mask = row_t, row_m
        p.tokens = None

    # -- request lifecycle -------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def submit(self, tokens: np.ndarray, *, seed: Optional[int] = None) -> int:
        """Enqueue one document (1-D int32 word ids). ``seed`` defaults to
        the request id; it fully determines the fold-in randomness and
        must be unique per in-flight request (it IS the request id)."""
        tokens = np.asarray(tokens, np.int32).ravel()
        if tokens.size == 0:
            raise ValueError("empty document")
        rid = self._next_rid if seed is None else seed
        if rid in self._reqs:
            raise ValueError(f"seed/request id {rid} already in flight")
        self._next_rid = max(self._next_rid, rid) + 1
        p = _Pending(rid=rid, tokens=tokens, submit_t=time.perf_counter())
        self._reqs[rid] = p
        bucket = self._bucket(tokens.size)
        tr = obs.tracer()
        if tr.enabled:
            tr.async_begin("request.queued", self._aid(rid), cat="serve",
                           bucket=bucket, tag=self.trace_tag)
        with tr.span("engine.submit", cat="serve", bucket=bucket):
            if self._packer is not None:
                self._packer.submit((p, bucket))  # packs off-thread
            else:
                self._pack(p, bucket)
                self._queue[bucket].append(p)
        return rid

    def _aid(self, rid: int) -> str:
        """Async trace-event id for one request (unique per engine)."""
        return f"{self.trace_tag}:{rid}" if self.trace_tag else str(rid)

    # -- slot admission / retirement --------------------------------------
    def _admit(self, pool: _Slots, bucket: int):
        q = self._queue[bucket]
        admitted = False
        tr = obs.tracer()
        hist = None
        for s in range(self.slots):
            if pool.req[s] is not None or not q:
                continue
            p = q.pop(0)
            # rows were packed at submit time (or by the admission packer
            # daemon, overlapping a device sweep): installation is two
            # row memcpys, never a zero-and-slice repack.
            pool.tokens[s] = p.row_tokens
            pool.mask[s] = p.row_mask
            pool.seeds[s] = p.rid
            pool.sweeps[s] = 0
            pool.req[s] = p.rid
            p.row_tokens = p.row_mask = None
            p.admit_t = time.perf_counter()
            if hist is None:
                hist = obs.metrics().histogram("serve.queue_wait_ms",
                                               bucket=bucket)
            hist.observe((p.admit_t - p.submit_t) * 1e3)
            if tr.enabled:
                aid = self._aid(p.rid)
                tr.async_end("request.queued", aid, cat="serve")
                tr.async_begin("request.inflight", aid, cat="serve",
                               bucket=bucket, slot=s, tag=self.trace_tag)
            admitted = True
        if admitted:
            pool.mark_dirty()

    def _retire(self, pool: _Slots):
        done = [s for s in range(self.slots)
                if pool.req[s] is not None and pool.sweeps[s] >= self.burnin]
        if not done:
            return
        tr = obs.tracer()
        with tr.span("engine.retire", cat="serve", bucket=pool.length,
                     n=len(done)):
            # mixtures from the last sweep's emitted histograms (pool.m
            # is set by every step; retirement requires >= 1 sweep). The
            # read blocks until the step that retires them has run.
            with tr.span("engine.retire_wait", cat="serve"):
                theta = np.asarray(self._theta_fn(
                    pool.m, self.snap.psi, self.snap.alpha,
                ))
            self.stats.host_syncs += 1
            now = time.perf_counter()
            hist = obs.metrics().histogram("serve.service_ms",
                                           bucket=pool.length)
            for s in done:
                # evict the request entirely: a long-lived engine must
                # not accumulate per-request state (tokens, theta)
                # forever.
                p = self._reqs.pop(pool.req[s])
                self._completed[p.rid] = theta[s]
                self.stats.completed += 1
                self.stats.record_latency(now - p.submit_t)
                if p.admit_t is not None:
                    hist.observe((now - p.admit_t) * 1e3)
                if tr.enabled:
                    tr.async_end("request.inflight", self._aid(p.rid),
                                 cat="serve")
                pool.req[s] = None
                pool.mask[s] = False
        # host masks changed (freed rows go inert); the device twin is
        # refreshed lazily at the next upload — stale True rows only cost
        # wasted sweep lanes, never correctness (they are re-initialized
        # in-kernel when a new request takes the slot).

    # -- the step loop -----------------------------------------------------
    def step(self) -> bool:
        """Admit, sweep every bucket with in-flight work, retire.
        Returns False when nothing is in flight and the queue is empty."""
        busy = False
        tr = obs.tracer()
        for bucket in self.buckets:
            if self._queue[bucket] and bucket not in self._pools:
                self._pools[bucket] = _Slots.empty(self.slots, bucket)
            pool = self._pools.get(bucket)
            if pool is None:
                continue
            if self._queue[bucket]:
                with tr.span("engine.admit", cat="serve", bucket=bucket):
                    self._admit(pool, bucket)
            active = any(r is not None for r in pool.req)
            if not active:
                continue
            busy = True
            has_fresh = any(r is not None and pool.sweeps[s] == 0
                            for s, r in enumerate(pool.req))
            with tr.span("engine_step", cat="serve", bucket=bucket,
                         tag=self.trace_tag):
                with tr.span("engine.upload", cat="serve"):
                    d_tokens, d_mask, d_seeds = pool.device_batch()
                    d_sweeps = _upload(pool.sweeps)
                pool.z, pool.m = self._step_fn(
                    self.snap, d_tokens, d_mask, pool.z, d_seeds,
                    d_sweeps, self.base_key, impl=self.impl,
                    has_fresh=has_fresh,
                )
            live = np.array([r is not None for r in pool.req])
            pool.sweeps[live] += 1
            pool.steps += 1
            self.stats.steps += 1
            self.stats.live_slot_sweeps += int(live.sum())
            self.stats.shapes.add((self.slots, bucket))
            self._retire(pool)
        return busy or any(self._queue.values())

    def drain_completed(self) -> dict[int, np.ndarray]:
        """Hand back (and forget) mixtures completed since the last
        drain — the incremental counterpart of ``run`` used by fleet
        workers, which interleave ``step``s of several engines."""
        out, self._completed = self._completed, {}
        return out

    def in_flight(self) -> int:
        """Requests submitted but not yet completed (queued, being
        packed, or occupying a slot)."""
        return len(self._reqs)

    def close(self):
        """Stop the admission packer daemon, if any (idempotent). A
        fleet calls this when discarding a drained engine after a
        snapshot hot-swap."""
        if self._packer is not None:
            self._packer.close()

    def run(self) -> dict[int, np.ndarray]:
        """Drive steps until the queue drains; returns {rid: mixture} for
        requests completed since the previous ``run`` call (completed
        results are drained, not retained — the engine holds no
        per-request state after handing a mixture back)."""
        if self._packer is not None:
            self._packer.flush()  # everything submitted is admissible
        t0 = time.perf_counter()
        while self.step():
            pass
        self.stats.wall_s += time.perf_counter() - t0
        return self.drain_completed()
