"""Replicated serving fleet: N continuous-batching engines behind one
async admission router, fed by a snapshot registry.

The partially collapsed representation makes serving embarrassingly
parallel: with (Phi, Psi) frozen, a query document's fold-in touches
only read-only tables plus its own slots, so engines replicate with no
coordination beyond work dispatch. A ``ServeFleet`` runs one worker
thread per engine (default: one per ``jax.devices()`` entry; on CPU the
threads interleave host packing with XLA sweeps, which release the GIL),
each worker owning device-local copies of the snapshots it serves.

Correctness invariant (asserted in tests/test_fleet.py): a request's
mixture is bitwise-equal to the single-engine ``ServeEngine`` result for
the same (snapshot, base_key, seed, tokens) — regardless of worker
count, dispatch order, admission timing, or a concurrent registry
publish. It follows from the fold-in randomness contract
(serve/foldin.py): nothing in a document's chain depends on where or
with whom it was computed.

Hot-swap: workers watching a ``SnapshotRegistry`` re-check ``latest``
between engine steps. On a publish, NEW admissions bind to the new
version while in-flight slots finish on the engine — hence the snapshot
— they started on; a drained old engine is then discarded. No slot is
ever dropped and no in-flight mixture ever changes.

Ensemble inference: ``ensemble=E`` fans each request out to the E newest
registry versions (the standard MCMC answer to single-sample noise:
average mixtures over posterior samples). The router aggregates the E
per-version mixtures by mean in ascending version order, so the result
is deterministic given (registry version set, seed).
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence, Union

import jax
import numpy as np

from repro.serve.engine import DEFAULT_BUCKETS, ServeEngine
from repro.serve.registry import SnapshotRegistry
from repro.serve.router import AdmissionRouter, Task
from repro.serve.snapshot import ModelSnapshot

_PINNED = -1  # engine key for a fleet constructed from a bare snapshot
# ``EngineStats`` counters that worker and fleet summaries add up
_COUNTS = ("steps", "host_syncs", "live_slot_sweeps")


def _localize(snap: ModelSnapshot, device) -> ModelSnapshot:
    """A device-resident copy of every snapshot array (replication —
    each worker serves from its own device's HBM)."""
    return ModelSnapshot(*(jax.device_put(a, device) for a in snap))


class _Worker(threading.Thread):
    """One fleet worker: a device, a dict of per-version engines, and a
    pull -> admit -> step -> post loop."""

    def __init__(self, fleet: "ServeFleet", wid: int, device):
        super().__init__(daemon=True, name=f"ServeFleet.worker{wid}")
        self.fleet = fleet
        self.wid = wid
        self.device = device
        self.engines: dict[int, ServeEngine] = {}
        self.tasks: dict[tuple[int, int], Task] = {}  # (version, rid)
        self.completed = 0
        self.retired = dict.fromkeys(_COUNTS, 0)  # discarded engines
        self.swaps = 0
        self.error: Optional[BaseException] = None
        self._warm_bucket: Optional[int] = None

    # -- engines -----------------------------------------------------------
    def _engine(self, version: int) -> ServeEngine:
        eng = self.engines.get(version)
        if eng is None:
            f = self.fleet
            snap = _localize(f._snapshot(version), self.device)
            eng = ServeEngine(
                snap, slots=f.slots, burnin=f.burnin, impl=f.impl,
                buckets=f.buckets,
                base_key=jax.device_put(f.base_key, self.device),
                async_admit=True,
                trace_tag=f"w{self.wid}.v{version}",
            )
            self.engines[version] = eng
        return eng

    def _discard_drained(self, current: int):
        for v, eng in list(self.engines.items()):
            if v != current and eng.in_flight() == 0:
                if eng.stats.steps:
                    self.swaps += 1
                for k in _COUNTS:
                    self.retired[k] += getattr(eng.stats, k)
                eng.close()
                del self.engines[v]

    # -- the loop ----------------------------------------------------------
    def _tick(self) -> bool:
        f = self.fleet
        f._maybe_poll()
        self._engine(f._target_version)  # ensure the admission target
        # worker capacity is `slots` TOTAL across its engines: counting
        # only the current-version engine would let version-pinned
        # (ensemble) subtasks pile into other engines' unbounded queues,
        # silently defeating the router's max_pending backpressure.
        inflight = sum(e.in_flight() for e in self.engines.values())
        free = max(f.slots - inflight, 0)
        # a worker with in-flight slots must not park on an empty queue
        # (timeout=0): its sweeps are the fleet's throughput. Only a
        # fully idle worker blocks waiting for work.
        idle = inflight == 0
        pulled = (f.router.pull(free, prefer=self._warm_bucket,
                                timeout=0.05 if idle else 0.0)
                  if free else [])
        # bind version-less tasks AFTER the (blocking) pull: a hot-swap
        # that lands while this worker waits for work must redirect every
        # task it then pulls — the swap boundary is engine admission, not
        # the moment the worker went idle.
        current = f._target_version
        for t in pulled:
            version = current if t.version is None else t.version
            self._engine(version).submit(t.tokens, seed=t.rid)
            self.tasks[(version, t.rid)] = t
            self._warm_bucket = t.bucket
        busy = False
        for v, e in list(self.engines.items()):
            if not e.in_flight():
                continue
            busy |= e.step()
            done = e.drain_completed()
            for rid, theta in done.items():
                f.router.post(self.tasks.pop((v, rid)), theta)
            self.completed += len(done)
        self._discard_drained(current)
        return bool(pulled) or busy

    def run(self):
        try:
            with jax.default_device(self.device):
                while not self.fleet._stop.is_set():
                    self._tick()  # pull() blocks briefly when idle
        except BaseException as e:  # surfaced by ServeFleet.run/close
            self.error = e
        finally:
            for eng in self.engines.values():
                try:
                    eng.close()
                except Exception:
                    pass

    # -- stats -------------------------------------------------------------
    def summary(self) -> dict:
        engines = list(self.engines.values())  # snapshot: worker may mutate
        return {
            "worker": self.wid,
            "completed": self.completed,
            **{k: self.retired[k] + sum(getattr(e.stats, k) for e in engines)
               for k in _COUNTS},
            "snapshot_swaps": self.swaps,
            "compiled_shapes": sorted(
                {s for e in engines for s in list(e.stats.shapes)}
            ),
        }


class ServeFleet:
    """N replicated ``ServeEngine`` workers behind an admission router.

    ``source`` is either a frozen ``ModelSnapshot`` (fixed fleet) or a
    ``SnapshotRegistry`` (serves ``latest``; with ``watch_registry``
    hot-swaps on publish; with ``ensemble=E`` fans every request out to
    the E newest versions and averages).

    ``slo_ms`` turns on SLO accounting in the router: per-bucket
    ok/miss counters against the end-to-end latency threshold, surfaced
    by ``stats_summary`` and the global metrics registry.

    ``submit``/``run`` mirror ``ServeEngine``: submit enqueues (blocking
    on backpressure beyond ``max_pending`` queued subtasks), ``run``
    blocks until everything submitted has completed and hands back
    {rid: mixture}, drained. Use as a context manager or ``close()``
    explicitly — workers are real threads.
    """

    def __init__(
        self,
        source: Union[ModelSnapshot, SnapshotRegistry],
        *,
        workers: Optional[int] = None,
        slots: int = 8,
        burnin: int = 16,
        impl: str = "sparse",
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        base_key=None,
        ensemble: int = 1,
        watch_registry: bool = False,
        max_pending: int = 1024,
        poll_registry_s: float = 0.05,
        slo_ms: Optional[float] = None,
    ):
        if workers is None:
            workers = len(jax.devices())
        if workers < 1:
            raise ValueError("workers must be >= 1")
        if ensemble < 1:
            raise ValueError("ensemble must be >= 1")
        self.registry = source if isinstance(source, SnapshotRegistry) else None
        if self.registry is None:
            if watch_registry:
                raise ValueError("watch_registry needs a SnapshotRegistry")
            if ensemble > 1:
                raise ValueError("ensemble > 1 needs a SnapshotRegistry")
            self._snap_cache: dict[int, ModelSnapshot] = {_PINNED: source}
            self._target_version = _PINNED
        else:
            latest = self.registry.latest_version()
            if latest is None:
                raise FileNotFoundError(
                    f"registry {self.registry.path!r} has no published "
                    "versions to serve"
                )
            self._snap_cache = {}
            self._target_version = latest
        self.slots = slots
        self.burnin = burnin
        self.impl = impl
        self.buckets = tuple(sorted(buckets))
        self.base_key = jax.random.key(0) if base_key is None else base_key
        self.ensemble = ensemble
        self.watch = watch_registry
        self.poll_registry_s = poll_registry_s
        self.router = AdmissionRouter(
            buckets=self.buckets, max_pending=max_pending, slo_ms=slo_ms
        )
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._last_poll = 0.0
        self._next_rid = 0
        self._submitted = 0
        self._wall_s = 0.0
        self._t0: Optional[float] = None
        devices = jax.devices()
        self.workers = [
            _Worker(self, w, devices[w % len(devices)])
            for w in range(workers)
        ]
        for w in self.workers:
            w.start()

    # -- snapshots / registry ---------------------------------------------
    def _snapshot(self, version: int) -> ModelSnapshot:
        with self._lock:
            snap = self._snap_cache.get(version)
            if snap is None:
                snap = self._snap_cache[version] = self.registry.load(version)
                # bound the host-side cache across many hot-swaps; a
                # dropped entry costs at worst a reload (workers hold
                # their own device-local copies).
                cap = max(8, self.ensemble + 2)
                for v in sorted(self._snap_cache):
                    if len(self._snap_cache) <= cap:
                        break
                    if v not in (version, self._target_version, _PINNED):
                        del self._snap_cache[v]
            return snap

    def _maybe_poll(self):
        """Rate-limited registry re-check (workers call this between
        engine steps when ``watch_registry`` is on)."""
        if not self.watch:
            return
        now = time.perf_counter()
        with self._lock:
            if now - self._last_poll < self.poll_registry_s:
                return
            self._last_poll = now
        self.refresh_registry()

    def refresh_registry(self):
        """Synchronously re-read the registry's latest version. After
        this returns, every admission that has not yet reached an engine
        binds to the new version (in-flight slots are untouched).

        The target only ever moves FORWARD: registry versions are
        monotone, and a worker's rate-limited poll may race a publish —
        a stale read must never swap the fleet back onto the older
        snapshot."""
        if self.registry is None:
            return
        latest = self.registry.latest_version()
        if latest is not None and latest > self._target_version:
            self._target_version = latest

    # -- request lifecycle -------------------------------------------------
    def submit(self, tokens: np.ndarray, *, seed: Optional[int] = None,
               timeout: Optional[float] = None) -> int:
        """Enqueue one document. ``seed`` defaults to the request id and
        fully determines the fold-in randomness (the same contract as
        ``ServeEngine.submit``); blocks under backpressure."""
        self._raise_worker_errors()
        versions = None
        if self.ensemble > 1:
            versions = self.registry.latest_versions(self.ensemble)
        with self._lock:
            rid = self._next_rid if seed is None else seed
            self._next_rid = max(self._next_rid, rid) + 1
            if self._t0 is None:
                self._t0 = time.perf_counter()
        self.router.submit(rid, tokens, versions=versions, timeout=timeout)
        with self._lock:
            self._submitted += 1
        return rid

    def run(self, timeout: Optional[float] = None) -> dict[int, np.ndarray]:
        """Block until every submitted request has completed; returns
        {rid: mixture}, drained. Worker failures surface here."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        while True:
            self._raise_worker_errors()
            step = (None if deadline is None
                    else max(deadline - time.perf_counter(), 0.0))
            try:
                out = self.router.drain(
                    timeout=0.5 if step is None else min(step, 0.5)
                )
                break
            except TimeoutError:
                if deadline is not None and time.perf_counter() >= deadline:
                    raise
        with self._lock:
            if self._t0 is not None:
                self._wall_s += time.perf_counter() - self._t0
                self._t0 = None
        return out

    def _raise_worker_errors(self):
        for w in self.workers:
            if w.error is not None:
                err, w.error = w.error, None
                raise RuntimeError(
                    f"fleet worker {w.wid} failed"
                ) from err

    # -- stats / lifecycle -------------------------------------------------
    def stats_summary(self) -> dict:
        per_worker = [w.summary() for w in self.workers]
        # request-level completion from the router: an ensemble request
        # counts ONCE here; per-worker counters count engine subtasks.
        completed = self.router.completed_total()
        wall = self._wall_s + (
            time.perf_counter() - self._t0 if self._t0 is not None else 0.0
        )
        return {
            "workers": len(self.workers),
            "ensemble": self.ensemble,
            "completed": completed,
            **{k: sum(s[k] for s in per_worker) for k in _COUNTS},
            "snapshot_swaps": sum(s["snapshot_swaps"] for s in per_worker),
            "wall_s": round(wall, 3),
            "docs_per_s": round(completed / max(wall, 1e-9), 2),
            **self.router.latency_summary(),
            "per_worker": per_worker,
        }

    def close(self):
        """Stop workers and release engines (idempotent)."""
        self._stop.set()
        self.router.close()
        for w in self.workers:
            w.join(timeout=60)
        alive = [w.wid for w in self.workers if w.is_alive()]
        if alive:
            raise RuntimeError(f"fleet workers {alive} failed to stop")
        self._raise_worker_errors()

    def __enter__(self) -> "ServeFleet":
        return self

    def __exit__(self, *exc):
        self.close()
