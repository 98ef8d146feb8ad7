"""The always-on incremental scheduler loop (ISSUE 7).

An arrival run exposed the shape of the old engine: a pre-loaded 30k-pod
backlog drained at 28.8k pods/s, but under a live 5k/s offered stream it
bound almost nothing while pods arrived (backlog 29k at offer end, p99
create->bound 2.2 s) — a batch drain wearing a streaming costume. A real
kube-scheduler is never drained; it runs forever against a churning
cluster. This module inverts the control flow: the LOOP owns the
scheduler (pop whatever is queued the moment the device frees up)
instead of a scenario owning rounds.

ScheduleLoop is the one engine for both shapes:

- FIXED mode (``budget_s=None``) is the pipelined drain of ISSUE 2,
  byte-for-byte: each step pops one fixed-size chunk, dispatches its
  fused wave eval without blocking, then harvests the previous chunk.
  ``Scheduler.pipeline()`` and ``run_until_drained`` ride this mode, so
  the pre-loaded drain scenarios (and their A/B tests) are unchanged.

- STREAMING mode (``budget_s`` set) admits MICRO-WAVES on a latency
  budget instead of fixed chunks: each step pops ``min(ready, quantum)``
  where the quantum is a power-of-2 admission cap sized from the
  observed per-wave pop->bind-complete wall clock. At a pop where the
  ready backlog is larger than the quantum, the loop fits the latency of
  the current busy period's completed waves as a fixed cost plus a cost
  per pod, and raises the quantum at once to the largest ladder size
  whose predicted latency stays within the budget and whose next size
  down is predicted under half of it (amortizing per-wave fixed costs
  when a backlog waits, without crawling up one doubling at a time).
  The quantum halves when the latency EWMA crosses the budget (bounding
  what one wave can make the next arrival wait for). Pops pad to
  ``bucket(max(n, min_quantum))`` through the engine's
  ``wave_pad_floor`` machinery, so the compiled-shape set is the log2
  ladder between min_quantum and max_quantum — a ragged arrival stream
  (345, 589, 100, ...) never mints a fresh XLA compile (the GL003
  hazard the ladder exists to kill).

Between micro-waves only the delta touches the device (the Firmament
insight, PAPERS.md §Firmament: incremental re-solve over deltas turns a
fast batch solver into a low-latency online scheduler): the class
encoding is reused via the (vocab_gen, aff_seq) key, the snapshot
refresh rides the owner's changed_hint, and fence-accepted assumes fold
in through snapshot.apply_assume_delta — zero re-tensorization and zero
full node walks while the loop is live (tests/test_stream_loop.py pins
this through span counters). Correctness is unchanged from the drain:
wave k+1 is encoded blind to wave k's commits and the harvest fence
re-validates (capacity, topology, gang quorum) — admission control
changes WHEN waves run, never what a wave means.
"""

from __future__ import annotations

import collections
import gc
import threading
import time
import weakref
from typing import Callable, Dict, Optional

from kubernetes_tpu.observability import recorder as flightrec
from kubernetes_tpu.observability.podtrace import TRACER
from kubernetes_tpu.observability.recorder import RECORDER
from kubernetes_tpu.ops.predicates import bucket
from kubernetes_tpu.utils.trace import COUNTERS, Trace, begin_annotation

# CPython's cyclic collector, scheduled by the live loop. The default
# gen-0 threshold (700 net tracked allocations) runs hundreds of young
# passes inside one 30k-pod burst, each on the loop thread mid-wave;
# 100,000 leaves room for a few tracked objects per pod of a full
# 16,384-pod quantum, and gen 1 and gen 2 follow at 50 passes each, so a
# full pass stays rare. Collection stays enabled: a loop that never
# idles still collects, only less often.
GC_THRESHOLDS = (100_000, 50, 50)
# how long the loop must have popped nothing, with no wave in flight,
# before it spends the lull on one collection
GC_IDLE_S = 0.05
# how many of the current busy period's completed waves the admission fit
# keeps: enough sizes to part a wave's fixed cost from its cost per pod,
# recent enough to follow that cost as the cluster fills
WAVE_HISTORY = 8


class _CollectorSchedule:
    """The collector's schedule while any ScheduleLoop is live, shared by
    every loop of the process: the first loop to acquire it raises the
    thresholds to GC_THRESHOLDS, the last to release it puts back exactly
    the thresholds it found.

    While held, a `gc.callbacks` hook times every collection. The hook
    runs inside whatever allocation triggered the collection, possibly
    while that thread holds COUNTERS' lock, so it only adds to a list of
    its own; `flush()` moves that into COUNTERS from a safe point (each
    loop step, close): `gc.pause` (count and seconds, like a span) and
    `gc.collections.gen0/1/2`. While a profiler capture records, each
    pause is also a `gc.pause` annotation on the host plane, inside the
    span it stretched."""

    def __init__(self):
        # a plain lock, not lockcheck's: release() runs inside collections,
        # where the checker's own bookkeeping lock may already be held
        self._lock = threading.RLock()
        self._users = 0
        self._saved = None
        self._hook = self._on_gc
        self._t0 = None
        self._tm = None
        self._tally = [0.0, 0, 0, 0]    # pause seconds, gen0, gen1, gen2

    def acquire(self) -> None:
        with self._lock:
            if self._users == 0:
                self._saved = gc.get_threshold()
                gc.set_threshold(*GC_THRESHOLDS)
                gc.callbacks.append(self._hook)
            self._users += 1

    def release(self) -> None:
        """Also a weakref finalizer of a loop never closed, so it may run
        inside a collection: it takes no lock but its own."""
        with self._lock:
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self._hook)
                gc.set_threshold(*self._saved)
                self._end_pause()

    def _end_pause(self) -> Optional[float]:
        t0, self._t0 = self._t0, None
        tm, self._tm = self._tm, None
        if tm is not None:
            tm.__exit__(None, None, None)
        return None if t0 is None else time.perf_counter() - t0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            self._tm = begin_annotation("gc.pause")
            return
        dur = self._end_pause()
        if dur is not None:
            tally = self._tally
            tally[0] += dur
            tally[1 + info["generation"]] += 1

    def flush(self) -> None:
        # the swap allocates nothing between its read and its write, so a
        # collection lands wholly in the old tally or wholly in the new
        fresh = [0.0, 0, 0, 0]
        tally, self._tally = self._tally, fresh
        if tally[1] or tally[2] or tally[3]:
            COUNTERS.add_time("gc.pause", tally[0],
                              n=tally[1] + tally[2] + tally[3])
            for gen in range(3):
                if tally[1 + gen]:
                    COUNTERS.inc(f"gc.collections.gen{gen}", tally[1 + gen])


_COLLECTOR = _CollectorSchedule()


class ScheduleLoop:
    """A live two-stage scheduling pipeline, optionally self-pacing.

    step() pops one admission of pods, dispatches its fused wave eval
    WITHOUT blocking, then harvests the PREVIOUS admission — so wave
    k+1's device time overlaps wave k's host bookkeeping (assume, bulk
    bind, watch drain). overlap=False is the sequential debug mode:
    identical dataflow (same blind window, same fence), device forced to
    complete before the host tail — placements are bit-identical, only
    the wall-clock overlap is forfeited.

    budget_s=None (fixed mode) admits exactly ``chunk`` pods per step —
    the ISSUE 2 drain pipeline. budget_s set (streaming mode) admits up
    to the adaptive ``quantum`` (see module docstring); ``chunk`` then
    serves as the initial quantum when given.
    """

    def __init__(self, sched, chunk: int = 0, overlap: bool = True,
                 budget_s: Optional[float] = None,
                 min_quantum: int = 256, max_quantum: int = 16384,
                 fastlane=None):
        self.sched = sched
        self.overlap = overlap
        self.budget_s = budget_s
        self.inflight = None
        self._pending: Dict[str, int] = {}  # stats from interrupt flushes
        # Sparrow fast lane (ISSUE 17): when given an engine.fastlane
        # .FastLane, latency-critical pods route to the queue's fast tier
        # and are pumped between micro-waves (and while a harvest blocks
        # on the device). None = the tier is off and every step below is
        # shape-identical to the pre-fast-lane loop.
        self.fastlane = fastlane
        # per-STEP cap on critical-path fast pops: the bulk stream pays
        # the fast tier's host time out of its own budget, so one burst
        # of fast arrivals must not starve a quantum (harvest-overlap
        # pumps are exempt — the host would otherwise just be blocked on
        # the device)
        self.fast_budget = 256
        if fastlane is not None:
            sched.queue.fast_classifier = fastlane.classify
        sched._pipeline = self
        if budget_s is None:
            # fixed mode: one compiled wave shape per drain — ragged
            # arrival pops pad up to the chunk bucket instead of
            # compiling per power-of-2 size
            self.chunk = max(int(chunk or sched.pipeline_chunk), 1)
            self.min_quantum = self.max_quantum = self.quantum = self.chunk
            sched.engine.wave_pad_floor = self.chunk
        else:
            self.min_quantum = bucket(max(int(min_quantum), 1))
            self.max_quantum = max(bucket(max(int(max_quantum), 1)),
                                   self.min_quantum)
            q = bucket(max(int(chunk), 1)) if chunk else self.min_quantum
            self.quantum = min(max(q, self.min_quantum), self.max_quantum)
            self.chunk = 0
            # micro-waves share the bucket ladder: every pop pads to
            # bucket(max(n, min_quantum)), so the compiled-shape set is
            # bounded at log2(max_quantum / min_quantum) + 1
            sched.engine.wave_pad_floor = self.min_quantum
        # latency model (streaming mode): EWMA of per-wave pop ->
        # bind-complete wall clock, the exact span an arriving pod adds
        # to the next pod's worst case; and (pods, latency) of the
        # current busy period's completed waves, which size the quantum
        self._lat_ewma = 0.0
        self._busy_waves = collections.deque(maxlen=WAVE_HISTORY)
        # housekeeping under load (ISSUE 8): empty-round gating starved
        # backoff gc + assume-TTL expiry on a saturated stream — run them
        # on a wall-clock cadence regardless of load
        self.gc_interval_s = 2.0
        self._last_gc = time.monotonic()
        # DEGRADED MODE (ISSUE 8): when the fence keeps throwing waves
        # back (fence conflicts, liveness rejects, gang rollbacks breach
        # degrade_threshold of the attempts for degrade_window consecutive
        # pod-ful steps), the optimistic blind-wave pipeline is losing to
        # churn — drop to the classic SYNCHRONOUS round (every placement
        # sees every commit; no blind window to fence) for recover_steps
        # pod-ful steps, then re-try streaming. Re-entering is cheap and
        # the hysteresis window keeps one bad wave from flapping the mode.
        self.degraded = False
        self.degrade_threshold = 0.5
        self.degrade_window = 3
        self.recover_steps = 16
        self._breach_streak = 0
        self._degraded_left = 0
        # budget-breach tracing (ISSUE 13 satellite): a pod-ful streaming
        # step that outlives the latency budget dumps its step breakdown
        # (utils/trace.Trace.log_if_long — the reference's slow-Schedule
        # discipline at the micro-wave grain). trace_now/trace_sink are
        # the test seams (fake clock, captured sink); threshold 0
        # disables the trace construction entirely.
        self.trace_threshold_s = budget_s or 0.0
        self.trace_now = time.monotonic
        self.trace_sink = None
        # stream gauges into the owner's telemetry registry (ISSUE 13):
        # quantum/backlog/degraded are THE live-introspection answers to
        # "why is p99 moving" — re-registering under one key means a
        # replacement loop supersedes a closed one
        telemetry = getattr(sched, "telemetry", None)
        if telemetry is not None:
            telemetry.register_gauges("stream", self._gauges)
        # the collector's schedule (GC_THRESHOLDS) while this loop is
        # live; close() releases it, and so does the loop's own
        # collection should it never be closed
        self._idle_since: Optional[float] = None
        self._idle_collected = False
        _COLLECTOR.acquire()
        self._release_collector = weakref.finalize(self, _COLLECTOR.release)

    # ------------------------------------------------------------- state

    def _gauges(self):
        """Live stream state for the telemetry registry: what every
        introspection transport reports next to the counters. A scrape
        races the loop thread, so the in-flight handle is read ONCE —
        re-reading after the None check could catch the flush swap
        mid-stride."""
        handle = self.inflight
        inflight = 0 if handle is None else len(handle.pods)
        return {"stream_quantum": self.quantum,
                "stream_backlog": self.sched.queue.ready_count() + inflight,
                "stream_inflight": inflight,
                "stream_degraded": int(self.degraded),
                "stream_budget_ms": (self.budget_s or 0.0) * 1e3,
                "stream_fast_pending": self.sched.queue.fast_count()}

    @property
    def idle(self) -> bool:
        return self.inflight is None

    def flush(self) -> None:
        """Harvest the in-flight wave NOW (watch-event interrupt, classic-
        path barrier, shutdown). Its stats fold into the next step."""
        h, self.inflight = self.inflight, None
        if h is not None:
            for k, v in self.sched._complete_wave(h).items():
                self._pending[k] = self._pending.get(k, 0) + v
            self._observe_wave(h)

    # --------------------------------------------------------- admission

    def _observe_wave(self, handle) -> None:
        """Feed one completed wave into the latency model (streaming mode
        only): its pop -> bind-complete latency joins the busy period's
        waves that size the quantum (``_size_quantum``) and the EWMA,
        which halves the quantum the moment it crosses the budget. With
        the pipeline two deep the span covers the residual device wait
        plus both host tails, which is exactly what the NEXT arrival's
        create->bound will inherit."""
        if self.budget_s is None:
            return
        lat = time.monotonic() - handle.pop_ts
        self._busy_waves.append((len(handle.pods), lat))
        a = 0.3
        self._lat_ewma = lat if self._lat_ewma == 0.0 \
            else (1.0 - a) * self._lat_ewma + a * lat
        if self._lat_ewma > self.budget_s \
                and self.quantum > self.min_quantum:
            # one wave's latency crossed the budget: halve what the next
            # admission may make an arrival wait for
            self.quantum //= 2
            COUNTERS.inc("stream.quantum_shrink")

    def _wave_cost(self):
        """(fixed seconds, seconds per pod) of a wave's latency, fit by
        least squares over the busy period's waves. Waves all of one size
        cannot part the two, and a fit whose cost per pod is not positive
        would call any size free: then each wave's whole latency is
        charged per pod, and the dearest such rate taken, which
        overstates the cost of every larger wave."""
        waves = self._busy_waves
        mean_n = sum(n for n, _ in waves) / len(waves)
        mean_lat = sum(lat for _, lat in waves) / len(waves)
        sxx = sum((n - mean_n) ** 2 for n, _ in waves)
        if sxx > 0:
            per_pod = sum((n - mean_n) * (lat - mean_lat)
                          for n, lat in waves) / sxx
            if per_pod > 0:
                return mean_lat - per_pod * mean_n, per_pod
        return 0.0, max(lat / n for n, lat in waves)

    def _size_quantum(self) -> None:
        """At a pop (streaming mode): when the ready backlog is larger
        than the quantum, raise the quantum at once to the largest ladder
        size whose predicted latency stays within the budget and whose
        next size down is predicted under half of it (a full wave that
        ends within half the budget is what earns a doubling). Only
        ladder sizes, so no new shape compiles; never lower than it
        stands (the EWMA alone shrinks it).

        Only waves of the current busy period feed the fit: the loop
        forgets its waves whenever it goes idle (``step``). What a wave
        cost before the last idle stretch was measured against another
        backlog, another fill of the cluster and another load on the
        host, and a loop that carried it over would size its first waves
        unlike a freshly started loop facing the same backlog. So every
        busy period starts from its own measured waves: the first pops
        go at the quantum as it stands, until a wave has completed."""
        if not self._busy_waves \
                or self.sched.queue.ready_count() <= self.quantum:
            return
        fixed, per_pod = self._wave_cost()
        best, size = self.min_quantum, 2 * self.min_quantum
        while size <= self.max_quantum \
                and fixed + per_pod * size <= self.budget_s \
                and fixed + per_pod * (size // 2) < 0.5 * self.budget_s:
            best, size = size, 2 * size
        if best > self.quantum:
            self.quantum = best
            COUNTERS.inc("stream.quantum_sized")

    # ---------------------------------------------------------- degraded

    def _note_health(self, stats: Dict[str, int]) -> None:
        """Feed one completed step into the churn-health model (streaming
        mode only). Attempts = binds + requeues this step surfaced; a step
        that surfaced none leaves the window untouched (idle ticks must
        not decay a breach streak the next loaded step would continue)."""
        if self.budget_s is None:
            return
        requeues = (stats.get("fence_requeued", 0)
                    + stats.get("liveness_requeued", 0)
                    + stats.get("gang_requeued", 0)
                    # sustained preemption-fence rollbacks (ISSUE 14): a
                    # store that keeps refusing atomic evict+bind commits
                    # is the same signal class as fence churn — the
                    # optimistic wave path is losing, drop to classic
                    + stats.get("preempt_rollbacks", 0))
        attempts = (stats.get("bound", 0) + requeues
                    + stats.get("preemptions", 0))
        if self.degraded:
            if attempts > 0:
                self._degraded_left -= 1
                if self._degraded_left <= 0:
                    self.degraded = False
                    self._breach_streak = 0
                    COUNTERS.inc("stream.degraded_exit")
                    if RECORDER.enabled:
                        RECORDER.record(flightrec.DEGRADED, a=0)
            return
        if attempts <= 0:
            return
        if requeues >= self.degrade_threshold * attempts:
            self._breach_streak += 1
            if self._breach_streak >= self.degrade_window:
                self.degraded = True
                self._degraded_left = self.recover_steps
                COUNTERS.inc("stream.degraded_enter")
                if RECORDER.enabled:
                    RECORDER.record(flightrec.DEGRADED, a=1,
                                    b=self._breach_streak)
        else:
            self._breach_streak = 0

    # --------------------------------------------------------- collector

    def _collect_if_idle(self) -> None:
        """Called on a step that popped nothing (so no wave is in flight):
        once the loop has stayed so for GC_IDLE_S, spend the lull on ONE
        collection, which can delay only an arrival that lands during it.
        The young generations, unless gen 2 is due: its automatic pass
        would otherwise land on the next busy step. A process that
        switched the collector off keeps it off."""
        now = time.monotonic()
        if self._idle_since is None:
            self._idle_since, self._idle_collected = now, False
            return
        if self._idle_collected or now - self._idle_since < GC_IDLE_S \
                or not gc.isenabled():
            return
        self._idle_collected = True
        gc.collect(2 if gc.get_count()[2] >= GC_THRESHOLDS[2] else 1)
        COUNTERS.inc("gc.idle_collect")

    # --------------------------------------------------------- fast lane

    def _pump_fast(self, stats: Dict[str, int], limit: int = 0,
                   busy=None) -> int:
        """Drain the queue's fast tier through the FastLane executor —
        the tier-aware pop interleaved between micro-waves (ISSUE 17).
        ``limit`` caps pods this pump (0 = all); ``busy`` is an extra
        WaveHandle still owning the device (the harvest-overlap poll
        passes the wave it is waiting out). Routing is latency policy:
        the sampled eval runs on the resident device arrays only while
        NO wave is in flight (the CPU backend executes device programs
        FIFO — a dispatch behind a wave inherits the wave's latency),
        else the bit-equal host twin."""
        fl = self.fastlane
        if fl is None:
            return 0
        q = self.sched.queue
        if not q.fast_count():
            return 0
        pods = q.pop_fast(max_n=limit)
        if not pods:
            return 0
        pop_ts = time.monotonic()
        device_ok = True
        for h in (self.inflight, busy):
            if h is not None and not h.packed.is_ready():
                device_ok = False
                break
        for p in pods:
            fl.schedule(p, pop_ts, device_ok=device_ok)
        stats["fast_popped"] = stats.get("fast_popped", 0) + len(pods)
        return len(pods)

    # -------------------------------------------------------------- step

    def step(self, wait: float = 0.0) -> Dict[str, int]:
        s = self.sched
        stats = {"popped": 0, "bound": 0, "unschedulable": 0,
                 "bind_errors": 0, "preemptions": 0, "fence_requeued": 0,
                 "liveness_requeued": 0, "degraded_steps": 0}
        # budget-breach tracing (streaming mode): narrate THIS step's
        # phases; dumped only when the step outlives the budget — the
        # scheduler's slow-Schedule discipline at the micro-wave grain
        trace = None
        if self.budget_s is not None and self.trace_threshold_s > 0:
            trace = Trace("micro-wave step", now=self.trace_now,
                          sink=self.trace_sink, quantum=self.quantum)
        s.sync()  # columnar; node/volume events flush the pipeline first
        if trace is not None:
            trace.step("informer sync done")
        if self.fastlane is not None:
            # fast tier first (ISSUE 17): a latency-critical pod that
            # arrived in the sync above binds BEFORE this step's bulk
            # quantum even pops — budgeted so a fast burst can't starve
            # the bulk stream
            self._pump_fast(stats, limit=self.fast_budget)
        now = time.monotonic()
        if now - self._last_gc >= self.gc_interval_s:
            # housekeeping regardless of load (ISSUE 8): a saturated
            # stream never sees an empty round, so the empty-round-gated
            # gc would let backoff stamps for long-bound pods and expired
            # assumes grow without bound over a long run
            s._idle_gc()
            self._last_gc = now
        self._size_quantum()
        pods = s.queue.pop_batch(max_n=self.quantum, wait=wait)
        stats["popped"] = len(pods)
        if trace is not None and pods:
            trace.field("popped", len(pods))
            trace.step("micro-wave popped")
        handle = None
        if not pods:
            # parked-gang sweep on empty steps only: a pod-ful step either
            # takes the wave path, whose gang release sweeps, or falls
            # back to _process_batch which runs the arrival-exempt sweep
            # itself
            s._sweep_parked_gangs(())
        if pods and self.degraded:
            # degraded mode: churn is beating the blind-wave fence — run
            # the classic synchronous round (every placement sees every
            # commit; nothing to fence) until the health model recovers
            stats["degraded_steps"] = 1
        if pods:
            pop_ts = time.monotonic()
            chunk_pods = pods
            if not self.degraded:
                # quorum-ready gangs ride the wave path as ordinary
                # batches (ISSUE 5) — the harvest applies their
                # all-or-nothing fence; below-quorum members park here
                chunk_pods, gang_spans = s._release_gangs_for_wave(
                    pods, stats)
                if chunk_pods:
                    handle = s.engine.dispatch_waves(chunk_pods, pop_ts,
                                                     gangs=gang_spans)
                    if trace is not None and handle is not None:
                        trace.step("wave dispatched (async)")
            if handle is None and chunk_pods:
                # classic fallback (ISSUE 18: no chunk SHAPE lands here
                # anymore — host-check and Policy chunks ride the wave).
                # Remaining triggers: a gang whose quorum is unreachable
                # from its wave-eligible members, and degraded mode. The
                # counter is the no-flush routing guard's observable.
                COUNTERS.inc("stream.chunk_flush")
                self.flush()
                sub = s._process_batch(chunk_pods, pop_ts)
                sub["popped"] = 0  # already counted
                for k, v in sub.items():
                    stats[k] = stats.get(k, 0) + v
                if trace is not None:
                    trace.step("classic fallback round done")
            elif handle is not None and not self.overlap:
                # sequential mode: forfeit the overlap only. The span is
                # the profiler's measure of RAW per-wave device time (no
                # host work runs between dispatch and this block)
                from kubernetes_tpu.utils.trace import timed_span
                with timed_span("pipeline.device_sync"):
                    handle.block()
        prev, self.inflight = self.inflight, handle
        if prev is not None:
            fl = self.fastlane
            if fl is not None and (s.queue.fast_count() or fl.hot()):
                # harvest-overlap poll (ISSUE 17): the host is about to
                # block on prev's device array anyway, so until it lands,
                # serve fast pods (host-twin evals — the device is busy)
                # and SIP the watch stream for newly created ones
                # (sync_pods_sip drains only the leading run of simple
                # pod events and can never flush/reorder the pipeline).
                # Exempt from fast_budget: these pops cost the bulk
                # stream nothing — the alternative was idle blocking.
                packed = prev.packed
                while not packed.is_ready():
                    if self._pump_fast(stats, busy=prev) == 0 \
                            and s.sync_pods_sip() == 0:
                        time.sleep(0.0002)
            for k, v in s._complete_wave(prev).items():
                stats[k] = stats.get(k, 0) + v
            self._observe_wave(prev)
            if self.fastlane is not None and \
                    (s.queue.fast_count() or self.fastlane.hot()):
                # post-harvest pump (ISSUE 17): the harvest above is the
                # one host section the overlap poll can't thread through
                # — a fast pod that arrived inside it binds NOW, not
                # after the next step's sync + bulk quantum (budgeted:
                # the bulk stream already got this step's wave)
                s.sync_pods_sip()
                self._pump_fast(stats, limit=self.fast_budget)
            if trace is not None:
                trace.step("previous wave harvested + bound")
        if self._pending:
            for k, v in self._pending.items():
                stats[k] = stats.get(k, 0) + v
            self._pending = {}
        if not pods:
            # nothing popped and nothing left in flight: the busy period
            # is over, and its waves size no later one (_size_quantum)
            self._busy_waves.clear()
            s._idle_gc()
            self._collect_if_idle()
        else:
            self._idle_since = None
        _COLLECTOR.flush()
        self._note_health(stats)
        if trace is not None and (pods or prev is not None):
            # only steps that did wave work can breach meaningfully; an
            # idle tick dumping its (empty) breakdown would be noise
            trace.field("bound", stats["bound"])
            trace.field("degraded", int(self.degraded))
            if TRACER.enabled and trace.total() >= self.trace_threshold_s:
                # the pod-level black box joins the step forensics
                # (ISSUE 15): a breaching step's dump names the window's
                # slowest exemplar so the per-pod timeline is one
                # /debug/pods lookup away
                ex = TRACER.snapshot()["exemplars"]
                if ex:
                    trace.field("slowest_pod", ex[0]["key"])
                    trace.field("slowest_span_ms",
                                round(ex[0]["span_ms"], 1))
            trace.log_if_long(self.trace_threshold_s)
        return stats

    # ------------------------------------------------------------ quiesce

    def settled(self) -> bool:
        """The ONE quiesce predicate (benchmark stop conditions, drain(),
        tests): pipeline idle AND watch stream drained AND ready queue
        empty AND backoff heap empty. The deferred check matters: a pod
        requeued after a transient error is RETRIABLE, and declaring the
        loop settled before it re-enters would report results over a
        silently partial population. Calling this consumes watch events
        (sync side effect), like every other quiesce check before it."""
        s = self.sched
        return (self.idle and s.sync() == 0
                and s.queue.ready_count() == 0
                and not s.queue._deferred)

    def drain(self, idle_wait: float = 0.005) -> Dict[str, int]:
        """Step until settled; returns accumulated stats. Termination is
        the CALLER's contract — truly-unschedulable pods re-enter the
        ready queue forever, so scenario drivers wrap this in a
        wall-clock deadline or feed only placeable pods (warm/prime
        phases, tests)."""
        total: Dict[str, int] = {}
        while True:
            stats = self.step()
            for k, v in stats.items():
                total[k] = total.get(k, 0) + v
            if stats["popped"] == 0 and self.settled():
                return total
            if stats["popped"] == 0 and self.idle and idle_wait > 0:
                # a deferred pod's backoff must elapse — park on the
                # watch instead of spinning the step loop dry
                self.sched.sync(wait=idle_wait)

    # --------------------------------------------------------------- run

    def run(self, should_stop: Callable[[Dict[str, int], "ScheduleLoop"],
                                        bool],
            idle_wait: float = 0.002,
            on_step: Optional[Callable[[Dict[str, int], "ScheduleLoop"],
                                       None]] = None) -> Dict[str, int]:
        """Run continuously until ``should_stop(stats, loop)`` answers
        True — the loop owns the scheduler; scenarios observe through
        ``on_step`` and the scheduler's wave_observer instead of driving
        rounds themselves. Idle iterations (nothing popped, nothing in
        flight) block on the apiserver watch for up to ``idle_wait``
        seconds instead of busy-spinning, so an arrival wakes the loop
        the moment its event lands. Returns accumulated totals
        (close() is still the caller's job — an in-flight wave survives
        a stop so a later loop can resume it)."""
        total: Dict[str, int] = {}
        while True:
            stats = self.step()
            for k, v in stats.items():
                total[k] = total.get(k, 0) + v
            if on_step is not None:
                on_step(stats, self)
            if should_stop(stats, self):
                return total
            if stats["popped"] == 0 and self.idle and idle_wait > 0:
                # block for arrivals on the watch condition, not a sleep:
                # sync(wait=) parks on the apiserver's lock and wakes on
                # the next event broadcast
                self.sched.sync(wait=idle_wait)

    def close(self) -> Dict[str, int]:
        """Drain the in-flight wave and detach from the scheduler; returns
        any stats not yet reported through step(). The collector's
        thresholds go back to the process's own once the last live loop
        closes, also when the drain raises."""
        try:
            self.flush()
        finally:
            self._release_collector()
            _COLLECTOR.flush()
        out, self._pending = self._pending, {}
        if self.sched._pipeline is self:
            self.sched._pipeline = None
        # drop OUR gauges from the owner's registry (a replacement loop's
        # registration already superseded them — leave that one alone):
        # a closed loop serving stale quantum/degraded answers would be
        # introspection lying, and the registered bound method would pin
        # this loop (and its WaveHandle fields) alive
        telemetry = getattr(self.sched, "telemetry", None)
        if telemetry is not None:
            telemetry.unregister_gauges("stream", only_if=self._gauges)
        return out
