"""Serving metrics, registry-backed with ``Tracer`` surfacing.

Same pattern as ``resilience/counters.py``: every observation lands in
a :class:`~byteps_tpu.observability.metrics.MetricsRegistry` (the
process-global one for ``get_serve_metrics()`` — what ``/metrics``,
``OP_STATS`` and the TCP STATS reply scrape live — or a private one per
standalone ``ServeMetrics()`` so tests count in isolation).  When
``BYTEPS_TRACE_PATH`` is set each bump also lands on the shared
chrome-trace timeline as a counter event (value track), so batch
occupancy, queue depth, and token throughput render next to the
engine's push/pull spans in Perfetto — unchanged from pre-registry
traces.  Per-request latency samples (queue wait, TTFT, TPOT) feed
bounded-reservoir registry histograms that back the ``summary()``
percentiles the TCP STATS op reports.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from ..common import logging as bps_log
from ..common.tracing import TICK_PHASES
from ..observability.metrics import MetricsRegistry, get_registry

# canonical counter names
SUBMITTED = "serve.requests_submitted"
ADMITTED = "serve.requests_admitted"
REJECTED = "serve.requests_rejected"
COMPLETED = "serve.requests_completed"
CANCELLED = "serve.requests_cancelled"
FAILED = "serve.requests_failed"
TOKENS = "serve.tokens_generated"
PREFILL_TOKENS = "serve.prefill_tokens"
# chunked prefill (serving/engine.py chunk>0): one bump per jitted
# chunk call — with PREFILL_TOKENS this gives padded tokens/chunk
PREFILL_CHUNKS = "serve.prefill_chunks"
# prefix-reuse KV cache (serving/prefix.py): lookup outcomes per
# admission and the tokens whose prefill was skipped by a device-side
# K/V copy (the FLOP saving PREFILL_TOKENS no longer contains)
PREFIX_HITS = "serve.prefix_hits"
PREFIX_MISSES = "serve.prefix_misses"
PREFIX_HIT_TOKENS = "serve.prefix_hit_tokens"
PREFIX_INSERTIONS = "serve.prefix_insertions"
# paged KV cache (serving/blocks.py): live block-pool accounting
# (gauges, per tick) plus the pressure-path counters — prefix-entry
# evictions that released blocks, and requests preempted back to
# QUEUED when the pool ran dry mid-flight
KV_BLOCKS_FREE = "serve.kv_blocks_free"
KV_BLOCKS_USED = "serve.kv_blocks_used"
KV_BLOCKS_SHARED = "serve.kv_blocks_shared"
BLOCK_EVICTIONS = "serve.block_evictions"
PREEMPTIONS = "serve.preemptions"
# blocks the XLA gather fallback materialized into dense rows this
# tick (n_slots x high-water bucket, decode AND verify passes):
# GATHERED_BLOCKS * pool.block_bytes is the per-tick cache-stream copy
# the pos-capped gather shrinks and the fused kernel eliminates
GATHERED_BLOCKS = "serve.gathered_blocks"
# speculative decoding (serving/engine.py spec_k > 0, serving/spec.py):
# DECODE_TICKS counts ticks that ran a decode/verify forward (the
# denominator of tokens-per-tick — what speculation exists to raise);
# SPEC_* account the proposal economy.  PROPOSED counts tokens handed
# to the verifier, ACCEPTED the proposed tokens the model confirmed
# (extra tokens beyond the one-per-tick floor, BEFORE budget/eos
# truncation — the verifier's own yield), VERIFY_TICKS the ticks that
# ran the widened verify program instead of plain decode.  TOKENS
# stays emissions-only: accepted-but-never-emitted tokens (truncated
# at the request's budget or at EOS) are counted nowhere, so
# TPOT/tokens-per-tick cannot be skewed by work the client never saw.
DECODE_TICKS = "serve.decode_ticks"
SPEC_PROPOSED = "serve.spec_proposed_tokens"
SPEC_ACCEPTED = "serve.spec_accepted_tokens"
SPEC_VERIFY_TICKS = "serve.spec_verify_ticks"
# per-tick value tracks (gauges, not monotonic)
OCCUPANCY = "serve.batch_occupancy"
QUEUE_DEPTH = "serve.queue_depth"
# per-request latency tracks (milliseconds, one point per completion)
TTFT_MS = "serve.ttft_ms"
TPOT_MS = "serve.tpot_ms"
QUEUE_WAIT_MS = "serve.queue_wait_ms"
# per-request latency histograms (seconds — the summary()/scrape unit)
QUEUE_WAIT_S = "serve.queue_wait_s"
TTFT_S = "serve.ttft_s"
TPOT_S = "serve.tpot_s"
# live credit level of the prefill scheduler (padded tokens remaining)
PREFILL_CREDITS = "serve.prefill_credits"
# disaggregated prefill/decode (serving/disagg): KV blocks shipped from
# a prefill replica to its decode target over OP_KV_BLOCKS, the wire
# bytes they carried (payload only — framing overhead excluded so the
# counter divides into block_bytes exactly), and the per-request ship
# latency (park -> last ack, seconds) as a reservoir histogram
KV_BLOCKS_SHIPPED = "serve.kv_blocks_shipped"
KV_BLOCKS_SHIPPED_BYTES = "serve.kv_blocks_shipped_bytes"
SHIP_LATENCY_S = "serve.ship_latency_s"
# the tick from inside (docs/timeline.md "Reading a tick"): cumulative
# host seconds of the tick thread by phase (label ``phase``, one of
# common/tracing.py:TICK_PHASES) and the ticks that had work, so that a
# window's share is ``after - before`` of two STATS replies; the wait
# for the engine lock at the top of submit() (what TTFT holds and the
# queue wait does not) and the hand-off from _emit to the connection
# thread's sendall returning.  Registry-only (``mirror=False``): ten
# adds a tick on the Chrome timeline would be the flood _step_locked's
# idle-tick comment warns of, and the host spans carry the detail.
TICK_SECONDS = "serve.tick_seconds"
TICKS_WORKED = "serve.ticks_worked"
SUBMIT_LOCK_WAIT_S = "serve.submit_lock_wait_s"
EMIT_TO_WIRE_S = "serve.emit_to_wire_s"


class ServeMetrics:
    """Thread-safe serving counters + latency samples, registry-backed.

    ``registry=None`` builds a private registry (isolated counting —
    the semantics standalone instances always had); the
    ``get_serve_metrics()`` singleton binds the process-global registry
    so scrapes see the serving engine live."""

    _HIST = {"queue_wait": QUEUE_WAIT_S, "ttft": TTFT_S, "tpot": TPOT_S,
             "ship": SHIP_LATENCY_S,
             "submit_lock_wait": SUBMIT_LOCK_WAIT_S,
             "emit_to_wire": EMIT_TO_WIRE_S}

    def __init__(self, tracer=None,
                 registry: Optional[MetricsRegistry] = None):
        self._registry = (registry if registry is not None
                          else MetricsRegistry(tracer=tracer))
        # bumped-through-this-instance names: snapshot()/summary() report
        # exactly this instance's series even on a shared registry
        self._names: Dict[str, None] = {}
        self._lock = threading.Lock()
        # the tick's counters, looked up once: eleven get-or-creates a
        # tick were 0.08 ms of a 19.5 ms tick on the chip's host
        # (PERF.md §6, PR 36); reset_serve_metrics() empties it
        self._tick_counters: Dict[str, object] = {}

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry

    def _hist(self, label: str):
        return self._registry.histogram(self._HIST[label], track="serve")

    # ------------------------------------------------------------ counters

    def bump(self, counter: str, n: int = 1, **args) -> int:
        with self._lock:
            self._names.setdefault(counter, None)
        total = self._registry.counter(counter, track="serve").inc(n, **args)
        bps_log.debug("%s -> %d %s", counter, total, args or "")
        return total

    def gauge(self, name: str, value: float) -> None:
        """Non-monotonic value track (occupancy, queue depth, credit
        levels) — stored in the registry (live scrapes) AND mirrored to
        the Tracer value track as before."""
        self._registry.gauge(name, track="serve").set(value)

    # --------------------------------------------------------- observations

    def observe_tick(self, occupancy: float, queue_depth: int,
                     tokens_emitted: int) -> None:
        if tokens_emitted:
            self.bump(TOKENS, tokens_emitted)
        self.gauge(OCCUPANCY, occupancy)
        self.gauge(QUEUE_DEPTH, queue_depth)

    def observe_request(self, queue_wait_s: float, ttft_s: float,
                        tpot_s: Optional[float], tokens: int) -> None:
        """Record one completed request's latency profile.  ``tpot_s``
        is None for single-token requests (no inter-token gaps)."""
        self._hist("queue_wait").observe(queue_wait_s)
        self._hist("ttft").observe(ttft_s)
        if tpot_s is not None:
            self._hist("tpot").observe(tpot_s)
        self.gauge(QUEUE_WAIT_MS, queue_wait_s * 1e3)
        self.gauge(TTFT_MS, ttft_s * 1e3)
        if tpot_s is not None:
            self.gauge(TPOT_MS, tpot_s * 1e3)
        self.bump(COMPLETED, tokens=tokens)

    def observe(self, label: str, seconds: float) -> None:
        """One sample of a ``_HIST`` histogram outside a request's
        completion: ``submit_lock_wait``, ``emit_to_wire``."""
        self._hist(label).observe(seconds)

    def observe_tick_phases(self, seconds: Dict[str, float]) -> None:
        """One working tick's host seconds by phase, onto the cumulative
        ``serve.tick_seconds{phase}`` counters, and the tick itself."""
        counters = self._tick_counters
        if not counters:
            for phase in seconds:
                counters[phase] = self._registry.counter(
                    TICK_SECONDS, track="serve", mirror=False, phase=phase)
            counters[TICKS_WORKED] = self._registry.counter(
                TICKS_WORKED, track="serve", mirror=False)
        for phase, s in seconds.items():
            counters[phase].inc(s)
        counters[TICKS_WORKED].inc()

    # ------------------------------------------------------------ reporting

    def get(self, name: str) -> int:
        m = self._registry.get(name)
        return m.value if m is not None else 0

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            names = list(self._names)
        return {n: self.get(n) for n in names}

    def summary(self) -> Dict[str, object]:
        """Counters plus latency percentiles (seconds)."""
        out: Dict[str, object] = dict(self.snapshot())
        out[TICKS_WORKED] = self.get(TICKS_WORKED)
        out[TICK_SECONDS] = {
            m.labels["phase"]: m.value
            for m in (self._registry.get(TICK_SECONDS, phase=p)
                      for p in TICK_PHASES) if m is not None}
        for label in self._HIST:
            h = self._hist(label)
            out[f"{label}_p50_s"] = h.percentile(50)
            out[f"{label}_p99_s"] = h.percentile(99)
            out[f"{label}_n"] = h.count
        return out


_metrics: Optional[ServeMetrics] = None
_metrics_lock = threading.Lock()


def get_serve_metrics() -> ServeMetrics:
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            _metrics = ServeMetrics(registry=get_registry())
        return _metrics


def reset_serve_metrics() -> None:
    """Forget the singleton AND its counts.  The backing metrics live in
    the process-global registry, which outlives the singleton, so the
    ``serve.*`` namespace (counters, gauges, latency histograms) is
    removed explicitly — otherwise a rebuilt ``get_serve_metrics()``
    would report the previous run's totals and percentile samples."""
    global _metrics
    with _metrics_lock:
        inst, _metrics = _metrics, None
    if inst is not None:
        inst.registry.remove_prefix("serve.")
        inst._tick_counters.clear()  # or they would count on, unseen
        for n in inst.snapshot():  # free-form names outside serve.*
            inst.registry.remove(n)
