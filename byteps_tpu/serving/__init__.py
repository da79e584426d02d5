"""Continuous-batching serving engine (Orca/vLLM-style, JAX-native).

The inference layer (``byteps_tpu.inference``) stops at one-shot
``generate()`` calls: every caller pays a private prefill + decode loop,
and concurrent callers never share a batch.  This package turns those
kernels into a *serving engine*:

  * ``slots`` — a fixed-capacity KV-cache slot pool built on
    ``models.transformer.init_cache`` (N slots x max_seq padded cache),
    so admitting a request is a cache-row write, not a recompile;
  * ``blocks`` — the paged alternative (``paged=True``): KV memory as
    a pool of fixed-size blocks with per-slot block tables, lazy block
    grants, copy-on-write forks, and preemption under pressure —
    actual usage, not worst-case ``max_seq``, bounds concurrency, and
    a prefix-cache hit shares refcounted blocks instead of copying
    rows (PagedAttention / RadixAttention unified);
  * ``scheduler`` — credit-scheduled admission reusing the semantics of
    ``common/scheduler.py:ScheduledQueue``: prefill (large, bursty)
    interleaves against decode (small, latency-critical) under a token
    credit budget, FIFO within priority, with a bounded queue that
    rejects loudly when full;
  * ``engine`` — the jitted step functions (batched single-token decode
    over the whole slot pool; bucket-padded prefill, optionally split
    into position-offset chunks so long prompts interleave with decode
    ticks instead of stalling them) plus the host-side tick loop;
    static shapes end to end, so steady-state serving never retraces;
  * ``prefix`` — a refcounted, LRU/byte-budgeted store of block-aligned
    KV prefixes keyed by a rolling token hash: shared system prompts
    are copied device-side into the slot row instead of recomputed
    (bit-exact — the bytes move, nothing is re-derived);
  * ``spec`` — draft-free speculative decoding (``spec_k > 0``):
    n-gram prompt-lookup proposals from each request's own history,
    verified in ONE batched multi-token pass per tick
    (``Transformer.verify_tokens``) — several tokens per tick on
    repetitive output, bit-exact by construction because a proposal is
    accepted only when it equals the token the model itself produced;
  * ``frontend`` — an in-process ``ServeClient`` (submit / stream /
    cancel / drain) and a thin length-prefixed TCP frontend launched by
    ``launcher.py`` under the ``serve`` role;
  * ``router`` — the fault-tolerant scale-out tier over N frontend
    replicas (``launcher.py`` role ``router``): health-checked
    failover with deterministic mid-stream re-dispatch (a dead
    replica's requests resume token-identically on a survivor),
    prefix-affinity placement, per-replica credit backpressure,
    per-tenant fair-share credits, and graceful drain — and the
    router itself is no single point of failure: standbys follow an
    ``OP_JOURNAL`` state stream (``journal``), take over
    deterministically at a fenced epoch on active death, and
    multi-router clients re-issue mid-stream with ``resume`` —
    docs/serving.md "Router tier" / "Router HA";
  * ``disagg`` — disaggregated prefill/decode tiers (docs/serving.md
    "Disaggregated tiers"): prefill-role replicas ship finished-prompt
    KV as paged blocks over ``OP_KV_BLOCKS`` to the decode replica the
    router chose, which adopts them through the resume machinery —
    bit-exact, with decode-side re-prefill as the availability floor;
  * ``autoscale`` — the elastic-capacity subsystem (docs/serving.md
    "Elastic capacity & SLO classes"): windowed tier signals, a
    hysteresis-banded target-tracking scale policy, a launcher-backed
    actuator that journals scale events for HA takeover, and SLO-class
    admission — deadline-aware shedding (typed ``OverloadShedError``)
    plus work-conserving tenant shares (idle credits are lent and
    clawed back on demand);
  * ``metrics`` — TTFT/TPOT/queue-wait and occupancy/tokens-per-sec
    counters exported through the process ``Tracer``.

Correctness anchor: in deterministic mode (the default) the engine's
output is token-identical to sequential ``generate()`` per request —
see docs/serving.md.
"""

from .autoscale import (  # noqa: F401
    AutoscaleController,
    OverloadShedError,
    ReplicaLauncher,
    ScaleDecision,
    ScalePolicy,
    TenantShares,
    TierSignals,
    normalize_slo,
)
from .blocks import (  # noqa: F401
    BlockAllocator,
    BlocksExhaustedError,
    BlockTable,
    PagedSlotPool,
)
from .disagg import (  # noqa: F401
    KVShipAbortedError,
    KVShipDigestError,
    KVShipError,
    KVShipGeometryError,
    KVShipSequenceError,
    KVStager,
    pool_geometry,
    ship_parked,
)
from .engine import (  # noqa: F401
    EpochFencedError,
    Request,
    RequestState,
    ServingEngine,
)
from .frontend import (  # noqa: F401
    RemoteServeClient,
    ServeClient,
    ServeConnectionError,
    ServeReplyError,
    build_engine_from_env,
    serve,
    serve_from_env,
)
from .journal import JournalSender  # noqa: F401
from .metrics import ServeMetrics, get_serve_metrics  # noqa: F401
from .router import (  # noqa: F401
    ReplicaLostError,
    ReplicaState,
    RouterFrontend,
    RouterStandbyError,
    ServeRouter,
    WeightsMismatchError,
    router_from_env,
    serve_router,
)
from .spec import NgramProposer  # noqa: F401
from .prefix import (  # noqa: F401
    PagedPrefixCache,
    PrefixCache,
    PrefixEntry,
    weights_fingerprint,
)
from .scheduler import (  # noqa: F401
    AdmissionError,
    PrefillTask,
    QueueFullError,
    ServeScheduler,
)
from .slots import SlotPool  # noqa: F401
