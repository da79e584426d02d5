"""Continuous-batching engine: jitted slot-pool step functions + tick loop.

Two compiled programs serve steady state, regardless of how many
requests flow through:

  * **decode step** — one token for EVERY slot per tick: the model's
    per-row ``Transformer.decode`` is ``vmap``-ed over the slot axis
    with per-slot position scalars (slots sit at different depths), so
    the whole pool advances in one program with static ``[N_slots]``
    token/pos vectors and an active-slot mask.  Inactive slots compute
    garbage into their (freed) rows — the price of static shapes — and
    their sampled tokens are masked to ``pad_id``.
  * **prefill** — one request's padded prompt into its slot row:
    ``dynamic_slice`` the row out, run the model's cached prefill
    (static ``pos=0`` — the same dense-prefill path ``generate()``
    takes), gather the true last position's logits, ``dynamic_update_
    slice`` the row back.  Prompts are right-padded to power-of-two
    buckets so the compile count is O(log max_seq), not O(#lengths).
  * **chunked prefill** (``chunk > 0``) — the prefill generalized to
    position-offset chunks (``Transformer.prefill_chunk``): a long
    prompt runs as a sequence of ``[p0, p0 + C)`` chunk calls spread
    over consecutive ticks, each debiting the SAME credit pool the
    admission grants use, so no tick's prefill work exceeds the budget
    and decoding requests keep emitting between chunks (SARATHI-style
    stall bounding).  Requests sit in the ``PREFILLING`` state (slot
    assigned, excluded from decode) until their final chunk samples
    the first token.  Chunk buckets are powers of two capped at the
    chunk size — O(log chunk) compiled programs.
  * **prefix reuse** (``prefix_cache``) — before the first chunk, the
    longest block-aligned cached prefix of the prompt (serving/
    prefix.py) is copied device-side into the slot row by a jitted
    copy program (one trace — entries are full-row buffers), and
    prefill resumes at the boundary.  Bit-exact by construction: the
    K/V bytes are copied, not recomputed.
  * **paged KV cache** (``paged=True``, serving/blocks.py) — slot
    memory as fixed-size blocks with per-slot block tables: the decode
    and chunk programs gather each slot's rows through its table and
    scatter writes back to ``(table[pos // block], pos % block)``,
    blocks are granted lazily at boundary crossings, a prefix hit
    SHARES refcounted blocks (zero device copies — the copy/extract
    programs are never built), and pool exhaustion evicts prefix
    entries then preempts the newest request back to QUEUED (resume is
    bit-exact; docs/serving.md "Paged KV cache").  The gather is
    pos-capped: each tick streams only the block high-water bucket,
    never the null-padded table width.  With the **fused kernel**
    (``paged_kernel``, ops/paged_attention.py) decode and spec-verify
    skip the gather entirely — the Pallas kernel reads allocated,
    position-covered blocks in place through the block table
    (docs/serving.md "Fused paged attention").
  * **speculative decoding** (``spec_k > 0``, serving/spec.py) — the
    decode step generalized from 1 to ``k + 1`` query positions: a
    CPU-side n-gram proposer guesses up to ``k`` continuations from
    each request's own prompt + emitted history (no draft model), ONE
    batched ``Transformer.verify_tokens`` pass scores every proposal,
    and the longest prefix the model itself would have produced is
    accepted — several tokens per tick on repetitive workloads, one
    (exactly the plain decode's token) otherwise.  Rejected positions
    roll back for free: dense, the cursor simply does not advance past
    the accepted count and the stale K/V beyond it is overwritten
    before the causal mask can admit it (the freed-rows argument one
    position wider); paged, writes scatter per position to the slot's
    own granted blocks only (ungranted span positions aim at the null
    block and cap acceptance), so shared prefix blocks are never
    touched.  One verify program per speculation-depth bucket, pinned
    by ``compile_counts()`` exactly like chunk buckets; ticks where no
    slot proposes run the plain decode program untouched.

**Determinism / parity contract** (the correctness anchor, pinned by
tests/test_serving.py and scripts/serve_smoke.py): per request, the
engine reproduces sequential ``generate()`` token for token — greedy
trivially, and under sampling by replaying ``generate()``'s exact key
chain (``PRNGKey(seed)``; split once at prefill, once per decode step).
The numerics match because (a) every per-slot computation is
row-independent under ``vmap``, and (b) a longer cache than
``generate()``'s only adds *masked* attention slots, whose
``exp(-1e30 - max)`` scores underflow to exactly 0.0 and contribute
nothing to any softmax sum or PV dot.  Batch composition therefore
cannot leak between requests.

Tick order is fixed: cancellations, then credit-bounded admissions (in
scheduler grant order), then one decode pass over the pool (slot
order), then credits return.  Given an admission order, the engine's
entire output is deterministic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import hashlib
import queue
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import logging as bps_log
from ..common import tracing
from ..common.tracing import (SCOPE_MODEL, SCOPE_SERVE_ACCEPT,
                              SCOPE_SERVE_SELECT)
from ..inference import sample_logits
from ..models.transformer import Transformer
from . import metrics as sm
from .blocks import BlocksExhaustedError, PagedSlotPool
from .metrics import ServeMetrics, get_serve_metrics
from .prefix import PagedPrefixCache, PrefixCache, weights_fingerprint
from .scheduler import ServeScheduler
from .slots import SlotPool
from .spec import NgramProposer

__all__ = ["EpochFencedError", "Request", "RequestState", "ServingEngine"]


class EpochFencedError(RuntimeError):
    """A dispatch carried a router epoch LOWER than one this engine has
    already served: the sender is a deposed active router that does not
    yet know a standby took over (serving/router.py "Router HA").  The
    refusal is the split-brain guard — accepting the stale dispatch
    could double-serve a request the new epoch's router already
    re-dispatched.  Typed so the stale router can recognize the fence
    and demote itself instead of treating this replica as dead."""

    def __init__(self, epoch: int, high_water: int):
        self.epoch = epoch
        self.high_water = high_water
        super().__init__(
            f"dispatch fenced: epoch {epoch} < this engine's epoch "
            f"high-water {high_water} — a newer router epoch has taken "
            f"over this tier; the sending router must demote")


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"  # slot assigned, chunked prefill in flight
    ACTIVE = "active"
    DONE = "done"
    CANCELLED = "cancelled"
    FAILED = "failed"  # engine tick raised; see Request.error


_END = object()  # stream sentinel


@dataclasses.dataclass
class Request:
    """One in-flight generation request.  Stream tokens with ``for tok
    in req:`` (blocks until the engine emits them) or block for the
    whole sequence with ``result()``."""

    id: int
    prompt: np.ndarray          # [T] int32
    max_new_tokens: int
    seed: int = 0
    priority: int = 0
    state: RequestState = RequestState.QUEUED
    cancelled: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    prefill_pos: int = 0  # prompt tokens already in the slot's K/V rows
    _pf_paid: bool = dataclasses.field(default=False, repr=False)
    # the token sequence the current prefill covers: the prompt, or —
    # after a preemption (paged engine, block pressure) — the prompt
    # plus the already-emitted tokens minus the last one, whose K/V is
    # rebuilt by re-prefill while the token itself stays the next
    # decode input (docs/serving.md "Preemption")
    _seq: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    # preemption resume state: the last emitted token (next decode
    # input) and the carried sampling key at preemption time — restored
    # after the resume prefill so the per-request key chain continues
    # exactly where it stopped (bit-exact seeded parity)
    _resume_tok: Optional[int] = dataclasses.field(
        default=None, repr=False)
    _resume_key: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    # disaggregated serving (serving/disagg): keep_kv parks the
    # finished request's paged blocks for a ship instead of freeing
    # them; _kv_blocks carries staged block ids a decode-side admit
    # adopts in place of re-running prefill
    _keep_kv: bool = dataclasses.field(default=False, repr=False)
    _kv_blocks: Optional[List[int]] = dataclasses.field(
        default=None, repr=False)
    # anti-thrash watermark: a preempted request is re-admitted only
    # once this many blocks are free (its worst-case remaining need) —
    # eagerly re-admitting it would re-prefill, collide with the same
    # pressure, and be preempted again every tick
    _hold_blocks: int = dataclasses.field(default=0, repr=False)
    # tokens pre-seeded by a cross-replica resume submit: ANOTHER
    # engine emitted them, so this engine's latency/token metrics must
    # not claim them (TPOT would under-read exactly during failover)
    _resumed_n: int = dataclasses.field(default=0, repr=False)
    # rolling prefix-block digests, computed once at admit and reused
    # for the post-prefill insert (one blake2b per block per pass —
    # recomputing them three times per request sits on the tick thread)
    _prefix_digs: Optional[List[bytes]] = dataclasses.field(
        default=None, repr=False)
    _task: Optional[object] = dataclasses.field(default=None, repr=False)
    # speculative-decoding proposer context (prompt + emitted tokens,
    # appended incrementally — rebuilding it per tick would put an
    # O(T) copy per request on the tick thread; serving/spec.py)
    _spec_ctx: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    _spec_n: int = dataclasses.field(default=0, repr=False)
    # distributed tracing (docs/observability.md): hex trace id minted
    # at submit when RPC tracing is on; the request's serve span
    # carries it so trace_merge can line serving work up with the PS
    # ops the same logical operation issued
    trace_id: str = ""
    _t_pc: float = dataclasses.field(default=0.0, repr=False)
    # t_submit is taken on submit()'s first line, t_enqueued under the
    # engine lock: TTFT runs from the first (it holds the wait for the
    # lock), the queue wait from the second
    t_submit: float = 0.0
    t_enqueued: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0
    error: Optional[BaseException] = None
    _out: "queue.Queue" = dataclasses.field(default_factory=queue.Queue)
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    def __iter__(self):
        for tok, _ in self.stamped():
            yield tok

    def stamped(self):
        """The stream as ``(token, t_emit)`` pairs: ``t_emit`` is
        ``_emit``'s ``time.monotonic()`` stamp, from which the frontend
        measures the hand-off to the wire (``serve.emit_to_wire_s``)."""
        while True:
            item = self._out.get()
            if item is _END:
                # an engine failure must not masquerade as a clean,
                # short completion to streaming consumers
                if self.error is not None:
                    raise RuntimeError(
                        f"serving engine failed while request {self.id} "
                        f"was in flight: {self.error!r}") from self.error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request finishes; returns the emitted tokens
        (CANCELLED requests return whatever was emitted before).
        Raises if the engine failed while this request was in flight."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.id} not done within {timeout}s")
        if self.error is not None:
            raise RuntimeError(
                f"serving engine failed while request {self.id} was in "
                f"flight: {self.error!r}") from self.error
        return np.asarray(self.tokens, np.int32)

    @property
    def done(self) -> bool:
        return self._done.is_set()


def _prefill_forward(mdl: Transformer, tokens, caches, true_len):
    """Padded-prompt prefill returning the logits at ``true_len - 1``.

    Structurally identical to ``Transformer.decode(..., last_only=True)``
    — embed, blocks at static ``pos=0``, slice ONE position, ``ln_f``,
    head — except the slice lands on the true last prompt token instead
    of the literal last row, so right-padding never reaches the LM head.
    Pad K/V beyond ``true_len`` does enter the cache, but decode's
    causal mask admits position ``p`` only once the request's own write
    cursor passes it — by which point the pad row has been overwritten
    by a real token's K/V (see docs/serving.md).
    """
    cfg = mdl.cfg
    x = mdl.embed(tokens)
    if cfg.pos_emb == "learned":
        x = x + mdl.pos(jnp.arange(tokens.shape[1])[None, :])
    new_caches = []
    for block, c in zip(mdl.blocks, caches):
        x, nc = block(x, cache=c, pos=0)
        new_caches.append(nc)
    x = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    return mdl.logits(mdl.ln_f(x)), tuple(new_caches)


def _next_bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power-of-two >= n, floored at lo, clamped to hi."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def _resume_key_chain(seed: int, k: int) -> np.ndarray:
    """Carried sampling key after ``k`` emitted tokens: ``generate()``
    (and ``_select_token``) split once per emitted token and carry
    ``split(key)[0]``, so the key state is a pure function of ``(seed,
    k)`` — which is what makes a dead replica's key state recoverable
    by any other engine (serving/router.py failover; docs/serving.md
    "Router tier")."""
    key = jax.random.PRNGKey(seed)
    for _ in range(k):
        key = jax.random.split(key)[0]
    return np.asarray(key)


class ServingEngine:
    """Continuous-batching serving over a ``SlotPool``.

    Sampling parameters (``temperature``/``top_k``/``top_p``) are fixed
    per engine — they are *static* arguments of the compiled step
    functions, which is what makes steady-state serving retrace-free.
    Per-request variation rides the ``seed`` (and greedy engines ignore
    it).  ``eos_id`` stops a request early; every request also carries
    its own ``max_new_tokens`` budget.

    Drive it either by calling :meth:`step` yourself (tests, fully
    deterministic single-threaded use) or via :meth:`start`'s background
    tick thread (the frontend's mode).
    """

    def __init__(self, model: Transformer, variables, *,
                 n_slots: int = 8, max_seq: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 kv_quant: bool = False, cache_layout: str = "grouped",
                 max_queue: int = 64,
                 prefill_credits: Optional[int] = None,
                 min_prefill_bucket: int = 8,
                 chunk: int = 0,
                 prefix_cache=False,
                 prefix_block: int = 16,
                 prefix_bytes: int = 256 << 20,
                 paged: bool = False,
                 block: int = 16,
                 kv_mb: int = 0,
                 kv_blocks: Optional[int] = None,
                 kv_dtype: str = "",
                 paged_kernel: str = "auto",
                 tp: int = 0,
                 spec_k: int = 0,
                 spec_ngram: int = 3,
                 metrics: Optional[ServeMetrics] = None):
        self.model = model
        self.variables = variables
        cfg = model.cfg
        self.max_seq = max_seq if max_seq is not None else cfg.max_seq_len
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.greedy = temperature == 0
        self.min_prefill_bucket = max(1, min_prefill_bucket)
        # chunked prefill: normalize the chunk size onto the prefill
        # bucket grid (power-of-two multiple of min_prefill_bucket) so
        # every mid chunk hits one compiled program; 0 = whole-prompt
        # prefill (the PR 2 path, bit-identical)
        self.chunk = (_next_bucket(chunk, self.min_prefill_bucket,
                                   self.max_seq) if chunk and chunk > 0
                      else 0)
        # paged KV cache (serving/blocks.py): block-granular slot
        # memory with zero-copy prefix sharing.  Every paged prefill
        # runs through the position-offset chunk path (whole prompt as
        # one chunk when chunk == 0) so ONE write discipline — gather,
        # write the span, scatter the touched blocks back — covers all
        # prefill, and the traced-position constraints below apply.
        self.paged = bool(paged)
        # int8 paged pool (kv_dtype="int8", BYTEPS_SERVE_KV_DTYPE):
        # blocks store s8 values + per-(position, head) f32 scale rows,
        # quantized AT WRITE time on every path (fused scatter, chunk
        # prefill, gather fallback) — every read at a traced position
        # sees the same quantized bytes, so preempt/resume re-prefill
        # and the disagg fallback reproduce identical int8 blocks.
        # This is exactly the discipline the legacy dense kv_quant knob
        # LACKS (its static-pos=0 whole-prompt prefill attends
        # pre-quantization values), hence the two are mutually
        # exclusive rather than composable.
        if kv_dtype not in ("", "int8"):
            raise ValueError(
                f"kv_dtype must be '' or 'int8', got {kv_dtype!r}")
        if kv_dtype and kv_quant:
            raise ValueError(
                "kv_quant and kv_dtype are mutually exclusive: kv_quant "
                "quantizes the DENSE cache (whole-prompt prefill "
                "attends pre-quantization values — incompatible with "
                "paging/chunking/resume), kv_dtype quantizes the PAGED "
                "block pool with write-time determinism.  Pick one: "
                "kv_quant=True for dense engines, kv_dtype='int8' for "
                "paged engines.")
        if kv_dtype and not self.paged:
            raise ValueError(
                "kv_dtype='int8' quantizes the paged block pool and "
                "requires paged=True; dense engines quantize with "
                "kv_quant=True instead")
        self.kv_dtype = kv_dtype
        # fused paged-attention kernel (ops/paged_attention.py): decode
        # and spec-verify read allocated, position-covered blocks IN
        # PLACE through the block table instead of gathering a dense
        # row per slot per tick — the cache-stream copy the gather
        # path pays is gone.  "auto" = on for paged engines on TPU
        # (where the Mosaic kernel is compiled; the CPU fallback would
        # run interpret-mode Pallas per tick and crawl), "on" forces
        # it (CPU CI runs it in interpret mode for parity tests),
        # "off" keeps the XLA gather.  Prefill chunks always ride the
        # gather path — they run once per chunk, not once per tick.
        pk = paged_kernel
        if isinstance(pk, bool):
            pk = "on" if pk else "off"
        if pk not in ("auto", "on", "off"):
            raise ValueError(
                f"paged_kernel must be 'auto'|'on'|'off', got "
                f"{paged_kernel!r}")
        if self.paged and pk == "auto" and jax.default_backend() == "tpu":
            # VMEM gate: the widest verify program's f32 accumulator
            # ([ (spec_k+1)*H pad 16, KV*D ]) plus the double-buffered
            # block pair must fit; an oversized config keeps the
            # pos-capped gather instead of failing the Mosaic compile
            # at the first decode tick ("on" forces past the gate)
            from ..ops.paged_attention import paged_attention_usable

            tq_max = (spec_k if spec_k and spec_k > 0 else 0) + 1
            if not paged_attention_usable(
                    (n_slots, tq_max, cfg.num_heads, cfg.d_head), block,
                    cfg.kv_heads * cfg.d_head):
                bps_log.warning(
                    "serving engine: paged_kernel='auto' fails the fused "
                    "kernel's VMEM estimate (tq=%d, heads=%d, block=%d, "
                    "kv_heads*d_head=%d) — serving through the XLA "
                    "gather instead", tq_max, cfg.num_heads, block,
                    cfg.kv_heads * cfg.d_head)
                pk = "off"
        self.paged_kernel = self.paged and (
            pk == "on"
            or (pk == "auto" and jax.default_backend() == "tpu"))
        if self.paged and not self.paged_kernel and cache_layout == "flat":
            raise ValueError(
                "cache_layout='flat' on a paged engine requires the "
                "fused paged-attention kernel (paged_kernel='on'): the "
                "gather fallback would route flat rows through the "
                "dense decode kernel under vmap")
        # chunk (and prefix-resumed, and every paged) prefill attends
        # at a TRACED position, which under kv_quant reads the
        # already-quantized int8 K/V — whole-prompt prefill at static
        # pos=0 reads the pre-quantization values instead
        # (models/transformer.py dense fallback), so the combination
        # would silently diverge from generate() and from a chunk=0
        # engine.  Refuse loudly.
        if kv_quant and (self.chunk or prefix_cache or self.paged):
            raise ValueError(
                "chunked prefill / prefix cache / paged KV cache "
                "require a dense KV cache: a chunk at a traced "
                "position attends int8 K/V where whole-prompt prefill "
                "attends the pre-quantization values, breaking the "
                "bit-exact parity contract.  Run kv_quant engines with "
                "chunk=0, prefix_cache=False, paged=False — or, to "
                "quantize a PAGED engine, use kv_dtype='int8' "
                "(BYTEPS_SERVE_KV_DTYPE), whose quantize-at-write "
                "discipline is consistent at traced positions and "
                "composes with chunking, prefix reuse, and resume.")
        # same hazard class for flash prefill: whole-prompt prefill at
        # static pos=0 can take the Pallas flash kernel (attn_impl=
        # "flash" + the gcd bucket gate), while a chunk at a traced
        # position always takes dense cached attention — the two differ
        # in accumulation order, so greedy tokens could silently
        # diverge from generate().  max_seq < 128 can never produce a
        # flash-eligible bucket (the gate needs gcd(bucket, 1024) >=
        # 128 and buckets never exceed max_seq), so tiny configs pass.
        if (self.chunk or prefix_cache or self.paged) and (
                cfg.attn_impl == "flash" and not cfg.has_sp
                and self.max_seq >= 128):
            raise ValueError(
                "chunked prefill / prefix cache / paged KV cache "
                "require the dense prefill path: this config's "
                "whole-prompt prefill can take the flash kernel while "
                "chunks always take dense cached attention, and the "
                "two differ in accumulation order — token streams "
                "could silently diverge from generate().  Serve "
                "attn_impl='flash' models with chunk=0, "
                "prefix_cache=False, paged=False.")
        # cross-replica resume (serving/router.py failover): a
        # resume-with-prefix submit re-prefills prompt + already-emitted
        # tokens and continues the parked token/key chain — bit-exact
        # only when prefill of the emitted region reproduces the K/V the
        # ORIGINAL run's decode wrote.  kv_quant breaks that (prefill
        # attends pre-quantization values where decode attended int8),
        # and a flash-eligible whole-prompt prefill differs from dense
        # decode in accumulation order — both are refused at submit.
        # (kv_dtype="int8" is deliberately NOT resume-unsafe: the paged
        # pool quantizes at write time on every path, so a resume's
        # chunked re-prefill reproduces the original run's int8 blocks
        # byte-for-byte — the determinism the dense knob lacks.)
        if kv_quant:
            self._resume_unsafe = (
                "kv_quant: resume prefill attends pre-quantization K/V "
                "where the original decode attended the quantized values")
        elif (cfg.attn_impl == "flash" and not cfg.has_sp
                and self.max_seq >= 128):
            self._resume_unsafe = (
                "attn_impl='flash': resume prefill can take the flash "
                "kernel while the original run's emitted-token K/V came "
                "from dense decode — accumulation orders differ")
        else:
            self._resume_unsafe = ""
        # speculative decoding (serving/spec.py): depth rounds DOWN to
        # a power of two so a tick capped by row space can halve its
        # bucket and stay on the compiled-bucket grid ({1, 2, 4, ...}),
        # the same discipline as prefill buckets.
        if spec_k and spec_k > 0:
            if kv_quant:
                # conservative twin of the chunk/prefix/paged refusal:
                # spec's whole value is multi-token parity guarantees,
                # and the int8 cache's flat-layout decode kernel (tq=1)
                # vs the dense tq>1 verify is exactly the accumulation-
                # order divergence that breaks them
                raise ValueError(
                    "speculative decoding requires a dense fp KV cache "
                    "(kv_quant=False): the verify pass must be bit-"
                    "exact against single-token decode, which the "
                    "quantized cache paths do not guarantee across "
                    "query widths")
            if cache_layout != "grouped":
                raise ValueError(
                    f"speculative decoding requires cache_layout="
                    f"'grouped' (got {cache_layout!r}): a flat-layout "
                    f"pool decodes tq=1 through the fused Pallas "
                    f"kernel while the tq>1 verify always runs dense "
                    f"cached attention — the two differ in "
                    f"accumulation order, so accepted tokens could "
                    f"silently diverge from the non-speculative stream")
            if (kv_dtype and not self.paged_kernel
                    and jax.default_backend() == "tpu"):
                # the int8 pool forces flat storage, and on TPU the
                # gather fallback's tq=1 tick takes the fused decode
                # kernel while the tq>1 verify runs dense q8 attention
                # — the same accumulation-order divergence the
                # cache_layout refusal above guards.  The fused paged
                # kernel serves BOTH widths identically, so spec +
                # int8 is fine with paged_kernel on (and off-TPU both
                # widths run dense q8).
                raise ValueError(
                    "speculative decoding on an int8 paged pool "
                    "(kv_dtype='int8') requires the fused paged kernel "
                    "on TPU (paged_kernel='on'/'auto'): the gather "
                    "fallback decodes tq=1 through the fused dense "
                    "kernel while the tq>1 verify runs dense q8 "
                    "attention, which differ in accumulation order")
            k = 1
            while k * 2 <= spec_k:
                k *= 2
            # ngram floors at 2 (the documented contract): single-token
            # matches fire on any vocabulary reuse, and every false
            # proposal costs a widened verify forward
            self.spec = NgramProposer(k, max(2, spec_ngram))
        else:
            self.spec = None
        # tensor-parallel serving: tp > 1 shards the paged block pool
        # into per-KV-head-slice sub-pools ([tp, n_blocks, block,
        # (KV/tp)*D] — serving/blocks.py).  0 defers to the BYTEPS_TP
        # config knob; 1 serves unsharded.  Attention is exactly
        # partitioned by KV head (docs/parallel.md), so the sharded
        # engine's token stream is identical to the unsharded one.
        if not tp:
            from ..common.config import get_config as _gc
            tp = max(1, int(getattr(_gc(), "serve_tp", 1)))
        if tp > 1:
            if not self.paged:
                raise ValueError(
                    f"tp ({tp}) > 1 requires paged=True: tensor-"
                    f"parallel serving shards the paged block pool per "
                    f"KV-head slice; dense slot caches shard through "
                    f"init_cache's mesh path instead")
            if cfg.num_heads % tp:
                raise ValueError(
                    f"tp ({tp}) must divide num_heads "
                    f"({cfg.num_heads}) so query head slices align "
                    f"with KV head slices")
        self.tp = tp
        if self.paged:
            self.pool = PagedSlotPool(
                cfg, n_slots, self.max_seq, block=block,
                n_blocks=kv_blocks, kv_bytes=kv_mb << 20,
                kv_quant=kv_quant, kv_dtype=kv_dtype, tp=tp,
                layout=("flat" if (self.paged_kernel or tp > 1)
                        else cache_layout))
        else:
            self.pool = SlotPool(cfg, n_slots, self.max_seq,
                                 kv_quant=kv_quant, layout=cache_layout)
        # what actually serves decode, read back from the pool that was
        # built (not from the request): reported once here and on every
        # STATS reply, so a benchmark never has to infer the path from
        # the knobs it set
        self.attention_path = (
            ("paged_fused" if self.paged_kernel else "paged_gather")
            if self.paged else
            ("dense_flat_kernel" if self.pool.caches[0]["k"].ndim == 3
             else "dense_xla"))
        dev0 = jax.devices()[0]
        self.device = {"platform": dev0.platform,
                       "device_kind": dev0.device_kind,
                       "count": len(jax.devices())}
        bps_log.info(
            "serving engine: attention_path=%s (paged=%s, paged_kernel="
            "%r, cache_layout=%r) on %s %r x%d", self.attention_path,
            self.paged, paged_kernel, cache_layout, dev0.platform,
            dev0.device_kind, self.device["count"])
        # prefix-reuse KV cache: True builds a private store, or pass a
        # PrefixCache to share one across engines with IDENTICAL pool
        # geometry (entries are full cache-row buffers).  Every key is
        # salted with a fingerprint of THIS engine's weights, so
        # engines serving different checkpoints through a shared store
        # occupy disjoint key spaces — one model's K/V can never be
        # copied into another model's slot.  A PAGED engine's store
        # references its own block pool (entries are block-id lists, a
        # hit is a refcount bump, not a copy — serving/prefix.py
        # PagedPrefixCache), so it is always private: block ids are
        # meaningless in any other engine's pool.
        if self.paged and prefix_cache:
            if isinstance(prefix_cache, PrefixCache):
                raise ValueError(
                    "a paged engine's prefix store references its own "
                    "KV block pool (entries are block ids, not copied "
                    "buffers) and cannot be shared across engines; "
                    "pass prefix_cache=True")
            self.prefix = PagedPrefixCache(
                self.pool.alloc, block=self.pool.block,
                block_bytes=self.pool.block_bytes,
                max_bytes=prefix_bytes,
                on_evict=lambda n: self.metrics.bump(
                    sm.BLOCK_EVICTIONS, n))
        elif isinstance(prefix_cache, PagedPrefixCache):
            # the mirror refusal: a dense engine fed a paged store
            # would call insert() (refused) or copy entry.buffer — a
            # tuple of block ids, not a row pytree — into its cache
            raise ValueError(
                "a PagedPrefixCache references a paged engine's block "
                "pool and cannot back a dense engine; pass "
                "prefix_cache=True (or a plain PrefixCache)")
        elif isinstance(prefix_cache, PrefixCache):
            self.prefix = prefix_cache
        elif prefix_cache:
            self.prefix = PrefixCache(block=prefix_block,
                                      max_bytes=prefix_bytes)
        else:
            self.prefix = None
        # every prefix entry is one full cache row, so its size is fixed
        # by the pool geometry; when even one can never fit the byte
        # budget, _maybe_insert_prefix skips the device-side extract
        # entirely instead of paying it per request just for insert()
        # to refuse
        self._prefix_row_bytes = (sum(
            leaf.nbytes // n_slots
            for leaf in jax.tree_util.tree_leaves(self.pool.caches))
            if self.prefix is not None and not self.paged else 0)
        # the store salt commits to the weights AND the per-slot cache
        # row geometry (shape past the slot dim, dtype): an engine with
        # a different max_seq / layout / kv_quant sharing the store
        # sees a harmless miss instead of copying an incompatible
        # buffer and crashing the tick
        self._prefix_salt = b""
        self._weights_fp: Optional[str] = None
        if self.prefix is not None:
            geom = hashlib.blake2b(digest_size=16)
            for leaf in jax.tree_util.tree_leaves(self.pool.caches):
                geom.update(f"{leaf.shape[1:]}{leaf.dtype}".encode())
            wfp = weights_fingerprint(variables)
            self._weights_fp = wfp.hex()
            self._prefix_salt = wfp + geom.digest()
        # credit budget in padded prefill tokens per tick; default = one
        # max-length prefill (or, with chunking on, one chunk — the
        # whole point is bounding per-tick prefill), i.e. "a tick admits
        # at most one worst-case prompt's worth of prefill work" —
        # decode latency stays bounded while short prompts can still
        # batch several admissions per tick.  With chunking the budget
        # is floored at the chunk size so a continuation chunk can
        # always make progress on a fresh tick.
        budget = (prefill_credits if prefill_credits and prefill_credits > 0
                  else (self.chunk or self.max_seq))
        if self.chunk:
            budget = max(budget, self.chunk)
        self.scheduler = ServeScheduler(
            max_queue=max_queue, credit_budget=budget)
        self.metrics = metrics if metrics is not None else get_serve_metrics()
        # per-request trace ids (docs/observability.md) — resolved once;
        # submit pays one attribute check when tracing is off
        from ..observability.trace import rpc_tracing_enabled

        self._trace_rpc = rpc_tracing_enabled()

        self._lock = threading.RLock()
        # router-epoch fence (serving/router.py "Router HA"): the
        # highest epoch any dispatch has carried.  Its own small lock —
        # the fence check runs on frontend handler threads before
        # submit and must never contend with the tick loop
        self._epoch_lock = threading.Lock()
        self._epoch_hw = 0
        self._req_seq = 0
        self._slot_req: List[Optional[Request]] = [None] * n_slots
        # slots mid-chunked-prefill: assigned (cache rows being written)
        # but excluded from the decode pass until the final chunk
        # samples their first token
        self._prefilling: Dict[int, Request] = {}
        self._tick_chunk_debt = 0   # take_credits() debits to return
        self._tick_prefill = 0      # padded prefill tokens this tick
        # this tick's host seconds by phase (_phase), flushed onto
        # serve.tick_seconds at the tick's end
        self._phase_s = dict.fromkeys(tracing.TICK_PHASES, 0.0)
        self._tok = jnp.zeros((n_slots,), jnp.int32)
        self._keys = jnp.zeros((n_slots, 2), jnp.uint32)
        self._outstanding = 0
        self._drain_cv = threading.Condition(self._lock)
        self._wake = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop_flag = False
        self._engine_error: Optional[BaseException] = None
        # trace-time counters: the Python body of a jitted fn runs only
        # when jax (re)traces, so these count compilations portably —
        # steady-state stability is asserted on them
        self.decode_traces = 0
        self.prefill_traces = 0
        self.chunk_traces = 0
        self.prefix_copy_traces = 0
        self.prefix_extract_traces = 0
        self.block_cow_traces = 0
        self.verify_traces = 0
        # donate the cache pool into each step: the pool is replaced by
        # the step's output, and without donation XLA would copy every
        # layer's full [N, S, ...] cache (or [n_blocks, block, ...]
        # block pool) per tick just to write one row.  Dense engines
        # compile ONE decode program; paged engines compile one per
        # gather high-water bucket (the chunk-bucket discipline —
        # ``compile_counts()["decode_buckets"]`` pins it), or exactly
        # one on the fused-kernel path.
        self._decode_step = (
            None if self.paged
            else jax.jit(self._make_decode_fn(), donate_argnums=(1,)))
        self._paged_decode_fns: Dict[object, object] = {}
        self._prefill_fns: Dict[int, object] = {}
        self._chunk_fns: Dict[int, object] = {}
        # verify programs, keyed by query width tq = depth + 1 — one
        # compiled program per speculation-depth bucket (pinned by
        # compile_counts, the chunk-bucket discipline)
        self._verify_fns: Dict[int, object] = {}
        self._copy_fn = None
        self._extract_fn = None
        self._cow_fn = None
        # disaggregated serving (serving/disagg): finished-but-unshipped
        # parked KV — req.id -> {"ids": [block ids, incref'd], "pos": T}
        # — plus the lazily-jitted single-block scatter the decode-side
        # stager writes received blocks with.  Bounded by
        # BYTEPS_DISAGG_PARKED_CAP (oldest evicted + released).
        from collections import OrderedDict

        from ..common.config import get_config

        self._kv_write_fn = None
        self._parked_kv: "OrderedDict[int, dict]" = OrderedDict()
        self._parked_cap = max(1, get_config().disagg_parked_cap)

    # ---------------------------------------------------- jitted programs
    #
    # The decode, prefill, and chunk programs all end with the same
    # "pick a token, write the slot's row back" tail; it lives in ONE
    # place (_select_token/_slot_row/_write_row) so a fix to the
    # sampling key chain or the write-back discipline cannot silently
    # diverge between paths — the bit-exact parity anchor depends on
    # every path agreeing.

    def _select_token(self, logits_last, key):
        """Greedy/sampled token pick from ``[1, vocab]`` last-position
        logits, returning ``(token, carried_key)``.  Sampled mode
        replays generate()'s exact per-step key chain: carry split[0],
        sample with split[1]; greedy carries the key untouched."""
        with jax.named_scope(SCOPE_SERVE_SELECT):
            if self.greedy:
                return (jnp.argmax(logits_last[0], axis=-1)
                        .astype(jnp.int32), key)
            nk, sub = jax.random.split(key)
            tok = sample_logits(logits_last, sub, self.temperature,
                                self.top_k, self.top_p)[0].astype(jnp.int32)
            return tok, nk

    def _forward(self, variables, *args, method, **kw):
        """The model's forward of every serve program, under the scope
        the train step's carries (Flax's module paths nest inside)."""
        with jax.named_scope(SCOPE_MODEL):
            return self.model.apply(variables, *args, method=method, **kw)

    @staticmethod
    def _slot_row(caches, slot):
        """Slice one slot's ``[1, ...]`` cache row out of the pool."""
        return jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=0),
            caches)

    @staticmethod
    def _write_row(caches, new_row, slot):
        """Write a ``[1, ...]`` row back into the (donated) pool."""
        return jax.tree_util.tree_map(
            lambda c, r: jax.lax.dynamic_update_slice_in_dim(
                c, r, slot, axis=0),
            caches, new_row)

    def _make_decode_fn(self):
        greedy = self.greedy
        pad_id = self.pad_id
        select = self._select_token

        def one(variables, row, tok, pos, key):
            rowb = jax.tree_util.tree_map(lambda c: c[None], row)
            logits, new = self._forward(
                variables, tok[None, None], rowb, pos,
                method=Transformer.decode)
            nxt, nk = select(logits[:, -1], key)
            return jax.tree_util.tree_map(lambda c: c[0], new), nxt, nk

        def decode_fn(variables, caches, tok, pos, active, keys):
            self.decode_traces += 1  # trace-time only
            caches, nxt, keys2 = jax.vmap(
                one, in_axes=(None, 0, 0, 0, 0))(
                    variables, caches, tok, pos, keys)
            nxt = jnp.where(active, nxt, pad_id)
            if not greedy:
                keys2 = jnp.where(active[:, None], keys2, keys)
            else:
                keys2 = keys
            return caches, nxt, keys2

        return decode_fn

    def _paged_decode_fn(self, hw: Optional[int]):
        """Jitted paged decode step, two flavors:

        * ``hw`` an int — the XLA **gather** fallback at that block
          high-water bucket: per slot, gather ``table[:hw]``'s blocks
          into a ``hw * block``-row dense view (NOT the full
          ``max_seq`` width — the pos-capped gather stops streaming
          null-block / unwritten padding), run the SAME per-row decode
          (one attention implementation — ``Transformer.decode_paged``
          delegates to ``decode``), then scatter every slot's fresh
          K/V into the pool at its ``(write block, offset)`` target.
          One compiled program per bucket, the chunk-bucket
          discipline.
        * ``hw is None`` — the **fused kernel** path: one un-vmapped
          ``decode_paged_fused`` call serves the whole pool; fresh K/V
          scatters into the pool inside the forward and the Pallas
          kernel reads blocks in place through the table — no gather
          exists.

        Masked slots (free or PREFILLING) scatter into the null block
        either way, so their garbage write can never touch a shared
        prefix block or a mid-prefill row — simpler than the dense
        path's aim-at-the-cursor discipline."""
        key = "kernel" if hw is None else hw
        fn = self._paged_decode_fns.get(key)
        if fn is not None:
            return fn
        greedy = self.greedy
        pad_id = self.pad_id
        select = self._select_token
        tp = self.tp

        if hw is None:
            def decode_fn(variables, pcaches, tok, pos, active, keys,
                          tables, wblk, woff):
                self.decode_traces += 1  # trace-time only
                logits, new_pc = self._forward(
                    variables, tok[:, None], pcaches, tables, pos,
                    wblk, woff, True,
                    method=Transformer.decode_paged_fused)
                nxt, keys2 = jax.vmap(
                    lambda lg, k: select(lg[None], k))(
                        logits[:, -1], keys)
                nxt = jnp.where(active, nxt, pad_id)
                if not greedy:
                    keys2 = jnp.where(active[:, None], keys2, keys)
                else:
                    keys2 = keys
                return new_pc, nxt, keys2
        else:
            def one(variables, pcaches, table, tok, pos, key):
                logits, new_rows = self._forward(
                    variables, tok[None, None], pcaches, table, pos,
                    hw_blocks=hw, tp=tp,
                    method=Transformer.decode_paged)
                nxt, nk = select(logits[:, -1], key)
                # the one written position, sliced back out of the
                # gathered row for the pool scatter below
                fresh = tuple(
                    {n: jax.lax.dynamic_slice_in_dim(r[n], pos, 1,
                                                     axis=1)[0, 0]
                     for n in r} for r in new_rows)
                return fresh, nxt, nk

            def decode_fn(variables, pcaches, tok, pos, active, keys,
                          tables, wblk, woff):
                self.decode_traces += 1  # trace-time only
                # the hw cap is applied in ONE place: decode_paged's
                # hw_blocks slices each slot's table inside the vmap
                fresh, nxt, keys2 = jax.vmap(
                    one, in_axes=(None, None, 0, 0, 0, 0))(
                        variables, pcaches, tables, tok, pos, keys)
                nxt = jnp.where(active, nxt, pad_id)
                if not greedy:
                    keys2 = jnp.where(active[:, None], keys2, keys)
                else:
                    keys2 = keys
                if tp == 1:
                    new_pc = tuple(
                        {n: pc[n].at[wblk, woff].set(fr[n]) for n in pc}
                        for pc, fr in zip(pcaches, fresh))
                else:
                    # fresh leaves are head-major ([N, KV, D] values /
                    # [N, KV] scales): splitting the head axis into tp
                    # contiguous slices is exactly the per-shard
                    # partition of the unsharded row's bytes
                    new_pc = tuple(
                        {n: pc[n].at[:, wblk, woff].set(
                            fr[n].reshape(fr[n].shape[0], tp, -1)
                            .transpose(1, 0, 2)) for n in pc}
                        for pc, fr in zip(pcaches, fresh))
                return new_pc, nxt, keys2

        fn = jax.jit(decode_fn, donate_argnums=(1,))
        self._paged_decode_fns[key] = fn
        return fn

    def _verify_accept(self, props, tmat, kchain, prop_len, active,
                       tok, keys, budget):
        """The in-program accept/truncate tail shared by the dense and
        paged verify steps: given the candidate tokens ``tmat [N, tq]``
        (the model's pick at every position) and the proposals that fed
        positions ``1..d`` (``props [N, d]``), compute per slot the
        accepted count (1 + the leading run of proposals that equal the
        model's own tokens — position 0 IS the plain decode step, so a
        slot can never emit less than the non-speculative engine), then
        truncate at the request's remaining ``budget`` and at the first
        EOS, and pick the carried next-input token and sampling-key
        state matching EXACTLY the tokens that will be emitted —
        rejected positions' key splits are discarded with them, so the
        per-request chain stays generate()'s (seeded parity by replay).
        Running on device keeps ``_tok``/``_keys`` resident: the host
        reads back only the small (tmat, counts) arrays to emit."""
        with jax.named_scope(SCOPE_SERVE_ACCEPT):
            d = tmat.shape[1] - 1
            ok = ((props == tmat[:, :-1])
                  & (jnp.arange(d)[None, :] < prop_len[:, None]))
            lead = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            m = jnp.minimum(1 + lead, jnp.maximum(budget, 1))
            if self.eos_id is not None:
                is_eos = tmat == self.eos_id
                m = jnp.where(jnp.any(is_eos, axis=1),
                              jnp.minimum(m, jnp.argmax(is_eos, axis=1) + 1),
                              m)
            idx = (m - 1)[:, None]
            nxt = jnp.take_along_axis(tmat, idx, axis=1)[:, 0]
            nxt = jnp.where(active, nxt, tok)
            if self.greedy:
                nkeys = keys
            else:
                nkeys = jnp.take_along_axis(kchain, idx[:, :, None],
                                            axis=1)[:, 0]
                nkeys = jnp.where(active[:, None], nkeys, keys)
            m = jnp.where(active, m, 0)
            accepted = jnp.where(active, lead, 0)
            return nxt, nkeys, tmat, m, accepted

    def _verify_fn(self, tq: int):
        """Jitted speculative verify for one depth bucket (``tq`` =
        depth + 1 query positions): every slot runs the SAME per-row
        multi-token decode (``Transformer.verify_tokens`` — one
        attention implementation), vmapped over the pool exactly like
        the one-token step, then the in-program accept/truncate tail
        (``_verify_accept``) picks each slot's emitted prefix and
        carried token/key state.  Returns ``(caches, tok, keys,
        tmat, m_emit, accepted)`` — the host emits ``tmat[s, :m_emit]``
        per active slot and advances cursors; everything else stays on
        device."""
        fn = self._verify_fns.get(tq)
        if fn is not None:
            return fn
        select = self._select_token

        def one(variables, row, toks, pos, key):
            rowb = jax.tree_util.tree_map(lambda c: c[None], row)
            logits, new = self._forward(
                variables, toks[None, :], rowb, pos,
                method=Transformer.verify_tokens)
            ts, ks, k = [], [], key
            for i in range(tq):
                t_i, k = select(logits[:, i], k)
                ts.append(t_i)
                ks.append(k)
            return (jax.tree_util.tree_map(lambda c: c[0], new),
                    jnp.stack(ts), jnp.stack(ks))

        def verify_fn(variables, caches, props, prop_len, pos, active,
                      tok, keys, budget):
            self.verify_traces += 1  # trace-time only
            toks = jnp.concatenate([tok[:, None], props], axis=1)
            caches, tmat, kchain = jax.vmap(
                one, in_axes=(None, 0, 0, 0, 0))(
                    variables, caches, toks, pos, keys)
            return (caches,) + self._verify_accept(
                props, tmat, kchain, prop_len, active, tok, keys,
                budget)

        fn = jax.jit(verify_fn, donate_argnums=(1,))
        self._verify_fns[tq] = fn
        return fn

    def _paged_verify_fn(self, tq: int, hw: Optional[int]):
        """Paged twin of ``_verify_fn``, two flavors like the decode
        step.  Gather (``hw`` an int): gather ``table[:hw]``'s blocks
        per slot (the pos-capped high-water bucket — never the full
        null-padded width), verify the ``tq``-position span, then
        scatter the span's fresh K/V back **per position** to the
        host-computed ``(block, offset)`` targets — touched blocks
        only, never a whole-block rewrite, so a shared prefix block can
        never be written (ungranted or masked positions aim at the null
        block, and ``prop_len`` is pre-capped at the granted coverage
        so acceptance can never advance a cursor onto an unwritten
        position).  Fused kernel (``hw is None``): the same program
        shape as the kernel decode step, one query width wider —
        plain decode and verify ride the SAME kernel, which is what
        keeps spec-on token-identical to spec-off on this path."""
        key = ("kernel", tq) if hw is None else (tq, hw)
        fn = self._verify_fns.get(key)
        if fn is not None:
            return fn
        select = self._select_token
        tp = self.tp

        def chain(lg, key):
            """Per-slot select chain over ``lg [tq, vocab]``."""
            ts, ks, k = [], [], key
            for i in range(tq):
                t_i, k = select(lg[i][None], k)
                ts.append(t_i)
                ks.append(k)
            return jnp.stack(ts), jnp.stack(ks)

        if hw is None:
            def verify_fn(variables, pcaches, props, prop_len, pos,
                          active, tok, keys, budget, tables, wblk,
                          woff):
                self.verify_traces += 1  # trace-time only
                toks = jnp.concatenate([tok[:, None], props], axis=1)
                logits, new_pc = self._forward(
                    variables, toks, pcaches, tables, pos, wblk, woff,
                    method=Transformer.verify_tokens_paged_fused)
                tmat, kchain = jax.vmap(chain)(logits, keys)
                return (new_pc,) + self._verify_accept(
                    props, tmat, kchain, prop_len, active, tok, keys,
                    budget)
        else:
            def one(variables, pcaches, table, toks, pos, key):
                logits, new_rows = self._forward(
                    variables, toks[None, :], pcaches, table, pos,
                    hw_blocks=hw, tp=tp,
                    method=Transformer.verify_tokens_paged)
                ts, ks = chain(logits[0], key)
                # the tq written positions, sliced back out of the
                # gathered row for the per-position pool scatter below
                fresh = tuple(
                    {n: jax.lax.dynamic_slice_in_dim(r[n], pos, tq,
                                                     axis=1)[0]
                     for n in r} for r in new_rows)
                return fresh, ts, ks

            def verify_fn(variables, pcaches, props, prop_len, pos,
                          active, tok, keys, budget, tables, wblk,
                          woff):
                self.verify_traces += 1  # trace-time only
                toks = jnp.concatenate([tok[:, None], props], axis=1)
                fresh, tmat, kchain = jax.vmap(
                    one, in_axes=(None, None, 0, 0, 0, 0))(
                        variables, pcaches, tables, toks, pos, keys)
                if tp == 1:
                    new_pc = tuple(
                        {n: pc[n].at[wblk, woff].set(fr[n]) for n in pc}
                        for pc, fr in zip(pcaches, fresh))
                else:
                    # per-position head-major split, as in the decode
                    # scatter, one query-width axis wider
                    new_pc = tuple(
                        {n: pc[n].at[:, wblk, woff].set(
                            fr[n].reshape(fr[n].shape[0], tq, tp, -1)
                            .transpose(2, 0, 1, 3)) for n in pc}
                        for pc, fr in zip(pcaches, fresh))
                return (new_pc,) + self._verify_accept(
                    props, tmat, kchain, prop_len, active, tok, keys,
                    budget)

        fn = jax.jit(verify_fn, donate_argnums=(1,))
        self._verify_fns[key] = fn
        return fn

    def _paged_chunk_fn(self, bucket: int):
        """Paged twin of ``_chunk_fn``: gather the slot's rows through
        its block table, run the position-offset chunk, then scatter
        the written span's blocks back into the pool.  The span covers
        at most ``1 + ceil((bucket - 1) / block)`` consecutive logical
        blocks (static count); the scatter writes exactly those —
        out-of-range or untouched trailing entries write their own
        unchanged bytes (or land on the null block), which is a no-op
        by value, so shared blocks outside the span are never
        altered."""
        fn = self._chunk_fns.get(bucket)
        if fn is not None:
            return fn
        select = self._select_token
        blk = self.pool.block
        mb = self.pool.max_blocks
        null = self.pool.null_block
        nb_touch = (bucket - 1) // blk + 2
        tp = self.tp

        def chunk_fn(variables, pcaches, tokens, table, start, last_idx,
                     key):
            self.chunk_traces += 1  # trace-time only
            logits, new_rows = self._forward(
                variables, tokens, pcaches, table, start, last_idx,
                tp=tp, method=Transformer.prefill_chunk_paged)
            tok0, nk = select(logits[:, -1], key)
            first = start // blk
            new_pc = []
            for pc, nr in zip(pcaches, new_rows):
                out = {}
                for n, c in pc.items():
                    for i in range(nb_touch):
                        idx = first + i
                        safe = jnp.minimum(idx, mb - 1)
                        src = jax.lax.dynamic_slice_in_dim(
                            nr[n], safe * blk, blk, axis=1)[0]
                        bid = jnp.where(idx < mb, table[safe], null)
                        if tp == 1:
                            c = c.at[bid].set(src)
                        else:
                            c = c.at[:, bid].set(
                                src.reshape(blk, tp, -1)
                                .transpose(1, 0, 2))
                    out[n] = c
                new_pc.append(out)
            return tuple(new_pc), tok0, nk

        fn = jax.jit(chunk_fn, donate_argnums=(1,))
        self._chunk_fns[bucket] = fn
        return fn

    def _cow_copy(self, src: int, dst: int) -> None:
        """Device-side block copy backing a copy-on-write fork
        (``PagedSlotPool.make_writable``): one compiled program for
        every (src, dst) pair."""
        if self._cow_fn is None:
            if self.tp == 1:
                def cow(pcaches, src, dst):
                    self.block_cow_traces += 1  # trace-time only
                    return tuple(
                        {n: c[n].at[dst].set(c[n][src]) for n in c}
                        for c in pcaches)
            else:
                def cow(pcaches, src, dst):
                    self.block_cow_traces += 1  # trace-time only
                    # block axis is axis 1 behind the shard axis; the
                    # copy replicates the fork on every shard
                    return tuple(
                        {n: c[n].at[:, dst].set(c[n][:, src])
                         for n in c}
                        for c in pcaches)

            self._cow_fn = jax.jit(cow, donate_argnums=(0,))
        self.pool.caches = self._cow_fn(self.pool.caches,
                                        jnp.int32(src), jnp.int32(dst))

    # ------------------------------------------- disagg KV ship seam
    #
    # The prefill side of a disaggregated ship reads parked blocks out
    # of the pool (extract_kv_blocks); the decode side scatters received
    # blocks in (write_kv_block).  Both run under ``self._lock``: the
    # tick thread DONATES ``pool.caches`` into every step, so an
    # unlocked reader could hold a deleted buffer mid-copy.

    def take_parked_kv(self, req_id: int) -> Optional[dict]:
        """Claim (and remove) the parked KV entry a finished ``keep_kv``
        request left behind.  The caller owns the returned block refs
        and must ``release_kv_ids`` them when done."""
        with self._lock:
            return self._parked_kv.pop(req_id, None)

    def release_kv_ids(self, ids) -> None:
        """Drop one reference per block id (parked entries, refused
        adoptions, aborted stagings)."""
        if not ids:
            return
        with self._lock:
            for b in ids:
                self.pool.alloc.decref(int(b))

    def stage_alloc(self, n: int) -> List[int]:
        """Allocate ``n`` pool blocks for an incoming ship (decode
        side); raises ``BlocksExhaustedError`` when the pool cannot
        cover it — the sender aborts and the router re-prefills."""
        with self._lock:
            return self.pool.alloc.alloc(n)

    def extract_kv_blocks(self, ids) -> List[Dict[str, np.ndarray]]:
        """Host copies of the pool rows backing ``ids``: one dict per
        layer, each value ``[len(ids), ...block row]`` — the ship
        payload.  Row-major bytes are layout-identical between the
        grouped and flat pool layouts (same trailing element count), so
        the wire format does not encode the layout.  A tp-sharded pool
        reassembles each block's per-shard slices head-major into the
        unsharded flat row bytes, so ships are tp-count independent:
        a tp=2 prefill tier can feed an unsharded (or tp=4) decode
        tier."""
        idx = jnp.asarray(list(ids), jnp.int32)
        with self._lock:
            if self.tp == 1:
                return [{n: np.asarray(jnp.take(c[n], idx, axis=0))
                         for n in c} for c in self.pool.caches]
            out = []
            for c in self.pool.caches:
                layer = {}
                for n in c:
                    g = jnp.take(c[n], idx, axis=1)  # [tp, nb, blk, X]
                    layer[n] = np.asarray(
                        g.transpose(1, 2, 0, 3).reshape(
                            g.shape[1], g.shape[2], -1))
                out.append(layer)
            return out

    def write_kv_block(self, bid: int, layers) -> None:
        """Scatter ONE received block into the pool at physical id
        ``bid``.  ``layers`` is ``extract_kv_blocks``'s per-layer dict
        shape for a single block (leading axis dropped).  One compiled
        program total — the block id is a traced scalar."""
        if self._kv_write_fn is None:
            if self.tp == 1:
                def kv_write(pcaches, bid, blk):
                    return tuple(
                        {n: c[n].at[bid].set(blk[i][n]) for n in c}
                        for i, c in enumerate(pcaches))
            else:
                tp = self.tp

                def kv_write(pcaches, bid, blk):
                    # wire rows arrive in the unsharded head-major flat
                    # format (extract_kv_blocks); split the minor axis
                    # back into per-shard KV-head slices
                    return tuple(
                        {n: c[n].at[:, bid].set(
                            blk[i][n].reshape(
                                blk[i][n].shape[0], tp, -1)
                            .transpose(1, 0, 2)) for n in c}
                        for i, c in enumerate(pcaches))

            self._kv_write_fn = jax.jit(kv_write, donate_argnums=(0,))
        with self._lock:
            self.pool.caches = self._kv_write_fn(
                self.pool.caches, jnp.int32(bid), tuple(layers))

    def _park_kv_locked(self, req: Request) -> None:
        """Park a finished ``keep_kv`` request's blocks (incref BEFORE
        the slot free releases the table's own refs) so the frontend
        can ship them after the reply.  Cap-bounded: the oldest parked
        entry is evicted and released, never silently grown."""
        ids = list(self.pool.tables[req.slot].blocks)
        if not ids:
            return
        for b in ids:
            self.pool.alloc.incref(b)
        seq = req._seq if req._seq is not None else req.prompt
        self._parked_kv[req.id] = {"ids": ids, "pos": int(len(seq))}
        while len(self._parked_kv) > self._parked_cap:
            _, old = self._parked_kv.popitem(last=False)
            for b in old["ids"]:
                self.pool.alloc.decref(int(b))

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        select = self._select_token

        def prefill_fn(variables, caches, prompt, slot, true_len, key):
            self.prefill_traces += 1  # trace-time only
            logits, new_row = self._forward(
                variables, prompt, self._slot_row(caches, slot), true_len,
                method=_prefill_forward)
            tok0, nk = select(logits[:, -1], key)
            return self._write_row(caches, new_row, slot), tok0, nk

        fn = jax.jit(prefill_fn, donate_argnums=(1,))
        self._prefill_fns[bucket] = fn
        return fn

    def _chunk_fn(self, bucket: int):
        """Jitted position-offset chunk prefill for one bucket size:
        writes the chunk's K/V at ``[start, start + bucket)`` of the
        slot's row and returns the sampled token at chunk-local
        ``last_idx`` (meaningful only for a request's final chunk —
        mid-chunk callers discard it, and the carried key, so the
        sampling key chain still splits exactly once per request)."""
        fn = self._chunk_fns.get(bucket)
        if fn is not None:
            return fn
        select = self._select_token

        def chunk_fn(variables, caches, tokens, slot, start, last_idx, key):
            self.chunk_traces += 1  # trace-time only
            logits, new_row = self._forward(
                variables, tokens, self._slot_row(caches, slot), start,
                last_idx, method=Transformer.prefill_chunk)
            tok0, nk = select(logits[:, -1], key)
            return self._write_row(caches, new_row, slot), tok0, nk

        fn = jax.jit(chunk_fn, donate_argnums=(1,))
        self._chunk_fns[bucket] = fn
        return fn

    def _prefix_copy_fn(self):
        """Jitted device-side prefix restore: overwrite a slot's whole
        cache row with a stored full-row buffer.  Rows past the match
        length are the buffer's zero padding — safe stale content, the
        request's own prefill/decode overwrites them before the causal
        mask can admit them.  Full-row entries keep this ONE compiled
        program regardless of prefix length."""
        if self._copy_fn is None:
            def copy_fn(caches, buffer, slot):
                self.prefix_copy_traces += 1  # trace-time only
                return self._write_row(caches, buffer, slot)

            self._copy_fn = jax.jit(copy_fn, donate_argnums=(0,))
        return self._copy_fn

    def _prefix_extract_fn(self):
        """Jitted prefix capture: copy a slot's cache row with positions
        ``>= length`` zero-masked (one compiled program for every
        length).  NOT donated — the pool keeps its buffers."""
        if self._extract_fn is None:
            def extract_fn(caches, slot, length):
                self.prefix_extract_traces += 1  # trace-time only

                def ext(c):
                    row = jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=0)
                    idx = jnp.arange(row.shape[1]).reshape(
                        (1, -1) + (1,) * (row.ndim - 2))
                    return jnp.where(idx < length, row,
                                     jnp.zeros_like(row))

                return jax.tree_util.tree_map(ext, caches)

            self._extract_fn = jax.jit(extract_fn)
        return self._extract_fn

    # ------------------------------------------------------------- submit

    @contextlib.contextmanager
    def epoch_fence(self, epoch: int):
        """Check-and-record a router dispatch's epoch ATOMICALLY with
        the admission or cancel performed inside the ``with`` block: any
        epoch LOWER than the high-water already seen raises the typed
        :class:`EpochFencedError` (the frontend turns it into a status=1
        reply).  Equal epochs are fine — the active router stamps every
        dispatch with its current epoch.  The fencing-token discipline:
        once a takeover router's first dispatch lands here, the deposed
        epoch can never place (or cancel) work on this engine again, so
        a request the new epoch re-dispatched cannot also be driven by
        its old leg (docs/serving.md "Router HA").  The lock is held
        across the body — a bare check-then-act would leave a window
        where a deposed router's dispatch passes the check, the takeover
        epoch's first dispatch lands, and the stale action still runs
        afterward, the exact interleaving the fence exists to refuse."""
        epoch = int(epoch)
        with self._epoch_lock:
            if epoch < self._epoch_hw:
                raise EpochFencedError(epoch, self._epoch_hw)
            self._epoch_hw = epoch
            yield

    def fence_epoch(self, epoch: int) -> None:
        """Point-in-time epoch check (see :meth:`epoch_fence`; dispatch
        paths that admit or cancel work must use the context-manager
        form so the check is atomic with the action)."""
        with self.epoch_fence(epoch):
            pass

    @property
    def epoch_high_water(self) -> int:
        with self._epoch_lock:
            return self._epoch_hw

    def submit(self, prompt, max_new_tokens: int, *, seed: int = 0,
               priority: int = 0, resume_tokens=None,
               epoch: Optional[int] = None, keep_kv: bool = False,
               kv_blocks=None) -> Request:
        """Enqueue a generation request.  Raises ``ValueError`` on an
        infeasible request and ``QueueFullError`` (typed backpressure)
        when the bounded admission queue is at capacity.

        ``epoch`` (router dispatches only) runs the whole admission
        under :meth:`epoch_fence`, so a stale-epoch dispatch racing the
        takeover epoch's first dispatch is refused, never admitted.

        ``resume_tokens`` resumes a request another engine already
        emitted ``k`` tokens for (the router's cross-replica failover,
        serving/router.py): this engine re-prefills prompt + emitted
        tokens (position-wise determinism rebuilds the exact K/V the
        original decode wrote — the PR 9 preempt/resume argument, one
        engine hop wider), restores the parked next-input token, and —
        under sampling — recomputes the carried key as the ``k``-fold
        split chain of ``PRNGKey(seed)``, so the continued stream is
        token-identical to a never-interrupted run.  The key state is
        recoverable by construction (a pure function of ``seed`` and
        ``k``); ``max_new_tokens`` stays the request's TOTAL budget and
        the resumed tokens count against it (only new tokens are
        streamed; ``result()`` returns the full sequence).

        ``keep_kv`` (disagg prefill replicas) parks the finished
        request's paged blocks for a post-reply ship instead of freeing
        them; ``kv_blocks`` (disagg decode replicas) carries staged,
        already-written block ids whose adoption replaces the prefill
        pass entirely (docs/serving.md "Disaggregated tiers")."""
        # stamped before anything can wait: TTFT holds the wait for the
        # engine lock below (the tick thread holds it for a whole tick)
        t_submit = time.monotonic()
        fence = (contextlib.nullcontext() if epoch is None
                 else self.epoch_fence(epoch))
        with tracing.annotate(tracing.SPAN_SUBMIT), fence:
            return self._submit(prompt, max_new_tokens, seed=seed,
                                priority=priority,
                                resume_tokens=resume_tokens,
                                keep_kv=keep_kv, kv_blocks=kv_blocks,
                                t_submit=t_submit)

    @contextlib.contextmanager
    def _lock_waited(self):
        """The engine lock as a submit takes it: the wait under
        ``bps.submit/lock_wait`` and on ``serve.submit_lock_wait_s``.
        Callers write ``with self._lock_waited(), self._lock:`` — the
        second take is re-entrant and free, and it is where the lock
        analyzer (scripts/lint.py) reads the guarded scope from."""
        t0 = time.perf_counter()
        with tracing.annotate(tracing.SPAN_SUBMIT_LOCK_WAIT):
            self._lock.acquire()
        try:
            self.metrics.observe("submit_lock_wait",
                                 time.perf_counter() - t0)
            yield
        finally:
            self._lock.release()

    def _submit(self, prompt, max_new_tokens: int, *, seed: int,
                priority: int, resume_tokens, keep_kv: bool = False,
                kv_blocks=None, t_submit: float) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T = int(prompt.shape[0])
        if T < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if T + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_seq {self.max_seq}")
        resumed: List[int] = ([int(t) for t in resume_tokens]
                              if resume_tokens is not None else [])
        if (resumed and self.eos_id is not None
                and resumed[-1] == self.eos_id):
            # the stream already ended at EOS on the engine that died:
            # there is nothing to generate (and decoding past EOS would
            # emit tokens a never-interrupted run never produces).
            # Answer an already-finished request — no slot, no prefill,
            # safe even on configs that refuse recompute-based resume.
            with self._lock_waited(), self._lock:
                self._req_seq += 1
                req = Request(id=self._req_seq, prompt=prompt,
                              max_new_tokens=max_new_tokens, seed=seed,
                              priority=priority, t_submit=t_submit,
                              t_enqueued=time.monotonic())
                req.tokens = resumed
                req.state = RequestState.DONE
                req._out.put(_END)
                req._done.set()
                if kv_blocks is not None and self.paged:
                    # staged disagg blocks for a request that needs no
                    # decoding: nothing will adopt them — release now
                    for b in kv_blocks:
                        self.pool.alloc.decref(int(b))
                    kv_blocks = None
            self.metrics.bump(sm.SUBMITTED)
            self.metrics.bump(sm.COMPLETED)  # 0 tokens generated here
            return req
        if resumed:
            if self._resume_unsafe:
                raise ValueError(
                    f"this engine cannot resume a partially-emitted "
                    f"request bit-exactly ({self._resume_unsafe}); "
                    f"serve resumable replicas with a dense, "
                    f"non-flash-prefill config")
            if max_new_tokens <= len(resumed):
                raise ValueError(
                    f"resume carries {len(resumed)} tokens but "
                    f"max_new_tokens is {max_new_tokens} — nothing "
                    f"left to generate")
        # the admission grant is denominated in the padded tokens the
        # prefill will actually run: prompt plus (on resume) the
        # emitted tokens minus the parked last one
        bucket = _next_bucket(T + max(0, len(resumed) - 1),
                              self.min_prefill_bucket, self.max_seq)
        if self.chunk:
            # the admission grant pays for the FIRST chunk only; each
            # continuation chunk debits the same pool at process time
            bucket = min(bucket, self.chunk)
        # dead-engine check AND enqueue under the engine lock, which
        # _fail_all holds while draining: a submit racing the failure
        # path must either land before the drain (and be failed by it)
        # or see the error — never enqueue into a dead engine's queue.
        # The outstanding counter also increments here, BEFORE the tick
        # thread can see the request: a fast request could otherwise
        # finish (decrementing) first, and a concurrent drain() would
        # see a transiently-zero counter with work still in flight.
        with self._lock_waited(), self._lock:
            with tracing.annotate(tracing.SPAN_SUBMIT_ENQUEUE,
                                  req=self._req_seq + 1):
                if self._engine_error is not None:
                    raise RuntimeError(
                        f"serving engine is dead (tick failed with "
                        f"{self._engine_error!r}); restart it") \
                        from self._engine_error
                self._req_seq += 1
                req = Request(id=self._req_seq, prompt=prompt,
                              max_new_tokens=max_new_tokens, seed=seed,
                              priority=priority, t_submit=t_submit,
                              t_enqueued=time.monotonic())
                if resumed:
                    # pre-seed the emitted tokens and park the resume state
                    # exactly as _preempt would have: _admit then prefills
                    # prompt + tokens[:-1] and the final chunk restores the
                    # parked next-input token and carried key instead of
                    # emitting a fresh "first" token
                    req.tokens = resumed
                    req._resumed_n = len(resumed)
                    req._resume_tok = resumed[-1]
                    if not self.greedy:
                        req._resume_key = _resume_key_chain(seed, len(resumed))
                req._keep_kv = bool(keep_kv and self.paged)
                if kv_blocks is not None and self.paged:
                    req._kv_blocks = [int(b) for b in kv_blocks]
                    kv_blocks = None  # ownership moved to the request
                if self._trace_rpc:
                    # join the caller's active trace (a submit inside a
                    # traced client op) or mint a fresh id for this request
                    from ..observability.trace import (current_trace_id,
                                                       mint_trace_id)

                    req.trace_id = (current_trace_id()
                                    or mint_trace_id()).hex()
                    req._t_pc = time.perf_counter()
                self._outstanding += 1
                try:
                    req._task = self.scheduler.submit(req, bucket)
                except Exception:
                    self._outstanding -= 1
                    self._drain_cv.notify_all()  # same lock; wake waiters
                    self.metrics.bump(sm.REJECTED)
                    raise
        self.metrics.bump(sm.SUBMITTED)
        with self._wake:
            self._wake.notify_all()
        return req

    def cancel(self, req: Request) -> None:
        """Request cancellation.  A still-QUEUED request is dropped from
        the admission queue immediately (it stops holding queue depth
        and never consumes a grant); the eager drop races admission
        under the engine lock, and the grant-time cancelled check stays
        as the fallback.  An in-flight (PREFILLING/DECODING) request is
        retired eagerly too: ``cancel()`` serializes with ``step()``
        on the engine lock, so no decode or chunk program is mid-
        flight, and the slot — and in the paged engine its non-shared
        KV blocks and prefix block references — returns to the pool
        *now*, admissible by the very next tick rather than one tick
        later.  The tick-start sweep remains as a belt-and-braces
        fallback for the flag-only path."""
        req.cancelled = True
        with self._lock:
            if (req.state is RequestState.QUEUED and req._task is not None
                    and self.scheduler.remove(req._task)):
                self._finish(req, RequestState.CANCELLED)
            elif (req.state in (RequestState.PREFILLING,
                                RequestState.ACTIVE)
                    and req.slot is not None
                    and self._engine_error is None):
                self._finish(req, RequestState.CANCELLED)
        with self._wake:
            self._wake.notify_all()

    # --------------------------------------------------------------- tick

    def step(self) -> Dict[str, int]:
        """One engine tick: cancellations -> credit-bounded admissions ->
        one batched decode pass -> credits return.  Returns tick stats."""
        with self._lock:
            return self._step_locked()

    def _step_locked(self) -> Dict[str, int]:
        # with nothing in flight and nothing queued (the 50 ms background
        # poll) there is no tick to run: no span, no gauges, no counters
        # — a traced long-lived server would otherwise append to the
        # Tracer's in-memory list forever.  Nothing can arrive meanwhile:
        # submit() enqueues under this lock
        if not (self.pool.active_count or self.scheduler.depth):
            return {"admitted": 0, "emitted": 0, "active": 0, "queued": 0,
                    "prefill_tokens": 0}
        with tracing.annotate(tracing.SPAN_TICK,
                              active=self.pool.active_count,
                              queued=self.scheduler.depth) as span:
            return self._tick_locked(span)

    @contextlib.contextmanager
    def _phase_locked(self, span: str, phase: str, **args):
        """One phase of a tick, named twice from one pair of stamps: a
        host span on the device trace's clock, and its seconds on
        ``serve.tick_seconds{phase}`` at the tick's end."""
        t0 = time.perf_counter()
        try:
            with tracing.annotate(span, **args):
                yield
        finally:
            self._phase_s[phase] += time.perf_counter() - t0

    def _tick_locked(self, span) -> Dict[str, int]:
        emitted = 0
        admitted = 0
        granted: List = []
        self._tick_chunk_debt = 0
        self._tick_prefill = 0
        try:
            # 0. retire cancelled active/prefilling requests (frees
            # their slots for this tick's admissions)
            for slot in self.pool.active_slots():
                req = self._slot_req[slot]
                if req is not None and req.cancelled:
                    self._finish(req, RequestState.CANCELLED)
            # 1. continue in-flight chunked prefills (slot order) —
            # BEFORE new admissions: finishing started work frees
            # capacity soonest, and the continuation debits shrink the
            # credit pool the admission scan below sees
            for slot in sorted(self._prefilling):
                req = self._prefilling.get(slot)
                if req is not None:
                    emitted += self._advance_prefill(req)
            # 2. admissions, in scheduler grant order (priority desc,
            # FIFO)
            free = self.pool.free_count
            if free:
                with self._phase_locked(tracing.SPAN_TICK_ADMIT, "admit"):
                    granted = self.scheduler.admit(free)
                for task in granted:
                    req = task.request
                    if req.cancelled:
                        self._finish(req, RequestState.CANCELLED)
                    elif (self.paged and req._hold_blocks
                          and self.pool.alloc.free_count
                          < req._hold_blocks
                          and self.pool.active_count > 0):
                        # preempted request waiting out block pressure:
                        # stay QUEUED until its worst-case need fits
                        # (others are still freeing); with nothing else
                        # active it admits regardless — the pressure
                        # path then evicts the prefix store or fails
                        # loudly.  FCFS head-of-line: everything granted
                        # AFTER it goes back too, or a sustained stream
                        # of newer short requests would consume each
                        # tick's freed blocks and starve it forever
                        idx = granted.index(task)
                        for later in granted[idx:]:
                            self.scheduler.resubmit(later)
                        break
                    else:
                        req._hold_blocks = 0
                        admitted += 1
                        emitted += self._admit(req)
            # 3. one decode pass over the pool (PREFILLING slots are
            # assigned but not yet decodable — their first token comes
            # from their final prefill chunk)
            active = [s for s in self.pool.active_slots()
                      if s not in self._prefilling]
            if active:
                emitted += self._decode_tick(active)
        except Exception as e:
            # granted tasks are already popped from the queue: a
            # request whose _admit never ran (or raised before its slot
            # assignment) is invisible to both the queue drain and the
            # active-slot scan in _fail_all — fail it here or its
            # result()/drain() callers hang forever
            for task in granted:
                req = task.request
                if req.state is RequestState.QUEUED:
                    # a preempt-requeued request's task is back in the
                    # queue — pull the corpse so _fail_all's drain (or
                    # a later tick) cannot retire it a second time
                    self.scheduler.remove(task)
                    req.error = e
                    self._finish(req, RequestState.FAILED)
            raise
        finally:
            # 4. credits back — in normal ticks AFTER decode, so the
            # budget truly bounds the prefill work interleaved between
            # consecutive decode passes; on a failed tick, so the
            # credits of granted work (and continuation-chunk debits)
            # are never leaked
            for task in granted:
                self.scheduler.finish(task)
            if self._tick_chunk_debt:
                self.scheduler.return_credits(self._tick_chunk_debt)
                self._tick_chunk_debt = 0
        with self._phase_locked(tracing.SPAN_TICK_ACCOUNT, "account"):
            self.metrics.observe_tick(self.pool.occupancy(),
                                      self.scheduler.depth, emitted)
            # live credit level (post-return = the budget the next
            # tick's admission scan starts from)
            self.metrics.gauge(sm.PREFILL_CREDITS, self.scheduler.credits)
            if self.paged:
                bs = self.pool.block_stats()
                self.metrics.gauge(sm.KV_BLOCKS_FREE, bs["free"])
                self.metrics.gauge(sm.KV_BLOCKS_USED, bs["used"])
                self.metrics.gauge(sm.KV_BLOCKS_SHARED, bs["shared"])
        span.set_metadata(admitted=admitted, emitted=emitted)
        self.metrics.observe_tick_phases(self._phase_s)
        self._phase_s = dict.fromkeys(tracing.TICK_PHASES, 0.0)
        # "admitted" counts requests actually assigned a slot this tick
        # — NOT cancelled grants or held (resubmitted) tasks
        return {"admitted": admitted, "emitted": emitted,
                "active": self.pool.active_count,
                "queued": self.scheduler.depth,
                "prefill_tokens": self._tick_prefill}

    def _admit(self, req: Request) -> int:
        with self._phase_locked(tracing.SPAN_TICK_ADMIT, "admit", req=req.id):
            # the sequence this admission must prefill: the prompt, or —
            # when resuming a preempted request — prompt + emitted tokens
            # minus the last (its K/V is unwritten; it is the next decode
            # input, parked in _resume_tok)
            k = len(req.tokens)
            seq = (req.prompt if k == 0 else
                   np.concatenate([req.prompt,
                                   np.asarray(req.tokens[:-1], np.int32)]))
            req._seq = seq
            T = int(seq.shape[0])
            slot = self.pool.assign(req.id, T)
            assert slot is not None, "admit() granted beyond free slots"
            req.slot = slot
            if not req.t_admit:  # keep the first admission's queue-wait
                req.t_admit = time.monotonic()
                self.metrics.bump(sm.ADMITTED)
            self._slot_req[slot] = req
            if req._kv_blocks is not None:
                # disagg adoption: a shipped prefill's staged blocks replace
                # the prefill pass.  The table adopts them (ownership
                # transfer — the stager's refs become the table's), the
                # cursor is already at T from assign(), and the parked
                # resume pair seeds the next decode input exactly like the
                # chunked-resume path below — bit-exact by the position-wise
                # determinism argument (docs/serving.md "Disaggregated
                # tiers").  Any geometry surprise refuses adoption and falls
                # through to normal (re-)prefill — never a wrong answer.
                ids, req._kv_blocks = req._kv_blocks, None
                if (self.paged and req._resume_tok is not None
                        and len(ids) == -(-T // self.pool.block)
                        and len(ids) <= self.pool.tables[slot].max_blocks
                        and not self.pool.tables[slot].blocks):
                    self.pool.adopt_blocks(slot, ids)
                    req.state = RequestState.ACTIVE
                    self._tok = self._tok.at[slot].set(req._resume_tok)
                    if not self.greedy and req._resume_key is not None:
                        self._keys = self._keys.at[slot].set(
                            jnp.asarray(req._resume_key))
                    req._resume_tok = None
                    req._resume_key = None
                    return 0
                bps_log.warning(
                    "disagg: refusing adoption of %d staged block(s) for "
                    "request %d (want %d for T=%d) — re-prefilling",
                    len(ids), req.id, -(-T // self.pool.block)
                    if self.paged else -1, T)
                for b in ids:
                    self.pool.alloc.decref(int(b))
            p0 = 0
            if self.prefix is not None:
                req._prefix_digs = self.prefix.digests_for(
                    seq, salt=self._prefix_salt)
                m = self.prefix.match(seq, salt=self._prefix_salt,
                                      digests=req._prefix_digs)
                if m is not None:
                    entry, p0 = m
                    # pin across the attach/copy, then resume prefill at
                    # the boundary — the shared (or copied) bytes ARE the
                    # K/V whole prefill would recompute, so parity is by
                    # construction
                    self.prefix.acquire(entry)
                    try:
                        if self.paged:
                            # zero-copy prefix hit: the slot's table adopts
                            # the entry's blocks (refcount bumps, no device
                            # work — the acceptance criterion the compile
                            # counters pin)
                            self.pool.share_prefix(
                                slot, entry.buffer[:p0 // self.pool.block])
                        else:
                            self.pool.caches = self._prefix_copy_fn()(
                                self.pool.caches, entry.buffer, slot)
                    finally:
                        self.prefix.release(entry)
                    self.metrics.bump(sm.PREFIX_HITS)
                    self.metrics.bump(sm.PREFIX_HIT_TOKENS, p0)
                else:
                    self.metrics.bump(sm.PREFIX_MISSES)
        if p0 == 0 and not self.chunk and not self.paged:
            # whole-prompt prefill (the pre-chunking path, bit-identical)
            req.state = RequestState.ACTIVE
            bucket = _next_bucket(T, self.min_prefill_bucket, self.max_seq)
            with tracing.annotate(tracing.SPAN_TICK_PREFILL, req=req.id,
                                  bucket=bucket, start=0):
                return self._prefill_whole(req, seq, bucket)
        # chunked (or prefix-resumed, or paged) prefill: the request
        # parks in PREFILLING with the slot held; the admission grant
        # pre-paid its first chunk, later chunks debit the shared
        # credit pool
        req.state = RequestState.PREFILLING
        req.prefill_pos = p0
        req._pf_paid = True
        self._prefilling[slot] = req
        return self._advance_prefill(req)

    def _prefill_whole(self, req: Request, seq, bucket: int) -> int:
        """The whole prompt in one program (dense engine, no chunking,
        no prefix hit): build, launch, read the first token back."""
        T = int(seq.shape[0])
        slot = req.slot
        stage = tracing.STAGE_SPANS[tracing.SPAN_TICK_PREFILL]
        with self._phase_locked(stage["build"], "prefill_build"):
            padded = np.full((1, bucket), self.pad_id, np.int32)
            padded[0, :T] = seq
            key = (jnp.zeros((2,), jnp.uint32) if self.greedy
                   else jax.random.PRNGKey(req.seed))
            fn = self._prefill_fn(bucket)
            padded = jnp.asarray(padded)
        with self._phase_locked(stage["launch"], "prefill_launch"):
            caches, tok0, nk = fn(self.variables, self.pool.caches,
                                  padded, slot, T, key)
            del padded          # as in _decode_pass: not at the return
        self.pool.caches = caches
        self.metrics.bump(sm.PREFILL_TOKENS, bucket)
        self._tick_prefill += bucket
        if req._resume_tok is not None:
            # resuming a request another engine emitted tokens for
            # (router failover): the prefill's sampled token and key
            # split are discarded — the parked next-input token and
            # the recomputed carried key continue the original
            # chain, same discipline as the chunked resume path
            self._tok = self._tok.at[slot].set(req._resume_tok)
            if not self.greedy and req._resume_key is not None:
                self._keys = self._keys.at[slot].set(
                    jnp.asarray(req._resume_key))
            req._resume_tok = None
            req._resume_key = None
            self._maybe_insert_prefix(req)
            return 0
        self._tok = self._tok.at[slot].set(tok0)
        if not self.greedy:
            self._keys = self._keys.at[slot].set(nk)
        self._maybe_insert_prefix(req)
        with self._phase_locked(stage["readback"], "prefill_readback"):
            tok = int(tok0)
        self._emit(req, tok)
        return 1

    def _advance_prefill(self, req: Request) -> int:
        """Run as many prefill chunks for ``req`` as the tick's credits
        allow.  Returns 1 when the final chunk completed (first token
        emitted), else 0 — the request stays PREFILLING and resumes on
        the next tick's continuation pass with a fresh budget (0 is
        also the answer when block pressure preempted or failed the
        request mid-pass; the slot is gone then)."""
        seq = req._seq if req._seq is not None else req.prompt
        T = int(seq.shape[0])
        S = self.max_seq
        while True:
            p0 = req.prefill_pos
            csize = (min(T - p0, self.chunk) if self.chunk else T - p0)
            bucket = _next_bucket(csize, self.min_prefill_bucket,
                                  self.chunk or self.max_seq)
            if p0 and p0 + bucket > S and p0 + self.min_prefill_bucket <= S:
                # a covering bucket would overrun the row, and the
                # boundary guard below would then shift the chunk left
                # across positions the prefix copy (or earlier chunks)
                # already wrote — recomputing exactly what the reuse
                # saved.  Split instead: take the largest bucket that
                # fits at p0 and leave the tail to the next loop pass
                fit = self.min_prefill_bucket
                while fit * 2 <= S - p0:
                    fit *= 2
                bucket = fit
                csize = min(csize, bucket)
            # clamp the debit to the whole budget, exactly like
            # ServeScheduler.submit clamps an admission grant — a
            # bucket larger than the budget could otherwise NEVER be
            # paid for and the request would sit in PREFILLING forever
            need = (min(bucket, self.scheduler.credit_budget)
                    if self.scheduler.credit_budget > 0 else bucket)
            if req._pf_paid:
                req._pf_paid = False
            elif self.scheduler.take_credits(need):
                self._tick_chunk_debt += need
            else:
                return 0  # budget spent; next tick continues
            # boundary guard: a padded final bucket must not write past
            # the cache row.  Shift the chunk start left instead and
            # RE-FEED the overlapped prompt tokens — recomputing K/V
            # already in the row rewrites identical bytes (position-wise
            # determinism, docs/serving.md), so the overlap is bit-exact
            start = min(p0, S - bucket)
            with tracing.annotate(tracing.SPAN_TICK_PREFILL, req=req.id,
                                  bucket=bucket, start=start):
                out = self._prefill_chunk(req, seq, p0, csize, bucket,
                                          start)
            if out is not None:
                return out

    def _prefill_chunk(self, req: Request, seq, p0: int, csize: int,
                       bucket: int, start: int) -> Optional[int]:
        """One paid-for chunk ``[start, start + bucket)`` of ``req``'s
        prefill: build, launch and, on the final chunk, the first
        token's readback.  ``None`` = a middle chunk (the caller loops
        on), else ``_advance_prefill``'s answer."""
        T = int(seq.shape[0])
        slot = req.slot
        stage = tracing.STAGE_SPANS[tracing.SPAN_TICK_PREFILL]
        with self._phase_locked(stage["build"], "prefill_build"):
            if self.paged:
                # lazy block grant for the chunk's REAL tokens only
                # (min(..., T)): the padded bucket tail's writes route
                # to the null block through the table's null-filled
                # entries, so granting blocks for pure padding would
                # hold ghost memory for the slot's whole lifetime.
                # Then copy-on-write forks for any shared block the
                # span touches (only the shift-left re-feed can reach
                # one; the fork copy makes the identical-bytes rewrite
                # land in a private clone, keeping shared blocks
                # immutable)
                if not self._with_block_pressure(
                        req, lambda: self.pool.ensure_blocks(
                            slot, min(start + bucket, T))):
                    return 0
                if not self._with_block_pressure(
                        req, lambda: self.pool.make_writable(
                            slot, start, start + bucket,
                            self._cow_copy)):
                    return 0
            toks = np.full((1, bucket), self.pad_id, np.int32)
            end = min(start + bucket, T)
            toks[0, :end - start] = seq[start:end]
            final = p0 + csize >= T
            last_idx = (T - 1 - start) if final else (bucket - 1)
            key = (jnp.zeros((2,), jnp.uint32) if self.greedy
                   else jax.random.PRNGKey(req.seed))
            if self.paged:
                fn = self._paged_chunk_fn(bucket)
                where = self.pool.table_row(slot)
            else:
                fn = self._chunk_fn(bucket)
                where = slot
            toks = jnp.asarray(toks)
        with self._phase_locked(stage["launch"], "prefill_launch"):
            caches, tok0, nk = fn(self.variables, self.pool.caches, toks,
                                  where, start, last_idx, key)
            del toks, where     # as in _decode_pass: not at the return
        self.pool.caches = caches
        req.prefill_pos = p0 + csize
        self.metrics.bump(sm.PREFILL_TOKENS, bucket)
        self.metrics.bump(sm.PREFILL_CHUNKS)
        self._tick_prefill += bucket
        if not final:
            return None
        del self._prefilling[slot]
        req.state = RequestState.ACTIVE
        if req._resume_tok is not None:
            # resuming a preempted request: the K/V for every
            # already-emitted token is rebuilt; the final chunk's
            # sampled token AND its key split are discarded, and the
            # parked next-input token plus the carried key are restored
            # — the per-request key chain continues exactly
            # once-per-step, so seeded streams stay bit-exact across
            # preemption
            self._tok = self._tok.at[slot].set(req._resume_tok)
            if not self.greedy and req._resume_key is not None:
                self._keys = self._keys.at[slot].set(
                    jnp.asarray(req._resume_key))
            req._resume_tok = None
            req._resume_key = None
            self._maybe_insert_prefix(req)
            return 0  # nothing emitted; decode resumes next
        self._tok = self._tok.at[slot].set(tok0)
        if not self.greedy:
            self._keys = self._keys.at[slot].set(nk)
        self._maybe_insert_prefix(req)
        with self._phase_locked(stage["readback"], "prefill_readback"):
            tok = int(tok0)
        self._emit(req, tok)
        return 1

    def _with_block_pressure(self, req: Request, fn) -> bool:
        """Run ``fn()`` (a block allocation on behalf of ``req``); on
        :class:`BlocksExhaustedError`, reclaim memory and retry:

          1. evict unpinned prefix-cache entries (cheapest — cached
             prefixes can always be recomputed);
          2. preempt the NEWEST other in-flight request back to QUEUED
             (vLLM's recompute preemption: oldest work finishes first,
             so the system always makes forward progress);
          3. if ``req`` is itself the newest, it yields — preempted
             back to QUEUED to resume when older requests finish;
          4. a request that cannot fit the pool even alone fails
             loudly with the typed error attached.

        True = ``fn`` succeeded.  False = ``req`` lost its slot
        (preempted or failed); the caller abandons it this tick."""
        while True:
            try:
                fn()
                return True
            except BlocksExhaustedError as e:
                if self.prefix is not None and self.prefix.evict_for(
                        max(1, e.needed - e.free)):
                    continue
                others = [self._slot_req[s]
                          for s in self.pool.active_slots()
                          if self._slot_req[s] is not None
                          and self._slot_req[s] is not req]
                newer = [r for r in others if r.id > req.id]
                if newer:
                    self._preempt(max(newer, key=lambda r: r.id))
                    continue
                if others:
                    # req is the newest holder: it yields rather than
                    # deadlocking requests admitted before it
                    self._preempt(req)
                    return False
                # alone and still short: the pool can never fit this
                # request — fail it with the typed error
                req.error = e
                self._finish(req, RequestState.FAILED)
                return False

    def _preempt(self, victim: Request) -> None:
        """Preempt an in-flight request back to QUEUED (paged engine,
        KV block pressure): its slot and non-shared blocks return to
        the pool NOW; on re-admission it re-prefills prompt + emitted
        tokens (position-wise determinism makes the rebuilt K/V
        bit-identical to what incremental decode wrote) and resumes
        decoding from its parked next-input token and sampling key.
        Already-streamed tokens are kept — consumers see a stall, never
        a replay.  Re-queued via the ORIGINAL scheduler task, so it
        re-enters ahead of later submissions."""
        slot = victim.slot
        if victim.state is RequestState.ACTIVE and victim.tokens:
            victim._resume_tok = int(np.asarray(self._tok[slot]))
            if not self.greedy:
                victim._resume_key = np.asarray(self._keys[slot])
        # a PREFILLING victim keeps whatever resume state it carries: a
        # request preempted a SECOND time mid-resume still owes exactly
        # the parked token and key it owed before — clobbering them
        # would re-emit the parked token as a fresh "first" token
        self._prefilling.pop(slot, None)
        self._slot_req[slot] = None
        self.pool.free(slot)  # releases the table's block references
        victim.slot = None
        victim.prefill_pos = 0
        victim._pf_paid = False
        victim._seq = None
        # re-admission watermark: worst-case blocks to complete (prefix
        # sharing can only shrink the real need, so this is safe-side)
        victim._hold_blocks = -(-(int(victim.prompt.shape[0])
                                  + victim.max_new_tokens)
                                // self.pool.block)
        victim.state = RequestState.QUEUED
        self.scheduler.resubmit(victim._task)
        self.metrics.bump(sm.PREEMPTIONS)

    def _maybe_insert_prefix(self, req: Request) -> None:
        """After a completed prefill, capture the sequence's block-
        aligned prefix K/V into the store (skipped when already
        indexed).  Paged engines register the slot's own blocks —
        refcount bumps, zero device-side copies; dense engines pay the
        jitted zero-masked row extract."""
        if self.prefix is None:
            return
        seq = req._seq if req._seq is not None else req.prompt
        ins = self.prefix.insertable_len(seq,
                                         salt=self._prefix_salt,
                                         digests=req._prefix_digs)
        if ins <= 0:
            return
        if self.paged:
            ids = self.pool.tables[req.slot].blocks[
                :ins // self.pool.block]
            if (len(ids) == ins // self.pool.block
                    and self.prefix.insert_blocks(
                        seq[:ins], ids, salt=self._prefix_salt,
                        digests=req._prefix_digs)):
                self.metrics.bump(sm.PREFIX_INSERTIONS)
            return
        if (self.prefix.max_bytes
                and self._prefix_row_bytes > self.prefix.max_bytes):
            return
        buf = self._prefix_extract_fn()(self.pool.caches, req.slot, ins)
        if self.prefix.insert(seq[:ins], buf,
                              salt=self._prefix_salt,
                              digests=req._prefix_digs):
            self.metrics.bump(sm.PREFIX_INSERTIONS)

    def _gather_hw(self, tq: int) -> int:
        """Block high-water bucket for the XLA gather fallback: the
        smallest power-of-two block count (capped at ``max_blocks``)
        covering every assigned slot's ``[0, pos + tq)`` span this tick
        — masked slots sit at pos 0 and still land their ``tq``-wide
        garbage write inside the view.  Bucketing keeps the compile
        count O(log max_blocks) (the prefill-bucket discipline) while
        the gather stops streaming the null-block / unwritten padding
        beyond the highest live cursor."""
        blk = self.pool.block
        need = tq
        for slot in self.pool.active_slots():
            if slot in self._prefilling:
                # PREFILLING slots are masked out of paged decode AND
                # verify (their pos vector entry is 0, their garbage
                # write aims at the null block), so their — possibly
                # deep — prefill cursor must not drag every
                # interleaved decode tick back to full gather width
                continue
            need = max(need, self.pool.pos[slot] + tq)
        return _next_bucket(-(-need // blk), 1, self.pool.max_blocks)

    def _decode_tick(self, active: List[int]) -> int:
        with tracing.annotate(tracing.SPAN_TICK_DECODE,
                              slots=len(active)):
            return self._decode_pass(active)

    def _decode_pass(self, active: List[int]) -> int:
        n = self.pool.n_slots
        stage = tracing.STAGE_SPANS[tracing.SPAN_TICK_DECODE]
        if self.paged:
            # lazy block grant at the boundary crossing: a slot whose
            # cursor enters an uncovered block gets one here — under
            # pressure this is where prefix eviction / preemption fires
            with self._phase_locked(stage["blocks"], "blocks"):
                for slot in list(active):
                    req = self._slot_req[slot]
                    if req is None:
                        continue  # a victim of an earlier preemption
                    self._with_block_pressure(
                        req, lambda s=slot: self.pool.ensure_blocks(
                            s, self.pool.pos[s] + 1))
                active = [s for s in active
                          if self._slot_req[s] is not None
                          and s not in self._prefilling]
            if not active:
                return 0
        if self.spec is not None:
            props = self._collect_proposals(active)
            if props:
                out = self._verify_tick(active, props)
                if out is not None:
                    return out
        self.metrics.bump(sm.DECODE_TICKS)
        with self._phase_locked(stage["build"], "build"):
            pos = np.zeros((n,), np.int32)
            mask = np.zeros((n,), bool)
            for slot in active:
                pos[slot] = self.pool.pos[slot]
                mask[slot] = True
            if self.paged:
                # scatter targets: each active slot writes its cursor's
                # (block, offset); masked slots (free or PREFILLING)
                # write the null block, so their garbage can never land
                # in a shared prefix block or a mid-prefill row
                wblk = np.full((n,), self.pool.null_block, np.int32)
                woff = np.zeros((n,), np.int32)
                for slot in active:
                    wblk[slot], woff[slot] = self.pool.write_target(slot)
                if self.paged_kernel:
                    # fused kernel: one program, write targets per
                    # (slot, query) — tq = 1 here — and NO gather
                    # anywhere
                    fn = self._paged_decode_fn(None)
                    wblk, woff = wblk[:, None], woff[:, None]
                else:
                    # pos-capped gather: stream each slot's high-water
                    # bucket, not the full null-padded table width
                    hw = self._gather_hw(1)
                    self.metrics.bump(sm.GATHERED_BLOCKS, n * hw)
                    fn = self._paged_decode_fn(hw)
                args = (jnp.asarray(pos), jnp.asarray(mask), self._keys,
                        self.pool.tables_device(), jnp.asarray(wblk),
                        jnp.asarray(woff))
            else:
                # PREFILLING slots ride the decode step masked-off like
                # freed slots do, but their garbage K/V write must NOT
                # land at pos 0 (it would corrupt the copied prefix /
                # already-written chunks): aim it at the slot's
                # post-prefill cursor, which the request's own first
                # real decode overwrites before the causal mask can
                # ever admit it
                for slot in self._prefilling:
                    pos[slot] = self.pool.pos[slot]
                fn = self._decode_step
                args = (jnp.asarray(pos), jnp.asarray(mask), self._keys)
        with self._phase_locked(stage["launch"], "launch"):
            caches, nxt, keys = fn(self.variables, self.pool.caches,
                                   self._tok, *args)
            # the tick's input arrays go now, while the device runs: kept
            # to the pass's return their frees cost 0.5 ms a tick AFTER
            # the readback, with the device idle (PERF.md §6, PR 36)
            del args
        self.pool.caches = caches
        self._tok = nxt
        self._keys = keys
        with self._phase_locked(stage["readback"], "readback"):
            nxt_host = np.asarray(nxt)
        emitted = 0
        with self._phase_locked(stage["emit"], "emit"):
            for slot in active:
                req = self._slot_req[slot]
                self.pool.advance(slot)
                self._emit(req, int(nxt_host[slot]))
                emitted += 1
        return emitted

    def _collect_proposals(self, active: List[int]) -> Dict[int, List[int]]:
        """CPU-side prompt-lookup pass: per active slot, match the
        request's trailing n-gram against its own prompt + emitted
        history and propose up to ``k`` continuations (serving/spec.py).
        Proposals are capped at the slot's remaining row space and the
        request's remaining token budget minus one — tokens past either
        could never be emitted, so verifying them would be pure waste.
        Empty when nothing matched anywhere: the tick then runs the
        plain decode program, paying zero verify overhead."""
        props: Dict[int, List[int]] = {}
        S = self.max_seq
        for slot in active:
            req = self._slot_req[slot]
            if req is None or not req.tokens:
                continue
            cap = min(S - self.pool.pos[slot] - 1,
                      req.max_new_tokens - len(req.tokens) - 1)
            if cap < 1:
                continue
            buf = req._spec_ctx
            P = int(req.prompt.shape[0])
            if buf is None:
                buf = np.empty(P + req.max_new_tokens, np.int32)
                buf[:P] = req.prompt
                req._spec_ctx = buf
                req._spec_n = P
            k = len(req.tokens)
            have = req._spec_n - P
            if k > have:
                buf[P + have:P + k] = req.tokens[have:]
                req._spec_n = P + k
            p = self.spec.propose(buf[:req._spec_n], cap)
            if p:
                # SPEC_PROPOSED is bumped in _verify_tick from the
                # post-truncation lengths actually fed to the verifier
                # (a depth-bucket halving or paged coverage clip — or a
                # row-cap fallback to plain decode — drops tokens that
                # must not inflate the acceptance-rate denominator)
                props[slot] = p
        return props

    def _verify_tick(self, active: List[int],
                     props: Dict[int, List[int]]) -> Optional[int]:
        """One speculative tick: every slot rides a single ``tq = d + 1``
        verify pass (``d`` = this tick's depth bucket), and each active
        slot accepts the longest prefix of its proposals the model
        itself produced — at least one token (position 0 IS the plain
        decode step, so a tick can never emit less than the
        non-speculative engine).  Returns None when the depth bucket
        cannot fit every slot's row (the caller falls back to the plain
        decode program).

        Rollback of rejected positions is free by construction.  Dense:
        the cursor advances only past accepted tokens; the rejected
        span's K/V sits beyond it, never attended before the request's
        own later writes replace it (the freed-rows argument).  Paged:
        the scatter targets each span position's own granted block
        (host-computed), ungranted positions aim at the null block and
        cap acceptance, so shared prefix blocks are untouchable."""
        n = self.pool.n_slots
        S = self.max_seq
        d = _next_bucket(max(len(p) for p in props.values()), 1,
                         self.spec.k)
        # row cap: every slot whose write rides the program — active,
        # and (dense) PREFILLING slots whose masked garbage write is
        # aimed at their cursor — must fit [pos, pos + tq) inside its
        # row, or dynamic_update_slice would clamp the write leftward
        # over real K/V.  Halving stays on the compiled bucket grid.
        cap = S
        for slot in range(n):
            if self._slot_req[slot] is not None and (
                    not self.paged or slot not in self._prefilling):
                cap = min(cap, S - self.pool.pos[slot] - 1)
        while d > cap and d > 1:
            d //= 2
        if d > cap:
            return None
        with tracing.annotate(tracing.SPAN_TICK_VERIFY,
                              proposals=len(props)):
            return self._verify_pass(active, props, d)

    def _verify_pass(self, active: List[int],
                     props: Dict[int, List[int]], d: int) -> int:
        n = self.pool.n_slots
        S = self.max_seq
        tq = d + 1
        stage = tracing.STAGE_SPANS[tracing.SPAN_TICK_VERIFY]
        pmat = np.full((n, d), self.pad_id, np.int32)
        plen = np.zeros((n,), np.int32)
        posv = np.zeros((n,), np.int32)
        mask = np.zeros((n,), bool)
        budget = np.ones((n,), np.int32)
        for slot in active:
            req = self._slot_req[slot]
            posv[slot] = self.pool.pos[slot]
            mask[slot] = True
            budget[slot] = req.max_new_tokens - len(req.tokens)
            p = props.get(slot)
            if p:
                m = min(len(p), d)
                pmat[slot, :m] = p[:m]
                plen[slot] = m
        if self.paged:
            with self._phase_locked(stage["blocks"], "blocks"):
                for slot in active:
                    # span grant, best-effort: speculation must never
                    # evict prefix entries or preempt live requests
                    # just to hold guess-width — on exhaustion
                    # acceptance simply caps at the granted coverage
                    # (>= pos + 1, ensured by the decode pass's grant)
                    want = int(posv[slot]) + 1 + int(plen[slot])
                    try:
                        self.pool.ensure_blocks(slot, min(want, S))
                    except BlocksExhaustedError:
                        pass
        with self._phase_locked(stage["build"], "build"):
            if self.paged:
                blk = self.pool.block
                null = self.pool.null_block
                wblk = np.full((n, tq), null, np.int32)
                woff = np.zeros((n, tq), np.int32)
                for slot in active:
                    table = self.pool.tables[slot].blocks
                    cov = len(table) * blk - int(posv[slot])
                    # a proposal whose acceptance would advance the
                    # cursor onto an ungranted (null-aimed, unwritten)
                    # position is clipped BEFORE the verify, so the
                    # in-program accept can never outrun the granted
                    # coverage
                    plen[slot] = min(int(plen[slot]), cov - 1)
                    for j in range(min(tq, cov)):
                        p_ = int(posv[slot]) + j
                        wblk[slot, j] = table[p_ // blk]
                        woff[slot, j] = p_ % blk
                if self.paged_kernel:
                    fn = self._paged_verify_fn(tq, None)
                else:
                    hw = self._gather_hw(tq)
                    self.metrics.bump(sm.GATHERED_BLOCKS, n * hw)
                    fn = self._paged_verify_fn(tq, hw)
                where = (self.pool.tables_device(), jnp.asarray(wblk),
                         jnp.asarray(woff))
            else:
                # PREFILLING slots' masked garbage span aims at their
                # cursor, same discipline as the one-token step (the
                # span fits by the row cap above)
                for slot in self._prefilling:
                    posv[slot] = self.pool.pos[slot]
                fn = self._verify_fn(tq)
                where = ()
            args = (jnp.asarray(pmat), jnp.asarray(plen),
                    jnp.asarray(posv), jnp.asarray(mask), self._tok,
                    self._keys, jnp.asarray(budget)) + where
        with self._phase_locked(stage["launch"], "launch"):
            out = fn(self.variables, self.pool.caches, *args)
            del args, where     # as in _decode_pass: not at the return
        caches, self._tok, self._keys, tmat, m_emit, lead = out
        self.pool.caches = caches
        # ONE host transfer for everything the emit loop needs — three
        # separate np.asarray calls would block three times
        with self._phase_locked(stage["readback"], "readback"):
            tmat_h, me_h, lead_h = jax.device_get((tmat, m_emit, lead))
        emitted = 0
        accepted = 0
        with self._phase_locked(stage["emit"], "emit"):
            for slot in active:
                req = self._slot_req[slot]
                if req is None:
                    continue
                n_emit = int(me_h[slot])
                accepted += int(lead_h[slot])
                # cursor advances over EXACTLY the emitted tokens'
                # inputs: accepted-but-truncated tokens (budget/EOS)
                # advance nothing and are counted nowhere — the
                # next-input token and key chain were already picked to
                # match on device
                self.pool.advance(slot, n_emit)
                for tk in tmat_h[slot, :n_emit]:
                    self._emit(req, int(tk))
                    emitted += 1
        self.metrics.bump(sm.DECODE_TICKS)
        self.metrics.bump(sm.SPEC_VERIFY_TICKS)
        self.metrics.bump(sm.SPEC_PROPOSED, int(plen.sum()))
        if accepted:
            self.metrics.bump(sm.SPEC_ACCEPTED, accepted)
        return emitted

    def _emit(self, req: Request, tok: int) -> None:
        now = time.monotonic()
        if not req.t_first:  # first token THIS engine emitted (a
            req.t_first = now  # resumed request pre-seeds req.tokens)
        req.t_last = now
        req.tokens.append(tok)
        req._out.put((tok, now))
        done = (len(req.tokens) >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id))
        if done:
            self._finish(req, RequestState.DONE)

    def _finish(self, req: Request, state: RequestState) -> None:
        req.state = state
        if req.trace_id:
            # the request's whole-lifetime span, stamped with its trace
            # id — the serving-side anchor trace_merge's by-trace view
            # groups client/server spans under
            from ..common.tracing import get_tracer

            tracer = get_tracer()
            if tracer.enabled:
                tracer.complete(
                    f"serve:req{req.id}", "serve", req._t_pc,
                    time.perf_counter() - req._t_pc,
                    trace_id=req.trace_id, state=state.value,
                    tokens=len(req.tokens))
        if req.slot is not None:
            if (req._keep_kv and state is RequestState.DONE
                    and self.paged):
                # disagg prefill replica: park the finished request's
                # blocks (extra refs, taken BEFORE the free below drops
                # the table's own) so the frontend can ship them
                self._park_kv_locked(req)
            self._prefilling.pop(req.slot, None)
            self._slot_req[req.slot] = None
            self.pool.free(req.slot)
            req.slot = None
        if req._kv_blocks is not None:
            # staged blocks that were never adopted (cancel/failure
            # before admission): release, never leak
            for b in req._kv_blocks:
                self.pool.alloc.decref(int(b))
            req._kv_blocks = None
        req._out.put(_END)
        req._done.set()
        if state is RequestState.DONE:
            # count only THIS engine's emissions: a resumed request's
            # pre-seeded tokens belong to the engine that died, and
            # t_first/t_last span only the local ones — folding the
            # resumed count in would under-read TPOT exactly during
            # failover windows and double-count the tier's tokens
            n = len(req.tokens) - req._resumed_n
            tpot = ((req.t_last - req.t_first) / (n - 1) if n > 1 else None)
            self.metrics.observe_request(
                queue_wait_s=req.t_admit - req.t_enqueued,
                ttft_s=req.t_first - req.t_submit, tpot_s=tpot, tokens=n)
        elif state is RequestState.FAILED:
            self.metrics.bump(sm.FAILED)
        else:
            self.metrics.bump(sm.CANCELLED)
        with self._drain_cv:
            self._outstanding -= 1
            self._drain_cv.notify_all()

    # ---------------------------------------------------------- lifecycle

    def _idle(self) -> bool:
        return self.pool.active_count == 0 and self.scheduler.depth == 0

    def start(self) -> "ServingEngine":
        """Run the tick loop on a background thread (frontend mode)."""
        with self._lock:
            if self._thread is not None:
                return self
            self._stop_flag = False
            self._thread = threading.Thread(
                target=self._run, name="byteps-serve-engine", daemon=True)
            self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop_flag:
            try:
                self.step()
            except Exception as e:
                # a dead tick thread must not look like a hung one:
                # fail every in-flight and queued request loudly and
                # refuse new submissions — blocked result()/drain()
                # callers get the error instead of waiting forever
                bps_log.warning("serving engine tick failed: %r", e)
                self._fail_all(e)
                return
            with self._wake:
                if self._idle() and not self._stop_flag:
                    # named, so that a device-idle gap with nothing to
                    # do and one with the host busy read differently
                    with tracing.annotate(tracing.SPAN_TICK_IDLE_WAIT):
                        self._wake.wait(timeout=0.05)

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            self._engine_error = exc
            for slot in self.pool.active_slots():
                req = self._slot_req[slot]
                if req is not None:
                    req.error = exc
                    self._finish(req, RequestState.FAILED)
            # credit-FREE drain: admit() would skip queued tasks larger
            # than whatever credits the failed tick left, hanging their
            # result() callers forever
            for task in self.scheduler.drain_pending():
                task.request.error = exc
                self._finish(task.request, RequestState.FAILED)

    def stop(self, timeout: float = 10.0) -> None:
        self._stop_flag = True
        with self._wake:
            self._wake.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                # a wedged tick (e.g. a long compile) must not be
                # abandoned: clearing _thread would let a later start()
                # reset _stop_flag and spawn a SECOND tick loop beside
                # this one — leave it tracked, not restartable
                bps_log.warning(
                    "serving engine tick thread still running after "
                    "%.1fs; engine not restartable until it exits",
                    timeout)
            else:
                self._thread = None

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted request has finished.  Without a
        background thread, drives :meth:`step` inline (deterministic
        single-threaded mode)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        if self._thread is None:
            while True:
                with self._lock:
                    if self._outstanding == 0:
                        return
                self.step()
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("drain timed out")
        else:
            with self._drain_cv:
                while self._outstanding > 0:
                    if (deadline is not None
                            and time.monotonic() >= deadline):
                        raise TimeoutError("drain timed out")
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    self._drain_cv.wait(remaining)

    # --------------------------------------------------------- inspection

    @property
    def weights_fp(self) -> str:
        """Hex fingerprint of this engine's weights (serving/prefix.py
        ``weights_fingerprint`` — the same digest the prefix-store salt
        commits to).  Carried on the STATS reply as the engine's
        identity, so a ``ServeRouter`` can refuse a replica serving
        different weights instead of splicing silently-wrong resumes
        (docs/serving.md "Router tier").  Computed lazily and cached:
        prefix-cache engines already paid for it at construction."""
        if self._weights_fp is None:
            self._weights_fp = weights_fingerprint(self.variables).hex()
        return self._weights_fp

    def compile_counts(self) -> Dict[str, int]:
        """Trace counts of the step programs — steady-state serving must
        keep ``decode`` at ``decode_buckets`` (1 for dense engines and
        the fused-kernel paged path; the number of gather high-water
        buckets touched on the paged XLA fallback),
        ``prefill``/``chunk``/``verify`` at the number of distinct
        buckets touched, and the prefix copy/extract programs at 1 each
        (asserted by tests)."""
        return {"decode": self.decode_traces,
                "decode_buckets": (len(self._paged_decode_fns)
                                   if self.paged else 1),
                "prefill": self.prefill_traces,
                "prefill_buckets": len(self._prefill_fns),
                "chunk": self.chunk_traces,
                "chunk_buckets": len(self._chunk_fns),
                "verify": self.verify_traces,
                "verify_buckets": len(self._verify_fns),
                "prefix_copy": self.prefix_copy_traces,
                "prefix_extract": self.prefix_extract_traces,
                "block_cow": self.block_cow_traces}
