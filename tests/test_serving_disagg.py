"""Disaggregated prefill/decode tiers (byteps_tpu/serving/disagg/).

The correctness anchor: a request admitted to a prefill-role replica,
whose finished-prompt KV is shipped block-by-block over
``OP_KV_BLOCKS`` and adopted by the decode replica the router chose,
is token-identical to sequential ``generate()`` — greedy AND seeded
(docs/serving.md "Disaggregated tiers").  The rest: the stager's
refusal semantics (geometry, torn sequence, digest + bounded resend —
partial KV is never silently attended), ownership-transfer adoption
on the paged pool, and the registered receive-buffer pool on the
transport seam.

Chaos (prefill killed mid-ship) is slow-marked in
tests/test_router_chaos.py; this file is the fast tier-1 sibling.
"""

import json
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.engine.transport import RegisteredBufferPool
from byteps_tpu.inference import generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.observability.metrics import MetricsRegistry
from byteps_tpu.resilience.policy import RetryPolicy
from byteps_tpu.serving import (
    KVShipDigestError,
    KVShipGeometryError,
    KVShipSequenceError,
    KVStager,
    ServeMetrics,
    ServeRouter,
    ServingEngine,
)
from byteps_tpu.serving import metrics as sm
from byteps_tpu.serving import router as rt
from byteps_tpu.serving.disagg.ship import _digest, pool_geometry
from byteps_tpu.serving.frontend import serve

M = 8  # tokens per request (shared so generate() compiles once)


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), toks)
    return cfg, model, variables


@pytest.fixture(scope="module")
def prompts():
    # multi-block prompts (block=8): 2-3 blocks each, so every ship
    # moves more than one OP_KV_BLOCKS frame
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(20 + i), (9 + 4 * i,), 0, 61), np.int32)
        for i in range(4)]


@pytest.fixture(scope="module")
def greedy_refs(tiny, prompts):
    _, model, variables = tiny
    return [list(np.asarray(generate(model, variables, p[None], M,
                                     temperature=0.0)["tokens"])[0])
            for p in prompts[:2]]


def _paged_engine(tiny, temperature=0.0):
    _, model, variables = tiny
    return ServingEngine(model, variables, n_slots=4, max_seq=64,
                         temperature=temperature, paged=True, block=8,
                         chunk=16, metrics=ServeMetrics())


def _pool_used(engine):
    return engine.pool.alloc.used_count


# ------------------------------------------------- end-to-end bit-exactness


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_disagg_parity_prefill_ships_decode_adopts(tiny, prompts,
                                                   greedy_refs,
                                                   temperature):
    """One prefill-role + one decode-role replica behind a role-aware
    router: every request's KV is shipped and adopted (zero fallbacks)
    and the output is token-identical to sequential ``generate()`` —
    the shipped bytes ARE the prefill, nothing is re-derived."""
    _, model, variables = tiny
    # keep each call under the fast-tier budget: the seeded leg pays
    # extra sampling-path compiles, so it covers fewer prompts
    if temperature == 0.0:
        prompts, refs = prompts[:2], greedy_refs[:2]
    else:
        prompts = prompts[:1]
        refs = [list(np.asarray(generate(
            model, variables, p[None], M, temperature=temperature,
            rng=jax.random.PRNGKey(100 + i))["tokens"])[0])
            for i, p in enumerate(prompts)]
    engines = [_paged_engine(tiny, temperature) for _ in range(2)]
    srvs = [serve(e, 0, host="127.0.0.1", in_thread=True)[0]
            for e in engines]
    addrs = ["127.0.0.1:%d" % s.server_address[1] for s in srvs]
    base_used = [_pool_used(e) for e in engines]
    router = ServeRouter(
        addrs, roles=["prefill", "decode"], affinity=True, credits=4,
        deadline=30.0, stream_timeout=5.0, registry=MetricsRegistry(),
        retry=RetryPolicy(max_attempts=5, backoff_base=0.02,
                          jitter=0.0, backoff_cap=0.1, deadline=0.0))
    for rep in router._replicas:
        router._verify_replica_weights(rep, raising=True)
    try:
        for i, p in enumerate(prompts):
            got = list(router.stream(p, M, seed=100 + i))
            assert got == refs[i], (i, got, refs[i])
        st = router.stats()
        assert st["disagg"] is True
        assert st[rt.DISAGG_PREFILLS] == len(prompts)
        assert st[rt.DISAGG_SHIPPED_BLOCKS] >= 2 * len(prompts)
        assert st[rt.DISAGG_FALLBACKS] == 0
        assert st[rt.REDISPATCHES] == 0
        # the prefill replica shipped; the decode replica did not
        assert engines[0].metrics.get(sm.KV_BLOCKS_SHIPPED) >= 2 * len(
            prompts)
        assert engines[0].metrics.get(sm.KV_BLOCKS_SHIPPED_BYTES) > 0
        assert engines[1].metrics.get(sm.KV_BLOCKS_SHIPPED) == 0
        assert engines[0].metrics.summary()["ship_n"] == len(prompts)
        # no leaked blocks on either pool: parked KV was released after
        # the ship, adopted blocks were released when the slot retired
        assert [_pool_used(e) for e in engines] == base_used
    finally:
        router.close()
        for s in srvs:
            s.shutdown()
            s.server_close()


def test_disagg_single_token_request_short_circuits(tiny, prompts):
    """max_new_tokens=1 is satisfied entirely by the prefill leg's
    first token: the router returns without a decode dispatch and the
    TTL sweeper (not an attend) reclaims the staged blocks."""
    _, model, variables = tiny
    p = prompts[0]
    want = list(np.asarray(generate(model, variables, p[None], 1,
                                    temperature=0.0)["tokens"])[0])
    engines = [_paged_engine(tiny) for _ in range(2)]
    srvs = [serve(e, 0, host="127.0.0.1", in_thread=True)[0]
            for e in engines]
    addrs = ["127.0.0.1:%d" % s.server_address[1] for s in srvs]
    router = ServeRouter(
        addrs, roles=["prefill", "decode"], affinity=False, credits=4,
        deadline=30.0, stream_timeout=5.0, registry=MetricsRegistry())
    for rep in router._replicas:
        router._verify_replica_weights(rep, raising=True)
    try:
        assert list(router.stream(p, 1, seed=0)) == want
        st = router.stats()
        assert st[rt.DISAGG_PREFILLS] == 1
        assert st[rt.COMPLETED] == 1
        # the staged blocks are stranded by design; the decode-side
        # stager still knows about them until its TTL sweep
        stager = srvs[1].kv_stager()
        assert stager.stats()["staged"] == 1
        stager.ttl = 0.0
        assert stager.sweep() == 1
    finally:
        router.close()
        for s in srvs:
            s.shutdown()
            s.server_close()


# --------------------------------------------------------- stager refusals


@pytest.fixture()
def stager(tiny):
    e = _paged_engine(tiny)
    st = KVStager(e)
    yield e, st
    st.ttl = 0.0
    st.sweep()


def _block_payload(st, seed=0):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, st._block_bytes, dtype=np.uint8).tobytes()
    return raw, _digest([raw])


def _meta(key, i, n, geom, digest, pos=16):
    return json.dumps({"key": key, "i": i, "n": n, "pos": pos,
                       "geom": geom, "digest": digest})


def test_stager_refuses_geometry_mismatch(stager):
    e, st = stager
    raw, dig = _block_payload(st)
    with pytest.raises(KVShipGeometryError):
        st._accept(_meta("s1", 0, 2, "L2/B16/other", dig), raw)
    with pytest.raises(KVShipGeometryError):  # truncated payload
        st._accept(_meta("s1", 0, 2, pool_geometry(e), dig), raw[:-1])
    assert st.stats()["staged"] == 0


def test_stager_digest_refusal_is_resendable(stager):
    """A corrupt block is refused typed with the expected index
    UNCHANGED — the sender resends the same block and the staging
    completes; ``take`` transfers ownership of whole KV only."""
    e, st = stager
    geom = pool_geometry(e)
    raw0, dig0 = _block_payload(st, 0)
    raw1, dig1 = _block_payload(st, 1)
    ack = st._accept(_meta("s2", 0, 2, geom, dig0), raw0)
    assert ack == {"i": 0, "complete": False}
    with pytest.raises(KVShipDigestError):
        st._accept(_meta("s2", 1, 2, geom, "00" * 16), raw1)
    ack = st._accept(_meta("s2", 1, 2, geom, dig1), raw1)  # resend
    assert ack == {"i": 1, "complete": True}
    took = st.take("s2")
    assert took is not None and len(took["ids"]) == 2
    assert took["pos"] == 16
    e.release_kv_ids(took["ids"])
    assert st.take("s2") is None  # consumed


def test_stager_out_of_order_aborts_and_partial_never_adopted(stager):
    e, st = stager
    geom = pool_geometry(e)
    raw, dig = _block_payload(st)
    # a non-first block for an unknown ship is a torn staging
    with pytest.raises(KVShipSequenceError):
        st._accept(_meta("s3", 1, 3, geom, dig), raw)
    # out-of-order within a live staging aborts the WHOLE staging
    used0 = _pool_used(e)
    st._accept(_meta("s4", 0, 3, geom, dig), raw)
    assert _pool_used(e) == used0 + 3  # whole staging alloc'd up front
    with pytest.raises(KVShipSequenceError):
        st._accept(_meta("s4", 2, 3, geom, dig), raw)
    assert st.stats()["staged"] == 0
    assert _pool_used(e) == used0  # aborted staging released its blocks
    assert st.take("s4") is None


def test_adopt_blocks_is_ownership_transfer_with_typed_refusals(tiny):
    e = _paged_engine(tiny)
    pool = e.pool
    used0 = _pool_used(e)
    ids = e.stage_alloc(2)
    pool.adopt_blocks(0, ids)
    extra = e.stage_alloc(1)
    with pytest.raises(ValueError):  # table no longer empty
        pool.adopt_blocks(0, extra)
    with pytest.raises(ValueError):  # oversize refused before mutation
        pool.adopt_blocks(1, list(range(pool.tables[1].max_blocks + 1)))
    assert not pool.tables[1].blocks
    e.release_kv_ids(extra)  # refused adopt left ownership with caller
    with pool._lock:
        pool.reset_locked(0)  # releases adopted blocks like granted ones
    assert _pool_used(e) == used0  # ownership transfer, no leak


# --------------------------------------------------- int8 pools on the wire


def _int8_engine(tiny):
    _, model, variables = tiny
    return ServingEngine(model, variables, n_slots=4, max_seq=64,
                         temperature=0.0, paged=True, block=8,
                         chunk=16, kv_dtype="int8",
                         metrics=ServeMetrics())


def test_int8_geometry_contract_and_typed_dtype_refusal(tiny):
    """The geometry string carries the pool's dtype AND the scale-row
    leaves, so an int8 ship aimed at an fp32 pool (or vice versa) is
    refused typed BEFORE any block is allocated — and the int8 wire
    payload per block (s8 values + f32 scale rows, digest over those
    exact bytes) is well under half the fp32 one."""
    e8, e32 = _int8_engine(tiny), _paged_engine(tiny)
    geom8, geom32 = pool_geometry(e8), pool_geometry(e32)
    assert "int8" in geom8 and "k_scale" in geom8
    assert "int8" not in geom32 and geom8 != geom32
    st8, st32 = KVStager(e8), KVStager(e32)
    # wire bytes per block == pool accounting bytes per block
    assert st8._block_bytes == e8.pool.block_bytes
    assert st32._block_bytes == e32.pool.block_bytes
    assert st8._block_bytes < 0.35 * st32._block_bytes
    raw = np.zeros(st8._block_bytes, np.uint8).tobytes()
    used0 = _pool_used(e32)
    with pytest.raises(KVShipGeometryError):
        st32._accept(_meta("x8", 0, 2, geom8, _digest([raw])), raw)
    assert st32.stats()["staged"] == 0 and _pool_used(e32) == used0
    with pytest.raises(KVShipGeometryError):  # symmetric refusal
        st8._accept(_meta("x32", 0, 2, geom32, _digest([raw])), raw)
    assert st8.stats()["staged"] == 0


@pytest.mark.slow  # ~10s; test_int8_geometry_contract_and_typed_dtype_refusal keeps the int8 ship contract fast
def test_disagg_int8_ship_parity_and_shipped_bytes(tiny, prompts):
    """End-to-end int8 disagg: shipped s8+scale blocks adopted by the
    decode replica reproduce a single int8 engine's stream exactly
    (write-time quantization makes the shipped bytes THE prefill), and
    ``serve.kv_blocks_shipped_bytes`` reflects the shrunken blocks."""
    ps = prompts[:2]
    solo = _int8_engine(tiny)
    reqs = [solo.submit(p, M) for p in ps]
    solo.drain(timeout=120)
    refs = [list(np.asarray(r.result())) for r in reqs]
    engines = [_int8_engine(tiny) for _ in range(2)]
    srvs = [serve(e, 0, host="127.0.0.1", in_thread=True)[0]
            for e in engines]
    addrs = ["127.0.0.1:%d" % s.server_address[1] for s in srvs]
    router = ServeRouter(
        addrs, roles=["prefill", "decode"], affinity=True, credits=4,
        deadline=30.0, stream_timeout=5.0, registry=MetricsRegistry(),
        retry=RetryPolicy(max_attempts=5, backoff_base=0.02,
                          jitter=0.0, backoff_cap=0.1, deadline=0.0))
    try:
        for i, p in enumerate(ps):
            got = list(router.stream(p, M, seed=100 + i))
            assert got == refs[i], (i, got, refs[i])
        st = router.stats()
        assert st[rt.DISAGG_FALLBACKS] == 0
        shipped = engines[0].metrics.get(sm.KV_BLOCKS_SHIPPED)
        assert shipped >= 2 * len(ps)
        # every shipped block moved exactly block_bytes — the halved
        # int8 figure, not the fp32 one
        assert engines[0].metrics.get(sm.KV_BLOCKS_SHIPPED_BYTES) == \
            shipped * engines[0].pool.block_bytes
    finally:
        router.close()
        for s in srvs:
            s.shutdown()
            s.server_close()


# ------------------------------------------------- registered buffer pool


def test_registered_buffer_pool_roundtrip_and_reuse():
    pool = RegisteredBufferPool(max_buffers=2)
    b = pool.acquire(5000)
    assert len(b) >= 5000 and pool.stats()["misses"] == 1
    pool.release(b)
    b2 = pool.acquire(4097)  # same power-of-2 bucket -> reuse
    assert b2 is b and pool.stats()["hits"] == 1
    pool.release(b2)

    a, bsock = socket.socketpair()
    try:
        payload = bytes(range(256)) * 16
        a.sendall(payload)
        view = pool.recv_exact(bsock, len(payload))
        assert isinstance(view, memoryview)
        assert bytes(view) == payload
        pool.recycle(view)
        assert pool.stats()["free_buffers"] >= 1
    finally:
        a.close()
        bsock.close()


def test_registered_buffer_pool_eof_is_connection_error():
    pool = RegisteredBufferPool()
    a, bsock = socket.socketpair()
    a.sendall(b"xy")
    a.close()
    try:
        with pytest.raises(ConnectionError):
            pool.recv_exact(bsock, 10)
        assert pool.stats()["free_buffers"] >= 1  # buffer not leaked
    finally:
        bsock.close()
