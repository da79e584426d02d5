"""Hierarchical push/pull (docs/wire.md "Hierarchical reduction"): the
slice math, the ``name@s{r}`` slice keying of RemoteStore mutations, the
slice↔partition boundary interaction, the jitted scatter/gather group
exchange, the BYTEPS_LOCAL_RANK/SIZE init validation, and the
hierarchical-on-vs-off bit-exactness parity anchor (plus its scripted
drop_after chaos-replay variant — the fast tier-1 edition of
``chaos_smoke --hierarchical``).
"""

import dataclasses

import numpy as np
import pytest

from byteps_tpu.common.config import (Config, get_config, reset_config,
                                      set_config)
from byteps_tpu.compression import reset_compression_stats
from byteps_tpu.engine import hierarchical as hier
from byteps_tpu.engine import ps_server
from byteps_tpu.resilience import (FaultInjectingProxy, ResilienceCounters,
                                   RetryPolicy, reset_counters)
from byteps_tpu.resilience import counters as cn


@pytest.fixture(autouse=True)
def _fresh_state():
    reset_config()
    reset_counters()
    reset_compression_stats()
    yield
    reset_config()
    reset_counters()
    reset_compression_stats()


def _x(n=256, seed=0, dtype=np.float32):
    return np.random.default_rng(seed).standard_normal(n).astype(dtype)


def _spawn():
    srv, _ = ps_server.serve(0, host="127.0.0.1", use_native=False,
                             in_thread=True)
    return srv, f"127.0.0.1:{srv.server_address[1]}"


def _fast_policy(**kw):
    kw.setdefault("max_attempts", 6)
    kw.setdefault("backoff_base", 0.01)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("deadline", 20.0)
    return RetryPolicy(**kw)


def _hier_cfg(**kw):
    kw.setdefault("hierarchical", True)
    kw.setdefault("hierarchical_min_bytes", 1)
    kw.setdefault("local_size", 4)
    return Config(**kw)


def _mesh(n=4):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), axis_names=("dp",))


# --------------------------------------------------------------- slice math


def test_slice_spans_even_and_ragged():
    assert hier.slice_spans(16, 4) == [(0, 4), (4, 8), (8, 12), (12, 16)]
    # non-divisible leading dim: equal ceil chunks, ragged last slice
    assert hier.slice_spans(10, 4) == [(0, 3), (3, 6), (6, 9), (9, 10)]
    assert hier.slice_spans(7, 2) == [(0, 4), (4, 7)]
    # spans tile [0, n) exactly, in order
    for n, L in [(17, 4), (1000, 8), (9, 3), (31, 5)]:
        spans = hier.slice_spans(n, L)
        assert spans[0][0] == 0 and spans[-1][1] == n
        assert all(a < b for a, b in spans)  # every slice non-empty
        assert all(spans[i][1] == spans[i + 1][0]
                   for i in range(len(spans) - 1))


def test_slice_spans_degenerate_cases():
    assert hier.slice_spans(100, 1) is None          # no group
    assert hier.slice_spans(0, 4) is None            # empty tensor
    # an empty trailing slice would be a key nobody pushes: refused
    assert hier.slice_spans(5, 4) is None            # ceil=2, 3*2 >= 5
    assert hier.slice_spans(3, 4) is None
    assert hier.slice_spans(4, 4) == [(0, 1), (1, 2), (2, 3), (3, 4)]


def test_slice_name_parsing():
    assert hier.slice_name("layer.w", 3) == "layer.w@s3"
    assert hier.parse_slice_rank("w@s2", "w") == 2
    assert hier.parse_slice_rank("w@s2#p1", "w") == 2  # partitioned slice
    assert hier.parse_slice_rank("w2@s1", "w") is None
    assert hier.parse_slice_rank("w@sx", "w") is None
    assert hier.is_sliced_name("w@s0") and hier.is_sliced_name("w#p1")
    # ZeRO span keys (training/zero.py) are already 1/world units: the
    # hierarchical layer must never re-slice them
    assert hier.is_sliced_name("w@z1")
    assert not hier.is_sliced_name("plain.w")


def test_eligibility_gates():
    assert not hier.eligible(np.float32(3.0)[()], 4, 1)      # 0-d scalar
    assert not hier.eligible(np.ones(4, np.float32), 4, 1024)  # threshold
    assert hier.eligible(np.ones(1024, np.float32), 4, 1024)
    assert not hier.eligible(np.ones(1024, np.float32), 1, 1)  # L==1


# ----------------------------------------------- RemoteStore slice keying


def test_store_slices_eligible_tensor_and_reassembles():
    set_config(_hier_cfg())
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    x = _x(10)
    st.init_tensor("w", np.zeros(10, np.float32))
    out = st.push_pull("w", x)
    np.testing.assert_array_equal(out, x)
    # the store holds ONLY slice keys — ragged last slice included
    assert sorted(st.names()) == [f"w@s{r}" for r in range(4)]
    np.testing.assert_array_equal(st.pull("w"), x)
    # per-slice version counters answer through slice 0
    assert st.version("w") == 1
    st.close(); srv.shutdown(); srv.server_close()


def test_subthreshold_and_scalars_pass_through_unsliced():
    set_config(_hier_cfg(hierarchical_min_bytes=1024))
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    small = _x(16)             # 64B < 1024
    st.init_tensor("small", np.zeros(16, np.float32))
    np.testing.assert_array_equal(st.push_pull("small", small), small)
    scalar = np.float32(2.5)[()]
    st.init_tensor("scalar", np.zeros((), np.float32))
    assert st.push_pull("scalar", scalar) == scalar
    assert sorted(st.names()) == ["scalar", "small"]  # base keys, unsliced
    st.close(); srv.shutdown(); srv.server_close()


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32])
def test_dtype_preserved_through_slice_wire_roundtrip(dtype):
    set_config(_hier_cfg())
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    if np.issubdtype(dtype, np.floating):
        x = _x(24, dtype=dtype)
    else:
        x = np.arange(24, dtype=dtype) - 7
    st.init_tensor("t", np.zeros(24, dtype))
    out = st.push_pull("t", x)
    assert out.dtype == dtype
    np.testing.assert_array_equal(out, x)
    pulled = st.pull("t")
    assert pulled.dtype == dtype
    np.testing.assert_array_equal(pulled, x)
    st.close(); srv.shutdown(); srv.server_close()


def test_slice_partition_boundary_interaction():
    """BYTEPS_PARTITION_BYTES below the slice size: every slice further
    splits into ``name@s{r}#p{i}`` parts; reassembly must still be
    exact, and the keyspace shows both layers."""
    set_config(_hier_cfg(partition_bytes=32, partition_align=8))
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    x = _x(40)  # slices of 10 elems = 40B > 32B bound -> 2 parts each
    st.init_tensor("w", np.zeros(40, np.float32))
    out = st.push_pull("w", x)
    np.testing.assert_array_equal(out, x)
    names = sorted(st.names())
    assert "w@s0#p0" in names and "w@s0#p1" in names
    assert all(hier.parse_slice_rank(n, "w") is not None for n in names)
    np.testing.assert_array_equal(st.pull("w"), x)
    assert st.version("w") == 1
    st.close(); srv.shutdown(); srv.server_close()


def test_multidim_tensor_slices_on_flat_element_space():
    set_config(_hier_cfg())
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    x = _x(30).reshape(5, 6)
    st.init_tensor("m", np.zeros((5, 6), np.float32))
    out = st.push_pull("m", x)
    assert out.shape == (5, 6)
    np.testing.assert_array_equal(out, x)
    np.testing.assert_array_equal(st.pull("m"), x)
    st.close(); srv.shutdown(); srv.server_close()


def test_pull_side_discovery_of_foreign_sliced_tensor():
    """A client that never pushed a sliced tensor reassembles it from
    the ``name@s{r}`` keys via names() discovery (flat, like the
    partition discovery path)."""
    set_config(_hier_cfg())
    srv, addr = _spawn()
    writer = ps_server.RemoteStore([addr])
    x = _x(12)
    writer.init_tensor("w", np.zeros(12, np.float32))
    writer.push_pull("w", x)
    reader = ps_server.RemoteStore([addr])
    out = reader.pull("w")   # no meta: discovery kicks in
    np.testing.assert_array_equal(out.reshape(-1), x)
    assert reader.version("w") == 1
    writer.close(); reader.close(); srv.shutdown(); srv.server_close()


def test_push_pull_slices_partial_rank_is_additive():
    """The multi-process contract: a caller pushing ONLY its rank's
    slice touches just that key, and the per-slice sums line up with
    the full-group state."""
    set_config(_hier_cfg())
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    x = _x(16)
    st.init_tensor("w", np.zeros(16, np.float32))
    st.push_pull("w", x)
    # rank 2 pushes only its slice (elements 8:12)
    delta = np.full(4, 10.0, np.float32)
    out = st.push_pull_slices("w", {2: delta}, 4)
    assert set(out) == {2}
    np.testing.assert_allclose(out[2], x[8:12] + 10.0)
    full = st.pull("w")
    np.testing.assert_allclose(full[8:12], x[8:12] + 10.0)
    np.testing.assert_array_equal(full[:8], x[:8])
    st.close(); srv.shutdown(); srv.server_close()


# ------------------------------------------------------ parity anchor


def _train(store, steps, targets):
    state = {n: np.zeros_like(t) for n, t in targets.items()}
    for n in targets:
        store.init_tensor(n, state[n])
    for _ in range(steps):
        for n, t in targets.items():
            state[n] = store.push_pull(
                n, (0.2 * (t - state[n])).astype(t.dtype))
    return {n: store.pull(n) for n in targets}


def test_parity_hierarchical_on_vs_off_bit_exact():
    """THE acceptance anchor: dense fp32 single-writer training through
    a sliced store must be bit-for-bit identical to the unsliced store —
    slicing is an elementwise partition, so the server performs the
    same adds on the same values either way."""
    targets = {"w": _x(37, seed=1), "b": _x(128, seed=2),
               "tiny": _x(3, seed=3)}  # tiny: pass-through inside hier run

    def run(hier_on):
        set_config(_hier_cfg() if hier_on else Config())
        srv, addr = _spawn()
        st = ps_server.RemoteStore([addr])
        out = _train(st, 15, targets)
        st.close(); srv.shutdown(); srv.server_close()
        reset_config()
        return out

    on, off = run(True), run(False)
    for n in targets:
        assert on[n].tobytes() == off[n].tobytes(), (
            f"{n}: hierarchical-on diverged from off "
            f"(max |d| = {np.abs(on[n] - off[n]).max()})")


def test_hierarchical_scripted_drop_replay_bit_exact():
    """Fast tier-1 edition of ``chaos_smoke --hierarchical``: scripted
    drop_after faults (slice mutation applied, reply lost, connection
    reset) on sliced PUSH_PULL frames must be version-guard deduped
    per slice — the faulted run ends bit-for-bit equal to the clean
    run."""
    target = _x(24, seed=5)

    def run(script=None):
        set_config(_hier_cfg())
        srv, addr = _spawn()
        proxy = counters = None
        if script is not None:
            proxy = FaultInjectingProxy(addr, seed=0)
            proxy.script(*script)
            counters = ResilienceCounters()
            addr = proxy.addr
        st = ps_server.RemoteStore([addr], retry_policy=_fast_policy(),
                                   counters=counters)
        out = _train(st, 12, {"w": target})
        st.close()
        faults = 0
        if proxy is not None:
            faults = proxy.faults_injected
            proxy.close()
        srv.shutdown(); srv.server_close()
        reset_config()
        return out["w"], faults, counters

    clean, _, _ = run()
    # requests: 4 INIT slices then 4 slice PUSH_PULLs per step — fault
    # three of the mutating slice frames across different steps/ranks
    script = ["pass"] * 60
    for i in (5, 14, 23):
        script[i] = "drop_after"
    chaos, faults, counters = run(script)
    assert faults == 3
    assert counters.snapshot().get(cn.DEDUP, 0) >= 1
    assert clean.tobytes() == chaos.tobytes(), (
        f"sliced chaos run diverged (max |d| = "
        f"{np.abs(clean - chaos).max()})")


def test_hierarchical_compressed_per_slice_residuals():
    """EF residuals live per slice key: a compressed hierarchical push
    keeps one residual per ``name@s{r}`` (never a base-name residual),
    so slices never share (or double-fold) error state."""
    from byteps_tpu.compression import CompressionPolicy

    set_config(_hier_cfg())
    srv, addr = _spawn()
    comp = CompressionPolicy(default="onebit", min_bytes=1, ratio=0.25,
                             seed=0)
    st = ps_server.RemoteStore([addr], compression=comp)
    x = _x(32, seed=9)
    st.init_tensor("w", np.zeros(32, np.float32))
    st.push_pull("w", x)
    assert st._compressor.residual_norm("w") == 0.0
    norms = [st._compressor.residual_norm(f"w@s{r}") for r in range(4)]
    assert all(n > 0 for n in norms)
    st.close(); srv.shutdown(); srv.server_close()


# ------------------------------------------------- group-level exchange


def test_local_scatter_gather_jitted_roundtrip():
    """The two jitted stages pair exactly: psum_scatter over the local
    axis leaves rank r holding slice r of the member sum, and
    all_gather rebuilds the full buffer replicated — on the SAME
    slice boundaries hier.slice_spans describes (the multi-process
    rebuild path, driven directly since the single-controller exchange
    short-circuits it)."""
    from byteps_tpu.parallel import collectives

    mesh = _mesh()
    L, n = 4, 12
    stacked = np.stack([_x(n, seed=i) for i in range(L)])
    scattered = collectives.local_reduce_scatter(stacked, mesh, "dp")
    np.testing.assert_allclose(np.asarray(scattered), stacked.sum(0),
                               rtol=1e-6)
    chunk = hier.slice_chunk(n, L)
    for r, (a, b) in enumerate(hier.slice_spans(n, L)):
        shard = [s for s in scattered.addressable_shards
                 if (s.index[0].start or 0) == r * chunk]
        np.testing.assert_allclose(np.asarray(shard[0].data)[: b - a],
                                   stacked.sum(0)[a:b], rtol=1e-6)
    full = collectives.local_all_gather(np.asarray(scattered), mesh, "dp")
    np.testing.assert_allclose(np.asarray(full), stacked.sum(0),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="multiple"):
        collectives.local_reduce_scatter(stacked[:, :10], mesh, "dp")


def test_group_exchange_sums_and_accumulates():
    from byteps_tpu.engine.async_ps import AsyncParameterServer

    mesh = _mesh()
    store = AsyncParameterServer(use_native=False)
    stacked = np.stack([_x(10, seed=i) for i in range(4)])
    out = hier.hierarchical_push_pull(store, "g", stacked, mesh,
                                      min_bytes=1)
    np.testing.assert_allclose(np.asarray(out), stacked.sum(0),
                               rtol=1e-6)
    # slice keys on the store; ragged last slice (10 = 3+3+3+1)
    assert sorted(store.names()) == [f"g@s{r}" for r in range(4)]
    out2 = hier.hierarchical_push_pull(store, "g", stacked, mesh,
                                       min_bytes=1)
    np.testing.assert_allclose(np.asarray(out2), 2 * stacked.sum(0),
                               rtol=1e-6)


def test_group_exchange_average_and_shape_dtype():
    from byteps_tpu.engine.async_ps import AsyncParameterServer

    mesh = _mesh()
    store = AsyncParameterServer(use_native=False)
    stacked = np.stack([_x(24, seed=i).reshape(4, 6) for i in range(4)])
    out = hier.hierarchical_push_pull(store, "g", stacked, mesh,
                                      min_bytes=1, average=True)
    assert out.shape == (4, 6) and out.dtype == np.float32
    np.testing.assert_allclose(np.asarray(out), stacked.mean(0),
                               rtol=1e-5)


def test_group_exchange_matches_remote_store_slicing():
    """The group exchange and the store-internal slicing agree on the
    slice layout: pushing through one and pulling through the other
    yields the same bytes."""
    mesh = _mesh()
    set_config(_hier_cfg())
    srv, addr = _spawn()
    st = ps_server.RemoteStore([addr])
    stacked = np.stack([_x(10, seed=i) for i in range(4)])
    out = hier.hierarchical_push_pull(st, "g", stacked, mesh, min_bytes=1)
    np.testing.assert_allclose(np.asarray(out), stacked.sum(0), rtol=1e-6)
    pulled = st.pull("g")
    np.testing.assert_allclose(pulled.reshape(-1), np.asarray(out),
                               rtol=1e-6)
    st.close(); srv.shutdown(); srv.server_close()


def test_group_exchange_multiprocess_rebuild_branch(monkeypatch):
    """The multi-process rebuild leg of ``hierarchical_push_pull`` —
    NamedSharding over the local axis, concat of the addressable ranks'
    pulled slices, ``make_array_from_process_local_data``, jitted
    ``all_gather`` — driven on a single controller by mocking the
    process count.  Every rank is addressable here, so the
    process-local buffer is the full padded tensor and the branch must
    reproduce the single-controller short-circuit bit-for-bit (same
    slice keys on the store, same replicated result)."""
    import jax

    from byteps_tpu.engine.async_ps import AsyncParameterServer

    mesh = _mesh()
    stacked = np.stack([_x(10, seed=i) for i in range(4)])
    ref_store = AsyncParameterServer(use_native=False)
    ref = np.asarray(hier.hierarchical_push_pull(
        ref_store, "g", stacked, mesh, min_bytes=1))

    store = AsyncParameterServer(use_native=False)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    out = hier.hierarchical_push_pull(store, "g", stacked, mesh,
                                      min_bytes=1)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(out), ref)
    # the wire half is identical to the single-controller path: one
    # slice key per rank, ragged last slice included
    assert sorted(store.names()) == [f"g@s{r}" for r in range(4)]
    # a second exchange through the same branch accumulates (PS
    # semantics survive the rebuild path)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    out2 = hier.hierarchical_push_pull(store, "g", stacked, mesh,
                                       min_bytes=1)
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(out2), 2 * stacked.sum(0),
                               rtol=1e-6)


def test_group_exchange_ineligible_falls_back_unsliced():
    from byteps_tpu.engine.async_ps import AsyncParameterServer

    mesh = _mesh()
    store = AsyncParameterServer(use_native=False)
    stacked = np.stack([np.full((), float(i), np.float32)
                        for i in range(4)])
    out = hier.hierarchical_push_pull(store, "s", stacked, mesh)
    assert np.asarray(out) == pytest.approx(6.0)
    assert store.names() == ["s"]  # unsliced base key


def test_api_push_pull_hierarchical_eager_ps_path(monkeypatch):
    """api.push_pull(hierarchical=True) in async-PS mode rides the
    sliced wire path and returns the accumulated global state."""
    import byteps_tpu as bps
    from byteps_tpu.engine.async_ps import (AsyncParameterServer,
                                            set_async_store,
                                            reset_async_store)

    set_config(Config(enable_async=True, hierarchical_min_bytes=1))
    store = AsyncParameterServer(use_native=False)
    set_async_store(store)
    try:
        bps.init()
        n = bps.size()
        stacked = np.stack([_x(64, seed=i) for i in range(n)])
        out = bps.push_pull(stacked, average=False, name="hpp",
                            hierarchical=True)
        np.testing.assert_allclose(np.asarray(out), stacked.sum(0),
                                   rtol=1e-5)
        assert any(hier.SLICE_SEP in nm for nm in store.names())
    finally:
        bps.shutdown()
        reset_async_store()


# ------------------------------------------------- init validation


def test_init_validates_local_rank_against_process_reality():
    import byteps_tpu as bps

    set_config(Config(local_rank=2))  # single process claiming rank 2
    with pytest.raises(ValueError, match="slice"):
        bps.init()
    bps.shutdown()


def test_init_validates_local_size_against_mesh_reality():
    import byteps_tpu as bps
    import jax

    set_config(Config(local_size=jax.local_device_count() * 2))
    with pytest.raises(ValueError, match="devices"):
        bps.init()
    bps.shutdown()


def test_init_validates_rank_inside_size():
    import byteps_tpu as bps

    set_config(Config(local_rank=4, local_size=4))
    with pytest.raises(ValueError, match="out of range"):
        bps.init()
    bps.shutdown()


def test_init_accepts_consistent_local_contract():
    import byteps_tpu as bps

    set_config(Config(local_rank=0, local_size=4))
    bps.init()
    assert bps.local_size() == 4
    bps.shutdown()


# ------------------------------------------------- optimizer local axis


def test_distributed_optimizer_validates_local_axis():
    import optax

    from byteps_tpu.training.optimizer import (DistributedOptimizer,
                                               resolve_local_axis)

    assert resolve_local_axis(("dcn", "dp"), None) == ("dp", ("dcn",))
    assert resolve_local_axis(("dcn", "dp"), "dcn") == ("dcn", ("dp",))
    with pytest.raises(ValueError, match="local_axis"):
        resolve_local_axis(("dp",), "tp")
    with pytest.raises(ValueError, match="local_axis"):
        DistributedOptimizer(optax.sgd(0.1), axis_name=("dcn", "dp"),
                             local_axis="tp")


def test_train_step_with_explicit_local_axis_matches_default():
    """Pinning local_axis to the innermost axis explicitly is the
    default layout — the two steps must produce identical params."""
    import jax.numpy as jnp
    import optax

    from byteps_tpu.parallel.mesh import build_mesh
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    mesh = build_mesh(force_distributed=True)  # dcn(2) x dp(4)

    def loss_fn(params, mstate, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred[:, 0] - batch["y"]) ** 2), mstate

    params = {"w": jnp.full((8, 8), 0.02, jnp.float32)}
    batch = shard_batch({"x": jnp.ones((16, 8)), "y": jnp.zeros((16,))},
                        mesh, axes=("dcn", "dp"))

    outs = []
    for la in (None, "dp"):
        step = make_data_parallel_step(
            loss_fn, optax.sgd(0.1), mesh, axes=("dcn", "dp"),
            local_axis=la, donate=False)
        state = step.init_state(
            {"w": jnp.array(params["w"])})
        state, _ = step(state, batch)
        outs.append(np.asarray(state.params["w"]))
    np.testing.assert_array_equal(outs[0], outs[1])
