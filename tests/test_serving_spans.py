"""The serving tick from inside (ISSUE 36): host spans on the tick thread
and around ``submit()``'s lock, phase counters on the STATS reply, and
the scopes of the serve programs.

``annotate`` is recorded by a stub (name, arguments, thread, start and
end, parent on the same thread), so nothing here needs a profiler
session; what a real trace looks like is held by
``tests/benchmark/test_perfbench_tick_spans.py``.  No test times
anything against a wall-clock bar: the one sleep (50 ms with the
engine's lock held) is the cause whose effect the histogram must show.
"""

import contextlib
import re
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import tracing
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.serving import (RemoteServeClient, ServeMetrics,
                                ServingEngine)
from byteps_tpu.serving import metrics as sm
from byteps_tpu.serving.frontend import serve

ENGINES = {
    "dense": dict(),
    "dense_chunked": dict(chunk=8),
    "paged_gather": dict(paged=True, block=8, paged_kernel="off"),
    "paged_kernel": dict(paged=True, block=8, paged_kernel="on", chunk=8),
}
PASS = ["blocks", "build", "launch", "readback", "emit"]


class Recorder:
    """What ``tracing.annotate`` would have put on the trace."""

    def __init__(self):
        self.events = []
        self._tls = threading.local()

    @contextlib.contextmanager
    def __call__(self, name, **args):
        stack = self._tls.__dict__.setdefault("stack", [])
        ev = {"name": name, "args": dict(args),
              "thread": threading.current_thread().name,
              "parent": stack[-1] if stack else None,
              "t0": time.perf_counter(), "t1": None}
        self.events.append(ev)
        stack.append(ev)

        class Span:
            set_metadata = staticmethod(ev["args"].update)

        try:
            yield Span
        finally:
            ev["t1"] = time.perf_counter()
            stack.pop()

    def named(self, name):
        return [e for e in self.events if e["name"] == name]

    def children(self, parent):
        return [e for e in self.events if e["parent"] is parent]


@pytest.fixture()
def spans(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "annotate", rec)
    return rec


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables


def engine(tiny, kind="dense", **kw):
    model, variables = tiny
    return ServingEngine(model, variables, **{
        **dict(n_slots=4, max_seq=64, temperature=0.0,
               min_prefill_bucket=8, metrics=ServeMetrics()),
        **ENGINES[kind], **kw})


def prompt(n, seed=0):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 61), np.int32)


def run_ticks(eng):
    """Drive the engine inline; ``[(step's answer, wall seconds)]``."""
    out = []
    while eng._outstanding:
        t0 = time.perf_counter()
        res = eng.step()
        out.append((res, time.perf_counter() - t0))
    return out


# ------------------------------------------------------------- the tick


@pytest.mark.parametrize("kind", list(ENGINES))
def test_a_working_tick_names_its_phases_in_order_and_nested(
        tiny, spans, kind):
    eng = engine(tiny, kind)
    reqs = [eng.submit(prompt(5 + 9 * i, i), 4) for i in range(2)]
    ticks = run_ticks(eng)
    assert all(r.done for r in reqs)
    tick_spans = spans.named(tracing.SPAN_TICK)
    assert len(tick_spans) == len(ticks)        # every tick had work
    paged = "paged" in kind
    for span, (res, _) in zip(tick_spans, ticks):
        assert span["parent"] is None
        assert span["thread"] == threading.current_thread().name
        # what is known at the start, and what set_metadata adds
        assert set(span["args"]) == {"active", "queued", "admitted",
                                     "emitted"}
        assert span["args"]["admitted"] == res["admitted"]
        assert span["args"]["emitted"] == res["emitted"]
        kids = spans.children(span)
        names = [k["name"] for k in kids]
        # continuation chunks, then the grant and each admission with
        # its chunks, then the decode pass, then the accounting: last
        assert names[-1] == tracing.SPAN_TICK_ACCOUNT
        assert names.count(tracing.SPAN_TICK_ACCOUNT) == 1
        assert names.count(tracing.SPAN_TICK_DECODE) <= 1
        order = "".join({tracing.SPAN_TICK_PREFILL: "p",
                         tracing.SPAN_TICK_ADMIT: "a",
                         tracing.SPAN_TICK_DECODE: "d",
                         tracing.SPAN_TICK_ACCOUNT: "c"}[n] for n in names)
        assert re.fullmatch(r"p*(a(ap*)*)?d?c", order), order
        for k in kids:
            assert span["t0"] <= k["t0"] <= k["t1"] <= span["t1"]
            inner = [c["name"] for c in spans.children(k)]
            if k["name"] == tracing.SPAN_TICK_DECODE:
                want = PASS if paged else PASS[1:]
                assert inner == [f"{k['name']}/{s}" for s in want]
                assert k["args"]["slots"] >= 1
            elif k["name"] == tracing.SPAN_TICK_PREFILL:
                assert inner[:2] == [f"{k['name']}/build",
                                     f"{k['name']}/launch"]
                assert inner[2:] in ([], [f"{k['name']}/readback"])
                assert set(k["args"]) == {"req", "bucket", "start"}
            else:
                assert inner == []
            for c in spans.children(k):
                assert k["t0"] <= c["t0"] <= c["t1"] <= k["t1"]
    # a first token is read back exactly once a request
    readbacks = spans.named(tracing.SPAN_TICK_PREFILL + "/readback")
    assert len(readbacks) == len(reqs)
    assert sum(s["args"]["admitted"] for s in tick_spans) == len(reqs)
    assert sum(s["args"]["emitted"] for s in tick_spans) == sum(
        len(r.tokens) for r in reqs)


def test_a_speculative_tick_opens_verify_inside_decode(tiny, spans):
    eng = engine(tiny, "paged_gather", spec_k=4)
    # a proposer that always has a guess: whether the model accepts it
    # is beside the point, the widened pass must run
    eng.spec = types.SimpleNamespace(
        k=4, propose=lambda ctx, cap: [1, 2][:cap])
    eng.submit(prompt(9), 8)
    run_ticks(eng)
    verifies = spans.named(tracing.SPAN_TICK_VERIFY)
    assert verifies and eng.metrics.get(sm.SPEC_VERIFY_TICKS) == len(
        verifies)
    for v in verifies:
        assert v["parent"]["name"] == tracing.SPAN_TICK_DECODE
        assert v["args"]["proposals"] >= 1
        # the decode pass granted its blocks, then handed the tick over
        assert [c["name"] for c in spans.children(v["parent"])] == [
            tracing.SPAN_TICK_DECODE + "/blocks", tracing.SPAN_TICK_VERIFY]
        assert [c["name"] for c in spans.children(v)] == [
            f"{tracing.SPAN_TICK_VERIFY}/{s}" for s in PASS]


def test_an_idle_poll_emits_only_idle_wait(tiny, spans):
    eng = engine(tiny)
    assert eng.step()["active"] == 0 and spans.events == []
    assert eng.metrics.get(sm.TICKS_WORKED) == 0
    eng.start()
    try:
        deadline = time.monotonic() + 30.0
        while (len(spans.named(tracing.SPAN_TICK_IDLE_WAIT)) < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()
    assert {e["name"] for e in spans.events} == {
        tracing.SPAN_TICK_IDLE_WAIT}
    assert {e["thread"] for e in spans.events} == {"byteps-serve-engine"}


def test_a_request_is_followed_by_its_id_across_both_threads(tiny, spans):
    eng = engine(tiny, "paged_kernel").start()
    try:
        other = eng.submit(prompt(6, 1), 2)
        req = eng.submit(prompt(20, 2), 3)      # chunks of 8: 0, 8, 16
        req.result(timeout=120)
        other.result(timeout=120)
    finally:
        eng.stop()
    mine = [e for e in spans.events if e["args"].get("req") == req.id]
    assert [e["name"] for e in mine] == [
        tracing.SPAN_SUBMIT_ENQUEUE, tracing.SPAN_TICK_ADMIT
    ] + [tracing.SPAN_TICK_PREFILL] * 3
    assert [e["args"]["start"] for e in mine[2:]] == [0, 8, 16]
    assert {e["args"]["bucket"] for e in mine[2:]} == {8}
    assert mine[0]["thread"] == threading.current_thread().name
    assert {e["thread"] for e in mine[1:]} == {"byteps-serve-engine"}
    # submit() from its first line: lock_wait, then enqueue inside it
    enqueue = mine[0]
    submit = enqueue["parent"]
    assert submit["name"] == tracing.SPAN_SUBMIT
    assert [c["name"] for c in spans.children(submit)] == [
        tracing.SPAN_SUBMIT_LOCK_WAIT, tracing.SPAN_SUBMIT_ENQUEUE]
    assert "req" not in submit["args"]          # the id exists only inside


# ----------------------------------------------------- stamps and counters


def test_ttft_contains_the_lock_wait_and_the_queue_wait_does_not(
        tiny, monkeypatch):
    eng = engine(tiny)
    tokens = prompt(5)
    held, waiting = threading.Event(), threading.Event()
    real = tracing.annotate

    @contextlib.contextmanager
    def annotate(name, **args):
        if name == tracing.SPAN_SUBMIT_LOCK_WAIT:
            waiting.set()
        with real(name, **args) as span:
            yield span

    monkeypatch.setattr(tracing, "annotate", annotate)

    def hold():
        with eng._lock:
            held.set()
            waiting.wait(30.0)      # the submit is at the lock: now
            time.sleep(0.05)        # make it wait 50 ms more

    t = threading.Thread(target=hold)
    t.start()
    held.wait(30.0)
    req = eng.submit(tokens, 2)         # blocks until hold() lets go
    t.join()
    run_ticks(eng)
    s = eng.metrics.summary()
    assert s["submit_lock_wait_n"] == s["ttft_n"] == s["queue_wait_n"] == 1
    lock_wait = s["submit_lock_wait_p50_s"]
    assert lock_wait >= 0.04
    assert req.t_submit < req.t_enqueued <= req.t_admit <= req.t_first
    assert req.t_enqueued - req.t_submit >= 0.04
    assert s["queue_wait_p50_s"] == pytest.approx(
        req.t_admit - req.t_enqueued)
    # the two clocks (perf_counter for the wait, monotonic for the
    # request's stamps) read the same interval to well under 1 ms
    assert s["ttft_p50_s"] >= lock_wait + s["queue_wait_p50_s"] - 1e-3


@pytest.mark.parametrize("kind", ["dense", "paged_kernel"])
def test_phase_seconds_sum_to_no_more_than_the_ticks_wall_time(tiny, kind):
    eng = engine(tiny, kind)
    for i in range(3):
        eng.submit(prompt(5 + 7 * i, i), 5)
    before = eng.metrics.summary()
    assert before[sm.TICK_SECONDS] == {} and before[sm.TICKS_WORKED] == 0
    ticks = run_ticks(eng)
    eng.step()                                  # an idle tick counts nowhere
    after = eng.metrics.summary()
    assert after[sm.TICKS_WORKED] == len(ticks)
    phases = after[sm.TICK_SECONDS]
    assert set(phases) == set(tracing.TICK_PHASES)
    assert all(v >= 0.0 for v in phases.values())
    busy = {"prefill_build", "prefill_launch", "prefill_readback", "admit",
            "build", "launch", "readback", "emit", "account"}
    assert all(phases[p] > 0.0 for p in busy)
    assert (phases["blocks"] > 0.0) == ("paged" in kind)
    assert sum(phases.values()) <= sum(w for _, w in ticks)
    # registry-only, labelled as the labelled metrics beside them are
    snap = eng.metrics.registry.snapshot()["counters"]
    assert snap[f"{sm.TICK_SECONDS}{{phase=launch}}"] == phases["launch"]
    assert snap[sm.TICKS_WORKED] == len(ticks)
    assert "byteps_serve_tick_seconds_total{phase=\"launch\"}" in (
        eng.metrics.registry.to_prometheus())


def test_a_reset_does_not_leave_the_tick_counters_counting_unseen():
    """``ServeMetrics`` looks the tick's counters up once; a
    ``reset_serve_metrics()`` removes them from the registry, and an
    engine that outlives it must count on in sight, from zero."""
    from byteps_tpu.observability.metrics import get_registry

    m = sm.get_serve_metrics()
    try:
        m.observe_tick_phases({"build": 0.5})
        m.observe_tick_phases({"build": 0.25})
        assert get_registry().get(sm.TICK_SECONDS,
                                  phase="build").value == 0.75
        sm.reset_serve_metrics()
        assert get_registry().get(sm.TICK_SECONDS, phase="build") is None
        m.observe_tick_phases({"build": 0.125})
        assert get_registry().get(sm.TICK_SECONDS,
                                  phase="build").value == 0.125
        assert get_registry().get(sm.TICKS_WORKED).value == 1
    finally:
        sm.reset_serve_metrics()
        get_registry().remove_prefix("serve.")


def test_the_stats_reply_carries_the_new_keys(tiny):
    eng = engine(tiny)
    srv, thread = serve(eng, 0, host="127.0.0.1", in_thread=True)
    client = RemoteServeClient(f"127.0.0.1:{srv.server_address[1]}")
    try:
        toks = list(client.stream(prompt(6), 4))
        assert len(toks) == 4
        stats = client.stats()
    finally:
        client.close()
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=30.0)
    for label in ("submit_lock_wait", "emit_to_wire"):
        for key in ("p50_s", "p99_s", "n"):
            assert f"{label}_{key}" in stats
    assert stats["submit_lock_wait_n"] == 1
    assert stats["emit_to_wire_n"] == 4 and stats["emit_to_wire_p50_s"] > 0
    assert stats[sm.TICKS_WORKED] >= 1
    assert set(stats[sm.TICK_SECONDS]) == set(tracing.TICK_PHASES)
    hist = stats["metrics"]["histograms"]
    assert hist[sm.SUBMIT_LOCK_WAIT_S]["count"] == 1
    assert hist[sm.EMIT_TO_WIRE_S]["count"] == 4


# ------------------------------------------------ scopes in the programs


def lowered(eng, program):
    """The lowered text, locations included, of one serve program at
    the engine's own shapes."""
    n = eng.pool.n_slots
    i32 = jnp.int32
    vec = jnp.zeros((n,), i32)
    mask = jnp.zeros((n,), bool)
    key = jnp.zeros((2,), jnp.uint32)
    row = jnp.zeros((1, 8), i32)
    d = 2
    spec = (jnp.zeros((n, d), i32), vec, vec, mask, eng._tok, eng._keys,
            vec)
    head = (eng.variables, eng.pool.caches)
    if not eng.paged:
        fn, args = {
            "decode": (eng._decode_step, (eng._tok, vec, mask, eng._keys)),
            "chunk": (eng._chunk_fn(8), (row, 0, 0, 7, key)),
            "prefill": (eng._prefill_fn(8), (row, 0, 8, key)),
            "verify": (eng._verify_fn(d + 1), spec),
        }[program]
    else:
        mb = eng.pool.max_blocks
        tables = jnp.zeros((n, mb), i32)
        hw = None if eng.paged_kernel else 1
        one = (n, 1) if eng.paged_kernel else (n,)
        fn, args = {
            "decode": (eng._paged_decode_fn(hw),
                       (eng._tok, vec, mask, eng._keys, tables,
                        jnp.zeros(one, i32), jnp.zeros(one, i32))),
            "chunk": (eng._paged_chunk_fn(8),
                      (row, jnp.zeros((mb,), i32), 0, 7, key)),
            "verify": (eng._paged_verify_fn(d + 1, hw),
                       spec + (tables, jnp.zeros((n, d + 1), i32),
                               jnp.zeros((n, d + 1), i32))),
        }[program]
    return fn.lower(*head, *args).as_text(debug_info=True)


def op_names(text):
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("kind,program", [
    ("dense", "decode"), ("dense", "chunk"), ("dense", "prefill"),
    ("dense", "verify"), ("paged_gather", "decode"),
    ("paged_gather", "chunk"), ("paged_gather", "verify"),
    ("paged_kernel", "decode"), ("paged_kernel", "verify")])
def test_every_serve_program_names_the_model_and_the_selection(
        tiny, kind, program):
    eng = engine(tiny, kind, temperature=0.7, top_k=20,
                 spec_k=2 if program == "verify" else 0)
    names = op_names(lowered(eng, program))
    model = [n for n in names if tracing.SCOPE_MODEL in n]
    select = [n for n in names if tracing.SCOPE_SERVE_SELECT in n]
    accept = [n for n in names if tracing.SCOPE_SERVE_ACCEPT in n]
    # Flax's module paths nest inside bps.model, as in the train step
    assert any(re.search(r"bps\.model\)?/.*block_0/attn/q/dot_general", n)
               for n in model), sorted(model)[:5]
    assert not any("block_" in n for n in names - set(model)
                   if n.startswith("jit("))
    # the pick and the key split under bps.serve/select, outside the model
    assert any(n.endswith(("random_split", "random_bits", "argmax",
                           "random_wrap", "threefry2x32"))
               or "split" in n for n in select), sorted(select)
    assert not set(select) & set(model)
    assert bool(accept) == (program == "verify")
    if program == "verify":
        assert any("cumprod" in n or "cum" in n for n in accept)
    if kind == "paged_kernel":
        # the kernel's call site lies under bps.model, and the element
        # before pallas_call — what XLA names the Mosaic call after — is
        # still the name the readers match by substring
        assert any(re.search(
            r"bps\.model/.*attn/jit\(paged_decode_attention\)$", n)
            for n in names)
        assert "paged_decode_attention/pallas_call" in names


def test_a_flash_prefill_keeps_its_kernel_name_under_the_model_scope():
    cfg = TransformerConfig(vocab_size=61, num_layers=1, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=256,
                            dtype=jnp.float32, attn_impl="flash")
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))
    eng = ServingEngine(model, variables, n_slots=2, max_seq=256,
                        temperature=0.0, min_prefill_bucket=128,
                        metrics=ServeMetrics())
    text = eng._prefill_fn(128).lower(
        eng.variables, eng.pool.caches, jnp.zeros((1, 128), jnp.int32), 0,
        128, jnp.zeros((2,), jnp.uint32)).as_text(debug_info=True)
    names = op_names(text)
    assert any(tracing.SCOPE_MODEL in n and "flash" in n for n in names)
    assert any(re.search(r"flash_fwd[^/]*/pallas_call$", n) for n in names)
