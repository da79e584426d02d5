"""Pallas flash attention vs the reference attention (interpret mode on CPU;
the same kernel runs compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.parallel.ring_attention import local_attention

B, T, H, D = 2, 256, 2, 64


def _qkv(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    expected = local_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_grads_match_reference():
    q, k, v = _qkv(1)

    def loss_ref(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_uneven_blocks_rejected():
    q, k, v = _qkv(2)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=96, block_k=100, interpret=True)


def test_flash_bf16():
    q, k, v = _qkv(3, jnp.bfloat16)
    expected = local_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def _reference(q, k, v, causal, seg=None, window=None, slopes=None):
    """Plain attention with every option the kernels take: GQA expansion,
    causal / sliding-window band, ALiBi bias, segment ids."""
    if k.shape[2] != q.shape[2]:
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    T, D = q.shape[1], q.shape[3]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (D ** -0.5)
    row, col = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    if slopes is not None:
        s = s + slopes[None, :, None, None] * (col - row)[None, None]
    valid = jnp.ones((1, 1, T, T), bool)
    if causal:
        valid = valid & (row >= col)
        if window is not None:
            valid = valid & (row - col < window)
    if seg is not None:
        valid = valid & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _dense_ref(q, k, v, causal, seg=None):
    return _reference(q, k, v, causal, seg)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_match_reference(causal):
    """Packed-sequence / padding-mask masking via segment ids: forward and
    grads match the dense masked softmax (VERDICT r2 missing #5)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, T, H, D = 2, 64, 2, 32
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    seg = jnp.asarray(
        np.repeat(np.array([[0, 1, 1, 2], [0, 0, 3, 3]]), T // 4, axis=1))

    out = flash_attention(q, k, v, causal, None, 16, 16, True, seg)
    want = _dense_ref(q, k, v, causal, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal, None, 16, 16, True, seg) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _dense_ref(a, b, c, causal, seg) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("hkv", [1, 2])
def test_flash_gqa_mqa_match_reference(hkv):
    """GQA (grouped kv heads) / MQA (hkv=1): kernel reads the shared kv
    head via the index map; dk/dv group-sum back to [B, T, Hkv, D]."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, T, H, D = 2, 64, 4, 32
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, hkv, D))
    v = jax.random.normal(ks[2], (B, T, hkv, D))

    out = flash_attention(q, k, v, True, None, 16, 16, True)
    want = _dense_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, True, None, 16, 16, True) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _dense_ref(a, b, c, True) ** 2), (0, 1, 2))(q, k, v)
    assert gf[1].shape == (B, T, hkv, D) and gf[2].shape == (B, T, hkv, D)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_gqa_rejects_indivisible_heads():
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 3, 8))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, kv, kv, interpret=True, block_q=16, block_k=16)


def test_bert_classifier_rides_flash_with_padding_mask():
    """Model-level: BertClassifier(attn_impl='flash') with an HF-style
    padding mask computes through the flash kernel's segment ids and
    matches the local masked-softmax path on valid positions."""
    from byteps_tpu.models.bert import BertClassifier, bert_config

    def run(attn_impl):
        cfg = bert_config(vocab_size=64, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          dtype=jnp.float32, attn_impl=attn_impl)
        model = BertClassifier(cfg, num_classes=2)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 64)
        mask = jnp.asarray(np.array(
            [[1] * 24 + [0] * 8, [1] * 32]), jnp.int32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 32), jnp.int32))["params"]
        return model.apply({"params": params}, tokens,
                           attention_mask=mask)

    out_flash = run("flash")
    out_local = run("local")
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_local),
                               rtol=1e-4, atol=1e-5)


def _dense_ref_band(q, k, v, causal, window=None, slopes=None):
    return _reference(q, k, v, causal, None, window, slopes)


@pytest.mark.parametrize("window", [1, 16, 40, 64])
def test_flash_sliding_window_matches_reference(window):
    """Mistral-style causal sliding window: fwd + grads match the dense
    banded softmax, including windows not aligned to block boundaries."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, T, H, D = 2, 64, 2, 32
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)

    out = flash_attention(q, k, v, True, None, 16, 16, True,
                          window=window)
    want = _dense_ref_band(q, k, v, True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, True, None, 16, 16, True, window=window) ** 2),
        (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _dense_ref_band(a, b, c, True, window=window) ** 2),
        (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_alibi_matches_reference():
    """ALiBi bias computed in-kernel: fwd + grads match the dense biased
    softmax; also composed with a sliding window."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, T, H, D = 2, 64, 4, 32
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    slopes = jnp.asarray([2.0 ** (-i) for i in range(1, H + 1)], jnp.float32)

    out = flash_attention(q, k, v, True, None, 16, 16, True,
                          alibi_slopes=slopes)
    want = _dense_ref_band(q, k, v, True, slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, True, None, 16, 16, True, alibi_slopes=slopes) ** 2),
        (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _dense_ref_band(a, b, c, True, slopes=slopes) ** 2),
        (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)

    # window + alibi composed
    out2 = flash_attention(q, k, v, True, None, 16, 16, True,
                           window=24, alibi_slopes=slopes)
    want2 = _dense_ref_band(q, k, v, True, window=24, slopes=slopes)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=2e-4, atol=2e-5)


def test_flash_window_requires_causal():
    q, k, v = _qkv(7)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, False, None, 64, 64, True, window=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True, None, 64, 64, True, window=0)


def test_transformer_attn_window_config():
    """Model-level sliding window: config plumbs through to the kernel and
    changes the output vs full causal attention."""
    from byteps_tpu.models.transformer import Transformer, TransformerConfig

    def run(window):
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, d_model=32, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attn_impl="flash",
            attn_window=window)
        model = Transformer(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 64)
        variables = model.init(jax.random.PRNGKey(1), tokens)
        return model.apply(variables, tokens)

    full = run(None)
    windowed = run(8)
    assert not np.allclose(np.asarray(full), np.asarray(windowed))

    from byteps_tpu.models.transformer import TransformerConfig as TC
    with pytest.raises(ValueError):
        TC(attn_impl="local", attn_window=8).attention_fn()


def test_attention_window_with_key_mask():
    """attn_window must still apply when a padding mask routes attention
    through the segment-ids flash branch (regression: window was silently
    dropped there)."""
    from byteps_tpu.models.transformer import Attention, TransformerConfig

    def run(window):
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, d_model=32, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attn_impl="flash",
            attn_window=window)
        attn = Attention(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
        mask = jnp.ones((2, 64), jnp.int32).at[:, 48:].set(0)
        variables = attn.init(jax.random.PRNGKey(1), x, key_mask=mask)
        return attn.apply(variables, x, key_mask=mask)

    full = run(None)
    windowed = run(8)
    assert not np.allclose(np.asarray(full), np.asarray(windowed))

    # non-flash masked branch must reject attn_window, not drop it
    from byteps_tpu.models.transformer import Attention as A
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attn_impl="local", attn_window=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
    mask = jnp.ones((2, 64), jnp.int32)
    with pytest.raises(ValueError):
        A(cfg).init(jax.random.PRNGKey(1), x, key_mask=mask)


# --------------------------------------------------------------------------
# Sub-tile pruning inside a grid block (PR 26): the visit rule, the kernels
# that loop by it, and the gauges that say what a build visits.
# --------------------------------------------------------------------------

import re  # noqa: E402
import sys  # noqa: E402

from byteps_tpu.observability.metrics import get_registry  # noqa: E402

# (``byteps_tpu.ops`` shadows the submodule with the function of its name)
fa = sys.modules["byteps_tpu.ops.flash_attention"]


# windows: none, narrower than a 32-wide sub-tile, crossing sub-tiles (and,
# at block 64, grid blocks); ALiBi and windows need causal
_SUB_TILE_CASES = [
    (causal, window, extra)
    for causal in (False, True)
    for window in (None, 24, 50)
    for extra in ("plain", "segment_ids", "alibi", "gqa_hkv1", "gqa_hkv2")
    if causal or (window is None and extra != "alibi")]


def _take_backward(monkeypatch, backward):
    """``fused``: what the shape picks here, the single kernel; ``split``:
    no head's dq fits, so the dq and the dk/dv kernel."""
    if backward == "split":
        monkeypatch.setattr(fa, "_FUSED_BWD_DQ_BYTES", 0)


@pytest.fixture(params=["fused", "split"])
def backward(request, monkeypatch):
    _take_backward(monkeypatch, request.param)
    return request.param


# (block, backward): every grid under the single backward kernel, the
# two PR 26 built under the dq + dk/dv pair as well
_GRID_CASES = [(128, "fused"), (64, "fused"), (32, "fused"),
               (128, "split"), (64, "split")]


@pytest.mark.parametrize("block,backward", _GRID_CASES)
@pytest.mark.parametrize("causal,window,extra", _SUB_TILE_CASES)
def test_flash_sub_tiles_match_reference(monkeypatch, causal, window, extra,
                                         block, backward):
    """Forward AND dq, dk, dv against the plain reference: block 128 = one
    grid block of 4 x 4 32-wide sub-tiles (the plan is folded at trace
    time, no scratch carry for dk / dv), block 64 = a 2 x 2 grid of 2 x 2
    (the plan chosen from the program ids, carry in scratch), block 32 = a
    4 x 4 grid of 2 x 2 16-wide ones (a row's dq adds up over four k
    blocks)."""
    monkeypatch.setattr(fa, "_SUB_TILE", 16 if block == 32 else 32)
    _take_backward(monkeypatch, backward)
    B, T, H, D = 1, 128, 4, 32
    hkv = {"gqa_hkv1": 1, "gqa_hkv2": 2}.get(extra, H)
    ks = jax.random.split(jax.random.PRNGKey(26), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, hkv, D))
    v = jax.random.normal(ks[2], (B, T, hkv, D))
    seg = slopes = None
    if extra == "segment_ids":   # boundaries inside sub-tiles
        seg = jnp.asarray(np.searchsorted([20, 75, 100], np.arange(T),
                                          side="right"))[None]
    if extra == "alibi":
        slopes = jnp.asarray([2.0 ** -i for i in range(1, H + 1)],
                             jnp.float32)

    def flash(a, b, c):
        return flash_attention(a, b, c, causal, None, block, block, True,
                               seg, window, slopes)

    def ref(a, b, c):
        return _reference(a, b, c, causal, seg, window, slopes)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_tuned_sub_tile_matches_reference():
    """The same at the tuned sub-tile size: one block of 2 x 2 of them."""
    T = 2 * fa._SUB_TILE
    ks = jax.random.split(jax.random.PRNGKey(27), 3)
    q, k, v = (jax.random.normal(kk, (1, T, 1, 64)) for kk in ks)
    assert fa._sub_tile(T, T) == (T // 2, T // 2)

    def flash(a, b, c):
        return flash_attention(a, b, c, True, None, T, T, True)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(_reference(q, k, v, True)),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_reference(*a, True) ** 2),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_tile_visits_counts():
    """The cells' shape (T = 1024 = one block) and T = 4096 in 1024
    blocks."""
    assert fa.tile_visits(1024, 1024, 1024, 256, True) == ({(0, 0): 10}, 16)
    assert fa.tile_visits(1024, 1024, 1024, 128, True) == ({(0, 0): 36}, 64)
    assert fa.tile_visits(1024, 1024, 1024, 512, True) == ({(0, 0): 3}, 4)
    assert fa.tile_visits(1024, 1024, 1024, 256, False) == ({(0, 0): 16}, 16)
    # window 300 reaches back over one whole 256-wide tile into the next:
    # the band only, up to three tiles a row
    visited, total = fa.tile_visits(1024, 1024, 1024, 256, True, 300)
    assert (visited, total) == ({(0, 0): 1 + 2 + 3 + 3}, 16)
    visited, total = fa.tile_visits(4096, 1024, 1024, 256, True)
    assert total == 16
    for qi in range(4):
        for kj in range(4):
            want = 10 if qi == kj else 16 if kj < qi else 0
            assert visited[(qi, kj)] == want, (qi, kj)
    assert sum(visited.values()) == 16 * 17 // 2


@pytest.mark.parametrize("causal,window", [
    (False, None), (True, None), (True, 1), (True, 24), (True, 64),
    (True, 65), (True, 100), (True, 300)])
@pytest.mark.parametrize("T,bq,bk,sub", [
    (256, 256, 256, (64, 64)), (256, 128, 128, (64, 64)),
    (256, 64, 128, (32, 64)), (256, 128, 64, (64, 32)),
    (192, 192, 192, (192, 192))])
def test_visit_rule_is_exact(T, bq, bk, sub, causal, window):
    """Both range functions and the mask test against brute force over
    the band itself: a tile is visited iff it holds a valid position, and
    takes the mask path iff it also holds an invalid one."""
    sq, sk = sub
    row, col = np.arange(T)[:, None], np.arange(T)[None, :]
    valid = np.ones((T, T), bool)
    if causal:
        valid = row >= col
        if window is not None:
            valid = valid & (row - col < window)
    visited, total = fa.tile_visits(T, bq, bk, sub, causal, window)
    assert total == (bq // sq) * (bk // sk)
    from_q_side = {key: 0 for key in visited}
    for (qi, kj), n in visited.items():
        tiles = set()
        for a in range(bq // sq):
            r0 = qi * bq + a * sq
            lo, hi = fa._k_tile_range(r0, kj * bk, bk // sk, sq, sk,
                                      causal, window)
            tiles |= {(a, b) for b in range(lo, hi)}
        want = set()
        for a in range(bq // sq):
            for b in range(bk // sk):
                r0, c0 = qi * bq + a * sq, kj * bk + b * sk
                tile = valid[r0:r0 + sq, c0:c0 + sk]
                if tile.any():
                    want.add((a, b))
                    assert bool(fa._needs_mask(r0, c0, sq, sk, causal,
                                               window)) == (not tile.all())
        assert tiles == want and n == len(want)
        for b in range(bk // sk):
            lo, hi = fa._q_tile_range(kj * bk + b * sk, qi * bq, bq // sq,
                                      sq, sk, causal, window)
            assert {(a, b) for a in range(lo, hi)} == {
                t for t in want if t[1] == b}
            from_q_side[(qi, kj)] += max(hi - lo, 0)
    assert from_q_side == visited


def _plan_tiles(plans, d, k_major):
    """{(a, b): masked} of the plan serving offset ``d`` (none: {})."""
    tiles = {}
    for d_lo, d_hi, strips in plans:
        if d_lo <= d <= d_hi:
            for t0, t1, lo, hi, masked in strips:
                for t in range(t0, t1):
                    for u, flag in zip(range(lo, hi), masked):
                        tiles[(u, t) if k_major else (t, u)] = flag
    return tiles


@pytest.mark.parametrize("causal,window", [
    (False, None), (True, None), (True, 24), (True, 100), (True, 300)])
@pytest.mark.parametrize("T,bq,bk,sub", [
    (256, 256, 256, (64, 64)), (512, 128, 128, (64, 64)),
    (256, 64, 128, (32, 64)), (256, 128, 64, (64, 32))])
def test_step_plans_cover_the_visit_rule(T, bq, bk, sub, causal, window):
    """What the kernels execute — strips, specialised per grid offset —
    is the visit rule: the same tiles, masked where the edge crosses."""
    sq, sk = sub
    nq, nk = T // bq, T // bk
    for k_major, build in ((False, fa._q_major_plans),
                           (True, fa._k_major_plans)):
        plans = build(nq, nk, bq, bk, sub, causal, window, False)
        runs = [(lo, hi) for lo, hi, _ in plans]
        assert runs == sorted(runs) and all(
            a[1] < b[0] for a, b in zip(runs, runs[1:]))
        for qi in range(nq):
            for kj in range(nk):
                want = {}
                for a in range(bq // sq):
                    r0 = qi * bq + a * sq
                    lo, hi = fa._k_tile_range(r0, kj * bk, bk // sk, sq, sk,
                                              causal, window)
                    for b in range(lo, hi):
                        want[(a, b)] = fa._needs_mask(
                            r0, kj * bk + b * sk, sq, sk, causal, window)
                assert _plan_tiles(plans, qi * bq - kj * bk, k_major) == want


def test_step_plans_at_the_tuned_shapes():
    """T = 1024 causal is four ragged strips, masked on the diagonal
    only; non-causal — and a block below the diagonal at T = 4096 — is
    the whole block as one strip, no mask."""
    args = (1024, 1024, (256, 256))
    assert fa._q_major_plans(1, 1, *args, True, None, False) == [(0, 0, (
        (0, 1, 0, 1, (True,)), (1, 2, 0, 2, (False, True)),
        (2, 3, 0, 3, (False, False, True)),
        (3, 4, 0, 4, (False, False, False, True))))]
    assert fa._k_major_plans(1, 1, *args, True, None, False) == [(0, 0, (
        (0, 1, 0, 4, (True, False, False, False)),
        (1, 2, 1, 4, (True, False, False)), (2, 3, 2, 4, (True, False)),
        (3, 4, 3, 4, (True,))))]
    whole = ((0, 4, 0, 4, (False,) * 4),)
    assert fa._q_major_plans(1, 1, *args, False, None, False) == [
        (0, 0, whole)]
    diag, interior = fa._q_major_plans(4, 4, *args, True, None, False)
    assert diag[:2] == (0, 0) and len(diag[2]) == 4
    assert interior == (1024, 3072, whole)
    # segment ids / ALiBi: every tile masked, so equal ranges still merge
    assert fa._q_major_plans(1, 1, *args, False, None, True) == [
        (0, 0, ((0, 4, 0, 4, (True,) * 4),))]


def test_sub_tile_leaves_an_undivided_side_whole():
    s = fa._SUB_TILE
    assert fa._sub_tile(4 * s, 2 * s) == (s, s)
    assert fa._sub_tile(s + 8, 2 * s) == (s + 8, s)
    assert fa._sub_tile(64, 64) == (64, 64)


def _bwd_kernels(*shape_dtype, **kw):
    """Names of the backward kernels a gradient of this shape traces."""
    x = jax.ShapeDtypeStruct(*shape_dtype)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, interpret=True, **kw).astype(
            jnp.float32)), (0, 1, 2)))(x, x, x))
    return sorted(set(re.findall(r"flash_bwd_\w+", jaxpr)))


def test_backward_is_chosen_by_shape():
    """One backward kernel wherever a head's dq — fp32 accumulator plus
    the two buffers of its output block, rows padded to 128 lanes — fits
    the budget; the dq + dk/dv pair above it.  Nothing but T, D and the
    dtype enters."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert fa._fused_bwd_fits(1024, 64, bf16)        # the gpt2-medium cells
    assert fa._fused_bwd_fits(8192, 192, bf16)       # the joyai cell
    budget = fa._FUSED_BWD_DQ_BYTES
    assert budget == 32 * 2 ** 20 < fa._FUSED_BWD_VMEM_LIMIT
    # D = 64 pads to 128 lanes, 192 to 256: 8 and 16 bytes a padded lane
    assert fa._fused_bwd_fits(32768, 64, bf16)
    assert fa._fused_bwd_fits(32768, 128, bf16)
    assert not fa._fused_bwd_fits(32768 + 8, 128, bf16)
    assert fa._fused_bwd_fits(16384, 192, bf16)
    assert not fa._fused_bwd_fits(32768, 192, bf16)
    assert not fa._fused_bwd_fits(131072, 128, bf16)  # a 128k context
    # fp32 operands: 12 bytes a padded lane
    assert fa._fused_bwd_fits(16384, 128, f32)
    assert not fa._fused_bwd_fits(32768, 128, f32)

    reg = get_registry()
    assert _bwd_kernels((1, 1024, 2, 64), bf16) == ["flash_bwd_dq_flash_bwd_dkv"]
    assert reg.get("flash.bwd_fused", window="none").value == 1
    # above the budget (tracing only: nothing of this size runs here)
    assert _bwd_kernels((1, 65536, 1, 128), bf16) == [
        "flash_bwd_dkv", "flash_bwd_dq"]
    assert reg.get("flash.bwd_fused", window="none").value == 0


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 64])
def test_flash_value_heads_of_their_own_width(monkeypatch, block, causal,
                                              backward):
    """q / k heads 48 wide, v / o heads 32 (latent attention's 192 / 128
    in small): dq and dk carry D, dv carries Dv, on a 2 x 2 and a 4 x 4
    grid."""
    monkeypatch.setattr(fa, "_SUB_TILE", 16)
    B, T, H, D, Dv = 2, 128, 2, 48, 32
    ks = jax.random.split(jax.random.PRNGKey(30), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, Dv))

    def flash(a, b, c):
        return flash_attention(a, b, c, causal, None, block, block, True)

    out = flash(q, k, v)
    assert out.shape == (B, T, H, Dv)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference(q, k, v, causal)),
                               rtol=2e-4, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_reference(*a, causal) ** 2),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_tiles_gauges(causal, backward):
    """Each pallas_call build records what it visits, per kernel; at the
    cells' shape that is the triangle of the tuned s, and everything for
    a non-causal call.  Tracing alone records — nothing runs here."""
    reg = get_registry()
    reg.remove_prefix("flash.")
    x = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal, interpret=True).astype(jnp.float32)), (0, 1, 2)),
        x, x, x)
    n = 1024 // fa._SUB_TILE
    assert (n, n * (n + 1) // 2) == (4, 10)     # s = 256: 10 of 16
    band = {"window": "none"}
    assert reg.get("flash.bwd_fused", **band).value == (backward == "fused")
    kernels = {"fused": ("fwd", "bwd"),
               "split": ("fwd", "bwd_dq", "bwd_dkv")}[backward]
    for kernel in kernels:
        visited = reg.get("flash.tiles_visited", kernel=kernel, **band).value
        total = reg.get("flash.tiles_total", kernel=kernel, **band).value
        assert total == n * n
        assert visited == (n * (n + 1) // 2 if causal else total)
    for absent in {"bwd", "bwd_dq", "bwd_dkv"} - set(kernels):
        assert reg.get("flash.tiles_visited", kernel=absent, **band) is None


def test_layers_share_one_pallas_call_build(backward):
    """Three layers of one configuration trace and lower each kernel once:
    the builders are cached, and the callable they return is a jit."""
    builders = [fa._forward_call, fa._dkv_call]
    if backward == "split":
        builders.append(fa._dq_call)
    # (a shape of its own per case: the caches outlive a test)
    x = jax.ShapeDtypeStruct((1, 128, 2 + len(builders), 32), jnp.float32)
    before = [b.cache_info() for b in builders]

    def loss(q, k, v):
        for _ in range(3):
            q = flash_attention(q, k, v, True, None, 64, 32, True)
        return jnp.sum(q)

    jax.eval_shape(jax.grad(loss, (0, 1, 2)), x, x, x)
    for b, was in zip(builders, before):
        now = b.cache_info()
        assert now.misses - was.misses == 1
        assert now.hits - was.hits == 2
