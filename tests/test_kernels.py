"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracle in each kernel's ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.fused_sgd.ops import fused_sgd_update
from repro.kernels.fused_sgd.ref import sgd_reference
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_reference

RNG = np.random.default_rng(42)


def arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,s,h,kv,hd", [
    (2, 64, 4, 2, 32),
    (1, 128, 8, 8, 64),
    (2, 64, 4, 1, 32),       # MQA
    (1, 256, 4, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(b, s, h, kv, hd, dtype):
    q = arr(b, s, h, hd, dtype=dtype)
    k = arr(b, s, kv, hd, dtype=dtype)
    v = arr(b, s, kv, hd, dtype=dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("window", [16, 48, 100])
def test_flash_attention_sliding_window(window):
    q, k, v = arr(1, 128, 4, 32), arr(1, 128, 2, 32), arr(1, 128, 2, 32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32)
    ref = attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("b,h,kv,t,hd", [
    (2, 8, 2, 256, 32),
    (1, 4, 4, 512, 64),
    (3, 8, 1, 128, 128),     # MQA
])
@pytest.mark.parametrize("window", [0, 100])
def test_decode_attention_matches_reference(b, h, kv, t, hd, window):
    q = arr(b, 1, h, hd)
    k = arr(b, t, kv, hd)
    v = arr(b, t, kv, hd)
    _check_decode(q, k, v, b, h, kv, t, hd, window, atol=1e-5)


def test_decode_attention_bf16():
    b, h, kv, t, hd = 2, 8, 2, 256, 32
    q = arr(b, 1, h, hd, dtype=jnp.bfloat16)
    k = arr(b, t, kv, hd, dtype=jnp.bfloat16)
    v = arr(b, t, kv, hd, dtype=jnp.bfloat16)
    _check_decode(q, k, v, b, h, kv, t, hd, 0, atol=2e-2)


def _check_decode(q, k, v, b, h, kv, t, hd, window, atol):
    lengths = jnp.asarray(RNG.integers(1, t, size=b), jnp.int32)
    out = decode_attention(q, k, v, lengths, window=window, block_k=64)
    g = h // kv
    ref = decode_attention_reference(
        q[:, 0].reshape(b, kv, g, hd),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        lengths, window=window,
    ).reshape(b, 1, h, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# ssd scan


@pytest.mark.parametrize("b,l,h,g,p,n,chunk", [
    (2, 64, 4, 1, 16, 8, 16),
    (1, 96, 8, 2, 32, 16, 32),
    (2, 50, 4, 1, 16, 8, 16),      # non-divisible length (padding path)
    (1, 128, 4, 4, 64, 32, 64),    # groups == heads
])
def test_ssd_scan_matches_reference(b, l, h, g, p, n, chunk):
    x = arr(b, l, h, p)
    dt = jnp.abs(arr(b, l, h, scale=0.5)) + 0.01
    a = -jnp.abs(arr(h)) - 0.1
    bm = arr(b, l, g, n, scale=0.3)
    cm = arr(b, l, g, n, scale=0.3)
    out = ssd_scan(x, dt, a, bm, cm, chunk=chunk)
    ref = ssd_reference(x, dt, a, bm, cm, chunk=chunk)
    scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
    np.testing.assert_allclose(
        np.asarray(out) / scale, np.asarray(ref) / scale, atol=1e-5
    )


def test_ssd_scan_equals_naive_recurrence():
    """The chunked dual form must equal the literal SSM recurrence."""
    b, l, h, p, n = 1, 32, 2, 8, 4
    x = arr(b, l, h, p)
    dt = jnp.abs(arr(b, l, h, scale=0.5)) + 0.01
    a = -jnp.abs(arr(h)) - 0.1
    bm = arr(b, l, 1, n, scale=0.3)
    cm = arr(b, l, 1, n, scale=0.3)
    out = ssd_scan(x, dt, a, bm, cm, chunk=16)

    state = np.zeros((b, h, n, p))
    ys = []
    for t in range(l):
        dtt = np.asarray(dt[:, t])                      # (b,h)
        decay = np.exp(dtt * np.asarray(a))
        bt = np.repeat(np.asarray(bm[:, t]), h, axis=1)  # (b,h,n)
        ct = np.repeat(np.asarray(cm[:, t]), h, axis=1)
        xt = np.asarray(x[:, t])                         # (b,h,p)
        state = decay[..., None, None] * state + np.einsum(
            "bh,bhn,bhp->bhnp", dtt, bt, xt)
        ys.append(np.einsum("bhn,bhnp->bhp", ct, state))
    ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)


# ---------------------------------------------------------------------------
# fused sgd


@pytest.mark.parametrize("shape", [(100,), (33, 7), (1000, 130), (5, 4, 3)])
@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_sgd_matches_reference(shape, nesterov):
    p, g, m = arr(*shape), arr(*shape), arr(*shape)
    pn, mn = fused_sgd_update(p, g, m, lr=0.01, momentum=0.5,
                              nesterov=nesterov, block=1024)
    pr, mr = sgd_reference(p, g, m, 0.01, momentum=0.5, nesterov=nesterov)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(pr),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mr),
                               rtol=1e-5, atol=1e-7)


def test_fused_sgd_zero_momentum_is_plain_sgd():
    p, g, m = arr(64), arr(64), jnp.zeros(64)
    pn, _ = fused_sgd_update(p, g, m, lr=0.1, momentum=0.0, block=64)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(p - 0.1 * g),
                               rtol=1e-6)


def _pallas_operand_shapes(fn, *args):
    """Shapes of every operand of the (single) pallas_call ``fn`` traces to,
    found through the nested jit jaxprs."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append([v.aval.shape for v in eqn.invars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(found) == 1, found
    return found[0]


def _assert_tiled(shapes, lead, n, block):
    """lr is one SMEM scalar; p/g/m are (..., rows, 128) tiles covering
    ``n`` elements in whole ``block``-element tiles."""
    lr, *tiles = shapes
    assert lr == (1,), shapes
    rows = -(-block // 128)
    for s in tiles:
        assert s[:-2] == lead and s[-1] == 128, shapes
        assert s[-2] % rows == 0 and s[-2] * 128 >= n, shapes


@pytest.mark.parametrize("n,block", [
    (1, 256),             # single element, whole tile is pad
    (255, 256), (257, 256),    # one short / one past the tile boundary
    (1023, 1024), (4097, 1024),
    (130, 200),           # tile not a whole number of 128-lane rows
    (199_210, 65_536),    # the paper MLP's raveled parameter count
    (319_178, 65_536),    # the paper CNN's raveled parameter count
])
def test_fused_sgd_odd_tails(n, block):
    """fp32 parity on sizes that never divide the tile — the pad/unpad path
    of the flat-parameter update used by LocalTrainer(use_fused_sgd) —
    through the (rows, 128) tiling the TPU compiler accepts."""
    p, g, m = arr(n), arr(n), arr(n)

    def upd(p, g, m):
        return fused_sgd_update(p, g, m, lr=0.02, momentum=0.9, block=block)

    _assert_tiled(_pallas_operand_shapes(upd, p, g, m), (), n, block)
    pn, mn = upd(p, g, m)
    pr, mr = sgd_reference(p, g, m, 0.02, momentum=0.9)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(pr),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mr),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("C,n,block", [
    (4, 300, 256),
    (25, 4097, 1024),         # 25 lanes: one Table IV edge ring per lane
    (2, 199_210, 65_536),     # the paper MLP at the default tile
])
def test_fused_sgd_under_vmap(C, n, block):
    """The launch path vmaps the client update over the FL stack; the fused
    kernel must batch correctly, as (C, rows, 128) tiles."""
    p, g, m = arr(C, n), arr(C, n), arr(C, n)
    fn = jax.vmap(lambda p, g, m: fused_sgd_update(
        p, g, m, lr=0.05, momentum=0.5, block=block))
    _assert_tiled(_pallas_operand_shapes(fn, p, g, m), (C,), n, block)
    pn, mn = fn(p, g, m)
    pr, mr = sgd_reference(p, g, m, 0.05, momentum=0.5)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(pr),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mr),
                               rtol=1e-5, atol=1e-7)


def test_fused_sgd_traced_lr():
    """lr arrives as a traced scalar from the cosine schedule — must not be
    treated as a static value."""
    p, g, m = arr(128), arr(128), arr(128)

    @jax.jit
    def step(lr):
        return fused_sgd_update(p, g, m, lr=lr, momentum=0.5, block=128)

    for lr in (0.1, 0.01):
        pn, _ = step(jnp.asarray(lr, jnp.float32))
        pr, _ = sgd_reference(p, g, m, lr, momentum=0.5)
        np.testing.assert_allclose(np.asarray(pn), np.asarray(pr), rtol=1e-5,
                                   atol=1e-7)
