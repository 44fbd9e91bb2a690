"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm import (grouped_matmul, held_experts_gmm,
                                   held_experts_tgmm)
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.models.ssm import ssd_decode_step, ssd_scan_ref

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FA_CASES = [
    # (BH, S, T, D, G, causal, window, dtype)
    (4, 128, 128, 64, 1, True, 0, jnp.float32),
    (4, 256, 256, 64, 2, True, 0, jnp.float32),
    (2, 256, 256, 128, 1, True, 64, jnp.float32),
    (6, 512, 512, 64, 3, False, 0, jnp.float32),
    (2, 128, 128, 32, 1, True, 0, jnp.bfloat16),
    (4, 384, 384, 64, 4, True, 128, jnp.float32),
    (2, 64, 64, 96, 2, True, 0, jnp.float32),
]


@pytest.mark.parametrize("BH,S,T,D,G,causal,window,dtype", FA_CASES)
def test_flash_attention_sweep(BH, S, T, D, G, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (BH, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (BH // G, T, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (BH // G, T, D), jnp.float32).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              groups=G, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, groups=G)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_model_layout_matches_xla_path():
    from repro.models.attention import grouped_attention
    B, S, H, K, D = 2, 128, 8, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    pos = jnp.arange(S, dtype=jnp.int32)
    ref = grouped_attention(q, k, v, pos, pos, causal=True, impl="xla")
    out = flash_attention(q, k, v, window=0, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
SSD_CASES = [
    (2, 128, 4, 16, 8, 32, jnp.float32),
    (1, 256, 2, 64, 32, 64, jnp.float32),
    (2, 256, 3, 32, 16, 128, jnp.float32),
    (1, 128, 2, 32, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,p,n,chunk,dtype", SSD_CASES)
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32).astype(dtype)
    init = (jax.random.normal(ks[5], (b, h, p, n), jnp.float32) * 0.1
            ).astype(dtype)
    y1, f1 = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                             initial_state=init, interpret=True)
    y2, f2 = ssd_scan_ref(x, dt, A, B, C, chunk=chunk, initial_state=init)
    # bf16 inputs quantize intermediate states; rtol dominates there
    atol, rtol = (0.1, 3e-2) if dtype == jnp.bfloat16 else (2e-4, 1e-5)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=atol,
                               rtol=rtol)
    np.testing.assert_allclose(np.asarray(f1, np.float32),
                               np.asarray(f2, np.float32), atol=atol,
                               rtol=rtol)


def test_ssd_chunked_matches_sequential_recurrence():
    """The oracle itself vs step-by-step recurrence (ground truth)."""
    b, s, h, p, n = 2, 96, 4, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_chunk, fs = ssd_scan_ref(x, dt, A, B, C, chunk=32)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y, state = ssd_decode_step(x[:, t:t + 1], dt[:, t:t + 1], A,
                                   B[:, t:t + 1], C[:, t:t + 1], state)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y_chunk),
                               np.asarray(jnp.concatenate(ys, 1)), atol=3e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(state), atol=3e-4)


# ---------------------------------------------------------------------------
# held-expert grouped matmuls (moe gmm)
# ---------------------------------------------------------------------------
GMM_CASES = [
    # (m, k, n, group sizes, dtype): the groups need not fill the rows
    (256, 256, 384, (40, 0, 100, 57), jnp.float32),     # an empty group
    (128, 128, 256, (0, 0, 128), jnp.float32),           # one full group
    (512, 256, 128, (300, 212), jnp.bfloat16),
    (64, 96, 80, (3, 5, 0, 9, 20), jnp.float32),         # non-128 widths
    (256, 128, 128, (0, 0, 0), jnp.float32),             # nothing routed
]


def _gmm_inputs(m, k, n, sizes, dtype):
    ks = jax.random.split(KEY, 3)
    g = len(sizes)
    return ((jax.random.normal(ks[0], (m, k)) * 0.5).astype(dtype),
            (jax.random.normal(ks[1], (g, k, n)) * 0.1).astype(dtype),
            (jax.random.normal(ks[2], (m, n))).astype(dtype),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("m,k,n,sizes,dtype", GMM_CASES)
def test_held_experts_gmm_and_gradients(m, k, n, sizes, dtype):
    """Forward, input gradient (transposed weights) and weight gradient of
    the Pallas kernels, interpreted, against ``jax.lax.ragged_dot`` and its
    VJP, on the rows the groups hold."""
    x, w, dy, gs = _gmm_inputs(m, k, n, sizes, dtype)
    rows = np.arange(m) < sum(sizes)
    tol = dict(atol=5e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=1e-4, rtol=1e-4)
    f32 = lambda a: np.asarray(a, np.float32)
    y = held_experts_gmm(x, w, gs, interpret=True)
    y_ref, vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(a, b, gs,
                                        preferred_element_type=jnp.float32
                                        ).astype(dtype), x, w)
    dx_ref, dw_ref = vjp(dy)
    np.testing.assert_allclose(f32(y)[rows], f32(y_ref)[rows], **tol)
    dx = held_experts_gmm(dy, w, gs, transpose_rhs=True, interpret=True)
    np.testing.assert_allclose(f32(dx)[rows], f32(dx_ref)[rows], **tol)
    dw = held_experts_tgmm(x, dy, gs, interpret=True)
    np.testing.assert_allclose(f32(dw), f32(dw_ref), **tol)


def test_held_experts_gmm_under_vmap():
    """Each batch element with its own group sizes, interpreted."""
    x, w, dy, gs = _gmm_inputs(256, 128, 256, (40, 0, 100, 57), jnp.float32)
    xs = jnp.stack([x, 2 * x])
    dys = jnp.stack([dy, -dy])
    gss = jnp.stack([gs, jnp.asarray([0, 128, 0, 64], jnp.int32)])
    ys = jax.vmap(lambda a, s: held_experts_gmm(a, w, s, interpret=True))(
        xs, gss)
    dws = jax.vmap(lambda a, b, s: held_experts_tgmm(a, b, s,
                                                     interpret=True))(
        xs, dys, gss)
    for i in range(2):
        rows = np.arange(256) < int(gss[i].sum())
        y_ref, vjp = jax.vjp(lambda b: jax.lax.ragged_dot(xs[i], b, gss[i]),
                             w)
        np.testing.assert_allclose(np.asarray(ys[i])[rows],
                                   np.asarray(y_ref)[rows], atol=1e-4)
        np.testing.assert_allclose(np.asarray(dws[i]),
                                   np.asarray(vjp(dys[i])[0]), atol=1e-4)


def test_grouped_matmul_gradients_on_cpu():
    """The differentiable op (the oracle on the CPU), under ``vmap``,
    against autodiff of ``jax.lax.ragged_dot`` on the held rows."""
    x, w, dy, gs = _gmm_inputs(128, 128, 256, (30, 0, 50, 20), jnp.float32)
    rows = jnp.arange(128)[:, None] < 100

    def loss(f):
        return lambda a, b: jnp.sum(jnp.where(rows, f(a, b, gs), 0) * dy)

    got = jax.vmap(jax.grad(loss(grouped_matmul), (0, 1)),
                   in_axes=(0, None))(jnp.stack([x, x]), w)
    want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b),
                                   atol=1e-4)
