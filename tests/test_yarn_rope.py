"""YaRN rotary frequencies and the MLA softmax scale of DeepSeek-V2-Lite
against the published formula (``DeepseekV2YarnRotaryEmbedding`` and the
attention's ``softmax_scale``), written out here in numpy."""
import math

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.attention import grouped_attention, mla_softmax_scale
from repro.models.layers import apply_rope, rope_freqs


def _published_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def test_yarn_frequencies_match_the_published_formula():
    cfg = get_config("deepseek-v2-lite-16b")
    s = cfg.rope_scaling
    want = _published_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                               s.factor, s.original_max_position_embeddings,
                               s.beta_fast, s.beta_slow)
    got = np.asarray(rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, s))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the correction range of (32, 1) turns over 4,096 positions is
    # pairs 10..23: the fastest pairs keep their frequency, the slowest
    # are divided by the factor
    plain = np.asarray(rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta))
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40.0, rtol=1e-6)


def test_mla_softmax_scale_is_mscale_squared_over_sqrt_dim():
    cfg = get_config("deepseek-v2-lite-16b")
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(mla_softmax_scale(cfg) - mscale ** 2 / math.sqrt(192)) < 1e-12
    assert abs(mscale ** 2 - 1.5896) < 1e-3
    # the attention applies the scale it is given
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
               for _ in range(3))
    pos = jnp.arange(8, dtype=jnp.int32)
    got = grouped_attention(q, k, v, pos, pos, scale=0.3)
    s = np.einsum("bshd,bthd->bhst", q, k) * 0.3
    s = np.where(np.tril(np.ones((8, 8), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("bhst,bthd->bshd", w, v), atol=1e-5)


def test_rope_without_scaling_is_unchanged():
    """Configs without ``rope_scaling`` (InternLM2) keep theta^(-2i/D) and
    the rotation of pairs (i, i + D/2)."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(5, dtype=jnp.int32)
    inv = 1e6 ** (-np.arange(0, 8, 2) / 8)
    ang = np.arange(5)[:, None] * inv
    c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    want = np.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    np.testing.assert_allclose(np.asarray(apply_rope(x, pos, 1e6)), want,
                               atol=1e-5)
