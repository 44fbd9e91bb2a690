"""Substrate tests: data pipeline (Dirichlet properties), optimizers,
checkpointing, flops accounting — with hypothesis where it pays off."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import get_config, make_reduced
from repro.data.partition import dirichlet_partition, federate
from repro.data.synthetic import make_image_dataset, make_lm_dataset
from repro.models import SplitModel
from repro.optim import adam, clip_by_global_norm, cosine_schedule, sgd
from repro.utils.flops import (_cnn_unit_costs, client_portion_size,
                               full_size, measure_cnn_unit_costs,
                               model_flops_6nd, split_costs)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
@given(st.integers(0, 1000), st.sampled_from([0.1, 0.5, 1.0]))
@settings(max_examples=10, deadline=None)
def test_dirichlet_partition_properties(seed, alpha):
    labels = np.random.default_rng(seed).integers(0, 10, size=500)
    parts = dirichlet_partition(labels, 8, alpha, seed=seed)
    all_idx = np.concatenate(parts)
    assert len(all_idx) == 500
    assert len(np.unique(all_idx)) == 500          # exact partition
    assert all(len(p) >= 2 for p in parts)


def test_dirichlet_alpha_controls_heterogeneity():
    labels = np.random.default_rng(0).integers(0, 10, size=4000)

    def mean_skew(alpha):
        parts = dirichlet_partition(labels, 10, alpha, seed=0)
        from repro.core.balance import eq2_distance, label_histogram
        return np.mean([eq2_distance(label_histogram(labels[p], 10))
                        for p in parts])

    assert mean_skew(0.1) > mean_skew(1.0) > mean_skew(100.0)


def test_federate_and_iid():
    ds = make_image_dataset(300, seed=0)
    fed = federate(ds, 5, alpha=None)
    assert len(fed) == 5
    assert sum(len(v["y"]) for v in fed.values()) == 300
    lm = make_lm_dataset(50, seq_len=16, vocab=64)
    assert lm["tokens"].shape == (50, 16)
    assert (lm["labels"][:, :-1] == lm["tokens"][:, 1:]).all()
    assert lm["tokens"].max() < 64


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------
def _quad_problem():
    params = {"w": jnp.array([3.0, -2.0]), "b": jnp.array([1.0])}
    grad_fn = jax.grad(lambda p: jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2))
    return params, grad_fn


@pytest.mark.parametrize("opt", [sgd(0.1), sgd(0.1, momentum=0.9),
                                 adam(0.1)])
def test_optimizers_converge_on_quadratic(opt):
    params, grad_fn = _quad_problem()
    state = opt.init(params)
    for step in range(150):
        g = grad_fn(params)
        params, state = opt.update(params, g, state, step)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
    assert float(jnp.abs(params["b"]).max()) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.array([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(0)) < 0.2
    np.testing.assert_allclose(float(lr(10)), 1.0, rtol=1e-5)
    assert float(lr(99)) < 0.2


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip():
    cfg = make_reduced(get_config("zamba2-1.2b"))
    model = SplitModel(cfg)
    params = model.init(KEY)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_checkpoint(path, params, extra={"round": 7})
        restored, extra = load_checkpoint(path, params)
        assert extra["round"] == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_mismatch_raises():
    cfg = make_reduced(get_config("internlm2-1.8b"))
    model = SplitModel(cfg)
    params = model.init(KEY)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_checkpoint(path, params)
        other = SplitModel(make_reduced(get_config("mamba2-2.7b"))).init(KEY)
        with pytest.raises(ValueError, match="mismatch"):
            load_checkpoint(path, other)


# ---------------------------------------------------------------------------
# flops accounting (Fig. 3 semantics)
# ---------------------------------------------------------------------------
def test_portion_sizes_monotone_in_split():
    for arch in ("internlm2-1.8b", "resnet8", "vgg16"):
        cfg = get_config(arch)
        model = SplitModel(cfg)
        sizes = [client_portion_size(model, s)
                 for s in range(1, model.n_units + 1)]
        assert all(b >= a for a, b in zip(sizes, sizes[1:]))
        assert sizes[-1] <= full_size(model)


def test_split_costs_conserve_flops():
    """Fc + Fs ≈ F_full at any split (Fig. 3: portions partition the
    model)."""
    for arch in ("internlm2-1.8b", "mamba2-2.7b", "resnet8"):
        model = SplitModel(get_config(arch))
        kw = {"seq_len": 128} if not model.is_cnn else {}
        for s in (1, 2, model.n_units // 2 or 1):
            c = split_costs(model, s, **kw)
            np.testing.assert_allclose(c["fc"] + c["fs"], c["f_full"],
                                       rtol=1e-6)
            assert c["wc_size"] > 0 and c["feat_size"] > 0


@pytest.mark.parametrize("arch", ["resnet8", "vgg16", "mobilenet"])
def test_cnn_cost_table_matches_cpu_lowering(arch):
    """The committed per-unit (flops, out_elems) table is exactly what
    XLA's CPU cost analysis gives now: the Eq.-1 clock reads the table,
    so it is the same on every backend, and this test says when the
    table has to be regenerated (python -m repro.utils.flops)."""
    cfg = get_config(arch)
    assert _cnn_unit_costs(cfg) == measure_cnn_unit_costs(cfg)


def test_cnn_cost_table_refuses_modified_config():
    import dataclasses
    cfg = dataclasses.replace(get_config("resnet8"), width_mult=2.0)
    with pytest.raises(ValueError, match="no cost table"):
        _cnn_unit_costs(cfg)


@pytest.mark.parametrize("env", [None, "/some/shared/jax-cache"])
def test_compile_cache_dir(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    directory inside the checkout, the same on every run."""
    from repro.utils import compile_cache
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.cache_dir() == os.path.join(repo, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.cache_dir() == env


def test_param_counts_match_assignment_scale():
    """Total params are in the right ballpark for the named scales."""
    expect = {"internlm2-1.8b": (1.5e9, 2.4e9),
              "mamba2-2.7b": (2.2e9, 3.2e9),
              "gemma3-27b": (2.2e10, 3.2e10),
              "kimi-k2-1t-a32b": (0.8e12, 1.3e12),
              "deepseek-v2-lite-16b": (1.2e10, 2.0e10),
              "stablelm-3b": (2.3e9, 3.5e9),
              "zamba2-1.2b": (0.9e9, 1.9e9),
              "internvl2-1b": (3e8, 9e8)}
    for arch, (lo, hi) in expect.items():
        model = SplitModel(get_config(arch))
        n = full_size(model)
        assert lo <= n <= hi, (arch, n)


def test_model_flops_6nd_moe_uses_active():
    dense = model_flops_6nd(get_config("internlm2-1.8b"), 1000)
    assert dense > 0
    kimi = get_config("kimi-k2-1t-a32b")
    active = model_flops_6nd(kimi, 1000) / (6.0 * 1000)
    total = full_size(SplitModel(kimi))
    assert active < 0.1 * total            # 32B active of 1T
    assert active > 0.01 * total
