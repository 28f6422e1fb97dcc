"""Unit + property tests for the PFedDST scoring signals (paper Eq. 6–9)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.scoring import (
    flatten_headers,
    header_distance_matrix,
    header_gram_tree,
    loss_disparity_matrix,
    loss_disparity_row,
    loss_disparity_rows,
    recency_scores,
)
from repro.core.selection import combined_scores
from repro.models import model as model_mod
from repro.models.split import split_params

from conftest import tiny_batch


# ---------------------------------------------------------------------------
# Eq. 7 — header cosine
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(
    m=st.integers(2, 6),
    p=st.integers(3, 40),
    seed=st.integers(0, 2**30),
)
def test_header_cosine_properties(m, p, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, p))
    s = header_distance_matrix(x)
    assert s.shape == (m, m)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s).T, atol=1e-5)
    np.testing.assert_allclose(np.diag(np.asarray(s)), 1.0, atol=1e-5)
    assert np.all(np.asarray(s) <= 1.0 + 1e-5)
    assert np.all(np.asarray(s) >= -1.0 - 1e-5)


def test_header_cosine_scale_invariance():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    s1 = header_distance_matrix(x)
    s2 = header_distance_matrix(x * 7.3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


def test_header_cosine_identical_and_opposite():
    v = jax.random.normal(jax.random.PRNGKey(1), (16,))
    x = jnp.stack([v, v, -v])
    s = np.asarray(header_distance_matrix(x))
    assert s[0, 1] == pytest.approx(1.0, abs=1e-5)
    assert s[0, 2] == pytest.approx(-1.0, abs=1e-5)


def test_header_gram_tree_matches_flatten():
    key = jax.random.PRNGKey(2)
    tree = {
        "a": jax.random.normal(key, (5, 3, 4)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (5, 7)),
    }
    g1 = header_gram_tree(tree)
    g2 = header_distance_matrix(flatten_headers(tree))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


# ---------------------------------------------------------------------------
# Eq. 8 — recency
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(
    lam=st.floats(0.01, 3.0),
    t=st.integers(0, 1000),
    seed=st.integers(0, 2**30),
)
def test_recency_properties(lam, t, seed):
    m = 5
    last = jax.random.randint(jax.random.PRNGKey(seed), (m, m), -1, t + 1)
    s = np.asarray(recency_scores(last, jnp.asarray(t), lam))
    assert np.all(s >= 0.0) and np.all(s <= 1.0)
    never = np.asarray(last) < 0
    np.testing.assert_allclose(s[never], 1.0)
    # monotone: longer gap → larger score
    s_now = np.asarray(
        recency_scores(jnp.full((1, 1), t), jnp.asarray(t), lam)
    )[0, 0]
    s_old = np.asarray(
        recency_scores(jnp.zeros((1, 1), jnp.int32), jnp.asarray(t), lam)
    )[0, 0]
    assert s_now <= s_old + 1e-7


def test_recency_just_selected_is_zero():
    last = jnp.full((2, 2), 9)
    s = np.asarray(recency_scores(last, jnp.asarray(9), 0.5))
    np.testing.assert_allclose(s, 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Eq. 6 — loss disparity
# ---------------------------------------------------------------------------

def test_loss_disparity_diag_vs_offdiag(tiny_cnn, key):
    """A client trained on its own data should score lower on itself than a
    random peer does on it (after a bit of training)."""
    cfg = tiny_cnn
    m = 3
    keys = jax.random.split(key, m)
    params = jax.vmap(lambda k: model_mod.init_params(cfg, k))(keys)
    probe = {
        "images": jax.random.normal(
            key, (m, 4, cfg.image_size, cfg.image_size, 3)
        ),
        "labels": jnp.tile(jnp.arange(4), (m, 1)) % cfg.num_classes,
    }
    L = loss_disparity_matrix(cfg, params, probe)
    assert L.shape == (m, m)
    assert bool(jnp.all(jnp.isfinite(L)))
    assert bool(jnp.all(L >= 0))


def _vmapped_rows(cfg, rows, probe):
    """The oracle: each row's eval_loss vmapped over the M probe batches."""
    return jax.lax.map(
        lambda p: jax.vmap(lambda b: model_mod.eval_loss(cfg, p, b))(probe),
        rows,
    )


def _population_and_probe(cfg, key, m, b):
    params = jax.vmap(lambda k: model_mod.init_params(cfg, k))(
        jax.random.split(key, m)
    )
    ki, kl = jax.random.split(key)
    if cfg.family == "cnn":
        probe = {
            "images": jax.random.normal(
                ki, (m, b, cfg.image_size, cfg.image_size, 3)
            ),
            "labels": jax.random.randint(kl, (m, b), 0, cfg.num_classes),
        }
    else:
        probe = {"tokens": jax.random.randint(kl, (m, b, 16), 0,
                                              cfg.vocab_size)}
    return params, probe


@pytest.mark.parametrize("dtype,rtol", [("float32", 0.0),
                                        ("bfloat16", 1e-3)])
def test_loss_disparity_rows_match_vmapped_rows(tiny_cnn, key, dtype, rtol):
    """One flat forward a row gives every (row, client) loss the vmapped
    row gave: bitwise in float32, within bf16 fusion noise in bfloat16."""
    cfg = dataclasses.replace(tiny_cnn, dtype=dtype)
    m, b, r = 6, 8, 3
    params, probe = _population_and_probe(cfg, key, m, b)
    rows = jax.tree_util.tree_map(lambda x: x[:r], params)
    want = np.asarray(jax.jit(
        lambda p, x: _vmapped_rows(cfg, p, x))(rows, probe))
    got = np.asarray(jax.jit(
        lambda p, x: loss_disparity_rows(cfg, p, x))(rows, probe))
    assert got.shape == (r, m) and got.dtype == np.float32
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=0)
    # the single-row form, against the vmapped row outside the loop
    p1 = jax.tree_util.tree_map(lambda x: x[1], params)
    want_one = np.asarray(jax.jit(lambda p, x: jax.vmap(
        lambda bt: model_mod.eval_loss(cfg, p, bt))(x))(p1, probe))
    one = np.asarray(jax.jit(
        lambda p, x: loss_disparity_row(cfg, p, x))(p1, probe))
    np.testing.assert_allclose(one, want_one, rtol=rtol, atol=0)


def test_loss_disparity_matrix_lm_matches_vmapped_rows(key):
    """The LM path (`launch/steps.fed_round_step` passes (M, Bp, S)
    tokens): per-client shifted-token losses of one flat forward."""
    cfg = get_config("qwen2-1.5b").reduced()
    m, b = 3, 2
    params, probe = _population_and_probe(cfg, key, m, b)
    want = np.asarray(jax.jit(
        lambda p, x: _vmapped_rows(cfg, p, x))(params, probe))
    got = np.asarray(jax.jit(
        lambda p, x: loss_disparity_matrix(cfg, p, x))(params, probe))
    assert got.shape == (m, m)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=0)


# ---------------------------------------------------------------------------
# Eq. 9 — combination
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(
    alpha=st.floats(0.1, 4.0),
    c=st.floats(0.0, 3.0),
    seed=st.integers(0, 2**30),
)
def test_combined_scores_monotonicity(alpha, c, seed):
    m = 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    s_l = jax.random.uniform(ks[0], (m, m), minval=0.0, maxval=5.0)
    s_d = jax.random.uniform(ks[1], (m, m), minval=-1.0, maxval=1.0)
    s_p = jax.random.uniform(ks[2], (m, m), minval=0.01, maxval=1.0)
    s = combined_scores(s_l, s_d, s_p, alpha=alpha, comm_cost=c)
    # diagonal masked
    assert bool(jnp.all(jnp.diagonal(s) < -1e20))
    # paper's conditions: score increases with s_l, decreases with s_d
    s_hi = combined_scores(s_l + 1.0, s_d, s_p, alpha=alpha, comm_cost=c)
    off = ~jnp.eye(m, dtype=bool)
    assert bool(jnp.all(s_hi[off] >= s[off]))
    s_sim = combined_scores(s_l, s_d + 0.1, s_p, alpha=alpha, comm_cost=c)
    assert bool(jnp.all(s_sim[off] <= s[off]))


def test_recency_cannot_flip_sign():
    """s_p is multiplicative — it can't make a bad peer outrank a good one
    with the same recency (paper §II-B design rationale)."""
    s_l = jnp.array([[0.0, 2.0, 0.5]])
    s_d = jnp.zeros((1, 3))
    s_p = jnp.full((1, 3), 0.7)
    s = combined_scores(
        jnp.tile(s_l, (3, 1)), jnp.tile(s_d, (3, 1)), jnp.tile(s_p, (3, 1)),
        alpha=1.0, comm_cost=1.0,
    )
    assert s[0, 1] > s[0, 2]
