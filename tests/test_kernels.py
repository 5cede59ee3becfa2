"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.histogram import cell_edges
from repro.kernels import flash_attention, ttl_scan
from repro.kernels import ref
from repro.kernels.ttl_scan import (_exclusive_suffix_scan, _inclusive_scan,
                                    ttl_cost_surface)


def _hist_problem(e_dim, c_dim, seed):
    rng = np.random.default_rng(seed)
    edges = (cell_edges() if c_dim == 800
             else np.cumsum(rng.uniform(1, 100, c_dim)))
    hist = (rng.gamma(0.3, 1e9, (e_dim, c_dim))
            * (rng.random((e_dim, c_dim)) < 0.1)).astype(np.float32)
    time_w = hist * (edges[None] * rng.random((e_dim, c_dim))).astype(np.float32)
    last = (rng.gamma(0.3, 1e9, (e_dim, c_dim))
            * (rng.random((e_dim, c_dim)) < 0.05)).astype(np.float32)
    s = rng.uniform(5e-18, 5e-17, e_dim).astype(np.float32)
    n = rng.uniform(1e-11, 1e-10, e_dim).astype(np.float32)
    first = rng.gamma(1.0, 1e9, e_dim).astype(np.float32)
    return hist, time_w, last, edges.astype(np.float32), s, n, first


@pytest.mark.parametrize("e_dim,c_dim", [(1, 800), (3, 800), (17, 800),
                                         (64, 800), (5, 123), (2, 1024)])
def test_ttl_scan_kernel_vs_oracle(e_dim, c_dim):
    prob = _hist_problem(e_dim, c_dim, seed=e_dim * 1000 + c_dim)
    _, _, full_k = ttl_scan(*prob, use_kernel=True, interpret=True)
    _, _, full_r = ttl_scan(*prob, use_kernel=False)
    np.testing.assert_allclose(np.asarray(full_k), np.asarray(full_r),
                               rtol=2e-5, atol=1e-4)


def test_ttl_scan_kernel_blocks():
    """Sweep edge-block sizes (grid partitioning must not change results)."""
    prob = _hist_problem(40, 800, seed=7)
    ref_surface = None
    for block_e in (8, 64, 256):
        s = ttl_cost_surface(*[jnp.asarray(x) for x in prob],
                             block_e=block_e, interpret=True)
        if ref_surface is None:
            ref_surface = s
        else:
            np.testing.assert_allclose(np.asarray(s), np.asarray(ref_surface),
                                       rtol=1e-6)


def test_ttl_scan_matches_core_policy_math():
    """The kernel must agree with repro.core.ttl_policy.expected_cost_curve
    (the simulator's argmin path) -- the kernel IS the production fast path."""
    from repro.core.costmodel import GB, SECONDS_PER_MONTH
    from repro.core.histogram import AccessHistogram
    from repro.core.ttl_policy import expected_cost_curve

    h = AccessHistogram.empty()
    rng = np.random.default_rng(0)
    h.add_gaps(rng.uniform(1, 5e6, 500), rng.uniform(1e6, 1e9, 500))
    h.add_last(rng.uniform(1, 5e6, 200), rng.uniform(1e6, 1e9, 200))
    h.add_first_read(5e9, remote=True)

    s_gb_mo, n_gb = 0.026, 0.02
    ttls, cost = expected_cost_curve(h, s_gb_mo, n_gb)
    s = np.float32(s_gb_mo / GB / SECONDS_PER_MONTH)
    n = np.float32(n_gb / GB)
    best_ttl, best_cost, full = ttl_scan(
        h.hist[None], h.time_weight[None], h.last[None], h.edges,
        np.asarray([s]), np.asarray([n]),
        np.asarray([h.first_read_remote_bytes]), interpret=True)
    np.testing.assert_allclose(np.asarray(full[0]), cost, rtol=2e-4)
    assert float(best_ttl[0]) == pytest.approx(
        float(ttls[np.argmin(cost)]), rel=0.03)


@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,off,dtype",
    [
        (2, 4, 2, 256, 256, 64, True, 0, jnp.float32),
        (1, 2, 2, 128, 384, 128, False, 0, jnp.float32),
        (1, 4, 1, 1, 512, 64, True, 511, jnp.float32),
        (2, 2, 2, 200, 200, 80, True, 0, jnp.float32),
        (1, 8, 4, 130, 257, 96, True, 0, jnp.float32),
        (2, 4, 4, 256, 256, 64, True, 0, jnp.bfloat16),
    ],
)
def test_flash_attention_vs_oracle(b, hq, hkv, sq, skv, d, causal, off, dtype):
    key = jax.random.PRNGKey(b * 31 + sq + skv)
    q = jax.random.normal(key, (b, hq, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, skv, d),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, skv, d),
                          jnp.float32).astype(dtype)
    out_k = flash_attention(q, k, v, causal=causal, q_offset=off,
                            interpret=True)
    out_r = flash_attention(q, k, v, causal=causal, q_offset=off,
                            use_kernel=False)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_block_sweep():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 384, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 384, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 384, 64))
    base = flash_attention(q, k, v, use_kernel=False)
    for bq, bkv in [(128, 128), (128, 256), (256, 128)]:
        out = flash_attention(q, k, v, block_q=bq, block_kv=bkv,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=3e-5, atol=3e-5)


def test_rwkv6_ref_matches_naive_loop():
    B, H, T, K = 1, 2, 7, 4
    rng = np.random.default_rng(0)
    r, k, v = (rng.normal(size=(B, H, T, K)).astype(np.float32)
               for _ in range(3))
    w = rng.uniform(0.5, 0.99, (B, H, T, K)).astype(np.float32)
    u = rng.normal(size=(H, K)).astype(np.float32)
    out, s_fin = ref.rwkv6_ref(*map(jnp.asarray, (r, k, v, w)), jnp.asarray(u))
    # naive python recurrence
    s = np.zeros((B, H, K, K), np.float32)
    outs = np.zeros((B, H, T, K), np.float32)
    for t in range(T):
        kv = k[:, :, t, :, None] * v[:, :, t, None, :]
        eff = s + u[None, :, :, None] * kv
        outs[:, :, t] = np.einsum("bhk,bhkv->bhv", r[:, :, t], eff)
        s = w[:, :, t, :, None] * s + kv
    np.testing.assert_allclose(np.asarray(out), outs, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_fin), s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 100, 123, 800, 896, 1024])
def test_inclusive_scan_any_length(n):
    """The Hillis-Steele scan has no power-of-2 requirement (its docstring
    says so): pin cumsum equivalence across awkward lengths."""
    rng = np.random.default_rng(n)
    # Positive samples: cancellation-free, so float32 association error
    # stays ~eps * log2(n) relative and a tight rtol is meaningful.
    x = rng.uniform(0.1, 2.0, size=(3, n)).astype(np.float32)
    out = _inclusive_scan(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out),
                               np.cumsum(x.astype(np.float64), axis=1),
                               rtol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 100, 123, 800, 896, 1024])
def test_exclusive_suffix_scan_any_length(n):
    """out[j] = sum(x[j+1:]) at awkward lengths, like the prefix scan."""
    rng = np.random.default_rng(n + 1)
    x = rng.uniform(0.1, 2.0, size=(3, n)).astype(np.float32)
    out = _exclusive_suffix_scan(jnp.asarray(x))
    want = np.cumsum(x.astype(np.float64)[:, ::-1], axis=1)[:, ::-1] - x
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)


def test_ttl_scan_long_ttl_candidates_match_float64():
    """A heavy head and a light tail of re-read and paused bytes: on the
    long-TTL candidates the bytes still missing or paused are tiny against
    the totals.  Taken as total - prefix in float32 they cancel to noise;
    the kernel must stay within float32 rounding of the float64 surface on
    every candidate."""
    from repro.core.ttl_policy import batched_cost_curves

    edges = cell_edges()
    c_dim = edges.shape[0]
    hist = np.zeros((1, c_dim), np.float32)
    last = np.zeros((1, c_dim), np.float32)
    hist[0, 0] = last[0, 0] = 1e12
    hist[0, -40::8] = last[0, -30::6] = 1e3
    time_w = (hist * edges[None].astype(np.float32) * 0.9).astype(np.float32)
    s = np.asarray([1e-17], np.float32)
    n = np.asarray([2e-11], np.float32)
    first = np.asarray([1e9], np.float32)
    surface = ttl_cost_surface(
        *[jnp.asarray(x, jnp.float32)
          for x in (hist, time_w, last, edges, s, n, first)],
        interpret=True)
    _, cost64 = batched_cost_curves(hist, time_w, last, edges, first, s, n)
    np.testing.assert_allclose(np.asarray(surface), cost64[:, 1:], rtol=1e-5)


@pytest.mark.parametrize("c_dim", [123, 257, 800, 900])
def test_ttl_scan_non_pow2_c_vs_ref(c_dim):
    """Non-power-of-2 candidate counts through the *kernel* path (padding to
    the 128-lane boundary + in-kernel scan) must match ref.ttl_cost_ref on
    the unpadded columns -- the regression the _inclusive_scan docstring
    points at."""
    prob = _hist_problem(9, c_dim, seed=c_dim)
    surface_k = ttl_cost_surface(*[jnp.asarray(x) for x in prob],
                                 interpret=True)
    surface_r = ref.ttl_cost_ref(*[jnp.asarray(x) for x in prob])
    assert surface_k.shape == (9, c_dim)
    np.testing.assert_allclose(np.asarray(surface_k), np.asarray(surface_r),
                               rtol=2e-5, atol=1e-4)
