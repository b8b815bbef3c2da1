"""Pallas kernels vs pure-jnp oracles (interpret=True): shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.moments import BetaParams, log_posterior_grid
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.posterior_grid import (
    posterior_grid_fleet_pallas,
    posterior_grid_pallas,
)


def _fleet_case(k, n, seed=0, zero_cols=False):
    """Synthetic K-worker telemetry with per-worker params and ragged masks."""
    key = jax.random.PRNGKey(seed)
    kf, kt, kp = jax.random.split(key, 3)
    f = jax.random.uniform(kf, (k, n), minval=0.05, maxval=0.95)
    mu = jnp.linspace(5.0, 40.0, k)
    t = f**0.9 * mu[:, None] + f**0.7 * 2.0 * jax.random.normal(kt, (k, n))
    # per-worker ragged validity + (optionally) whole zeroed columns
    mask = (jnp.arange(n)[None, :] < jnp.linspace(n // 2, n, k)[:, None]).astype(
        jnp.float32
    )
    if zero_cols:
        mask = mask * (jnp.arange(n) % 5 != 0).astype(jnp.float32)[None, :]
    lam = jnp.linspace(0.1, 0.5, k)
    alpha = jnp.linspace(0.6, 0.95, k)
    beta = jnp.linspace(0.5, 0.9, k)
    ap = BetaParams(jnp.linspace(1.5, 4.0, k), jnp.linspace(2.0, 3.0, k))
    bp = BetaParams(jnp.linspace(2.0, 5.0, k), jnp.linspace(1.5, 2.5, k))
    return t, f, mask, mu, lam, alpha, beta, ap, bp


def _assert_logp_close(got, want, rtol=2e-5):
    scale = 1.0 + float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=rtol, atol=rtol * scale
    )


@pytest.mark.parametrize("zero_cols", [False, True])
@pytest.mark.parametrize(
    "k,g,n",
    [(1, 64, 100), (3, 300, 777), (4, 512, 128), (5, 17, 33),
     # K off the 8-worker groups; N = 64 (the ring) unpadded; N past the N
     # block; G off the 128 lanes
     (13, 256, 64), (17, 300, 64), (2, 64, 777), (2, 17, 4096)],
)
def test_posterior_grid_fleet_parity(k, g, n, zero_cols):
    """One fused launch (interpret mode) == unified oracle, both modes, for
    odd/padded G and N, per-worker priors, and zero-mask columns."""
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k, n, zero_cols=zero_cols)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    got = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
        interpret=True, block_g=128, block_n=256,
    )
    want = log_posterior_grid(grid, t, f, mu, lam, alpha, beta, ap, bp, mask)
    assert got.shape == (k, 2, g)
    _assert_logp_close(got, want)


def test_oracle_symmetric_grid_identity():
    """On the (symmetric) exponent grid, the mirrored-pg^2 beta mode —
    the production fast path — must agree with the general reciprocal form."""
    from repro.core.moments import exponent_grid

    k, n = 3, 250
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k, n, seed=9)
    for g in (64, 257):  # even and odd (padded) grid sizes
        grid = exponent_grid(g)
        general = log_posterior_grid(
            grid, t, f, mu, lam, alpha, beta, ap, bp, mask, symmetric_grid=False
        )
        mirrored = log_posterior_grid(
            grid, t, f, mu, lam, alpha, beta, ap, bp, mask, symmetric_grid=True
        )
        _assert_logp_close(mirrored, general, rtol=1e-5)


def test_posterior_grid_fleet_matches_vmapped_oracle():
    """The fleet axis of one launch == vmapping the oracle worker by worker."""
    k, g, n = 4, 96, 200
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k, n, seed=3)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    got = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
        interpret=True,
    )
    want = jax.vmap(
        lambda ti, fi, mi, mui, lami, ai, bi, apa, apb, bpa, bpb: log_posterior_grid(
            grid, ti, fi, mui, lami, ai, bi,
            BetaParams(apa, apb), BetaParams(bpa, bpb), mi,
        )
    )(t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b)
    _assert_logp_close(got, want)


def test_posterior_grid_single_unit_is_fleet_slice():
    """The legacy single-unit, single-mode entry == the matching row of the
    fused fleet launch with K=1."""
    g, n = 128, 300
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(1, n, seed=5)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    fleet = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
        interpret=True,
    )
    got_a = posterior_grid_pallas(
        grid, t[0], f[0], mask[0], mu[0], lam[0], beta[0], ap.a[0], ap.b[0],
        mode="alpha", interpret=True,
    )
    got_b = posterior_grid_pallas(
        grid, t[0], f[0], mask[0], mu[0], lam[0], alpha[0], bp.a[0], bp.b[0],
        mode="beta", interpret=True,
    )
    _assert_logp_close(got_a, fleet[0, 0], rtol=1e-6)
    _assert_logp_close(got_b, fleet[0, 1], rtol=1e-6)


def test_posterior_grid_fleet_fully_masked_worker():
    """A worker with zero valid observations must fall back to its prior
    (finite everywhere, no NaN/Inf from the dead telemetry)."""
    k, g, n = 3, 64, 150
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k, n, seed=7)
    mask = mask.at[1].set(0.0)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    got = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
        interpret=True,
    )
    assert bool(jnp.all(jnp.isfinite(got)))
    want = log_posterior_grid(grid, t, f, mu, lam, alpha, beta, ap, bp, mask)
    _assert_logp_close(got, want)
    # prior-only: the dead worker's alpha posterior is exactly the Beta prior
    gc = jnp.clip(grid, 1e-6, 1 - 1e-6)
    prior_only = (ap.a[1] - 1.0) * jnp.log(gc) + (ap.b[1] - 1.0) * jnp.log1p(-gc)
    np.testing.assert_allclose(
        np.asarray(got[1, 0]), np.asarray(prior_only), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("k,pad", [(13, 3), (17, 9)])
def test_posterior_grid_fleet_masked_pad_rows(k, pad):
    """Fully masked trailing rows, as the sharded launch pads a fleet, at
    the served width (G = 256, N = 64) and the default tile: the real rows
    match the oracle and the pad rows read their prior."""
    n, g = 64, 256
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k + pad, n, seed=11)
    mask = mask.at[k:].set(0.0)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    got = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
        interpret=True,
    )
    want = log_posterior_grid(grid, t, f, mu, lam, alpha, beta, ap, bp, mask)
    assert bool(jnp.all(jnp.isfinite(got)))
    _assert_logp_close(got, want)
    gc = jnp.clip(grid, 1e-6, 1 - 1e-6)
    prior = lambda p: (p.a[k:, None] - 1.0) * jnp.log(gc) + (
        p.b[k:, None] - 1.0) * jnp.log1p(-gc)
    np.testing.assert_allclose(np.asarray(got[k:, 0]), np.asarray(prior(ap)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[k:, 1]), np.asarray(prior(bp)),
                               rtol=1e-4, atol=1e-4)


def test_posterior_grid_fleet_counts_its_padding():
    """Tracing the launcher adds the useful cells K*G*N and the cells its
    tile evaluates to the obs counters; at the ring's N = 64 the tile pads
    next to nothing."""
    from repro import obs

    k, g, n = 16, 256, 64
    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in [(g,), (k, n), (k, n), (k, n)] + [(k,)] * 8]
    names = ("kernels.posterior_grid.cells", "kernels.posterior_grid.padded_cells")
    before = [obs.snapshot()["counters"].get(name, 0) for name in names]
    launcher = posterior_grid_fleet_pallas.__wrapped__  # trace afresh
    jax.eval_shape(functools.partial(launcher, interpret=True), *args)
    cells, padded = (obs.snapshot()["counters"].get(name, 0) - b
                     for name, b in zip(names, before))
    assert cells == k * g * n
    assert cells <= padded <= 1.1 * cells


@pytest.mark.parametrize("mode", ["alpha", "beta"])
@pytest.mark.parametrize("g,n", [(64, 100), (300, 777), (512, 2048), (17, 33)])
def test_posterior_grid_shapes(mode, g, n):
    key = jax.random.PRNGKey(g * 1000 + n)
    kf, kt = jax.random.split(key)
    f = jax.random.uniform(kf, (n,), minval=0.05, maxval=0.95)
    t = f**0.9 * 25.0 + f**0.7 * 2.0 * jax.random.normal(kt, (n,))
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    mask = (jnp.arange(n) % 7 != 0).astype(jnp.float32)
    args = (jnp.float32(25.0), jnp.float32(0.25), jnp.float32(0.7),
            jnp.float32(2.0), jnp.float32(3.0))
    got = posterior_grid_pallas(
        grid, t, f, mask, *args, mode=mode, interpret=True,
        block_g=128, block_n=256,
    )
    want = ref.posterior_grid_ref(
        grid, t, f, args[0], args[1], args[2], args[3], args[4], mask, mode=mode
    )
    scale = 1.0 + float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5 * scale)


@pytest.mark.parametrize("block_g,block_n", [(128, 128), (128, 512), (256, 1024)])
def test_posterior_grid_block_invariance(block_g, block_n):
    """Result must not depend on the tiling: 2-5 G blocks (G = 600 past the
    default block of 512), 1-5 N blocks, partial last blocks of both."""
    key = jax.random.PRNGKey(5)
    kf, kt = jax.random.split(key)
    n, g = 513, 600
    f = jax.random.uniform(kf, (n,), minval=0.1, maxval=0.9)
    t = f * 10.0 + jax.random.normal(kt, (n,))
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    mask = jnp.ones((n,), jnp.float32)
    out = posterior_grid_pallas(
        grid, t, f, mask, 10.0, 1.0, 0.9, 2.0, 2.0,
        mode="alpha", interpret=True, block_g=block_g, block_n=block_n,
    )
    want = ref.posterior_grid_ref(
        grid, t, f, jnp.float32(10.0), jnp.float32(1.0), jnp.float32(0.9),
        jnp.float32(2.0), jnp.float32(2.0), mask, mode="alpha",
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=1e-3)


@pytest.mark.parametrize("block_g,block_n", [(8, 128), (64, 256), (128, 200)])
def test_posterior_grid_fleet_rejects_unaligned_blocks(block_g, block_n):
    """A G or N block off the 128 lanes is refused, not silently rounded."""
    k, g, n = 2, 100, 300
    t, f, mask, mu, lam, alpha, beta, ap, bp = _fleet_case(k, n)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    with pytest.raises(ValueError, match="multiples of 128"):
        posterior_grid_fleet_pallas(
            grid, t, f, mask, mu, lam, alpha, beta, ap.a, ap.b, bp.a, bp.b,
            interpret=True, block_g=block_g, block_n=block_n,
        )


def test_posterior_grid_ref_deprecation_names_unified_oracle():
    """The shim's DeprecationWarning must point callers at the CURRENT
    replacement — ``repro.core.moments.log_posterior_grid`` — and the
    equivalence the message promises must actually hold."""
    grid = jnp.linspace(1e-4, 1 - 1e-4, 8, dtype=jnp.float32)
    t = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    f = jnp.full((4,), 0.5, jnp.float32)
    args = (jnp.float32(1.0), jnp.float32(1.0), jnp.float32(0.5),
            jnp.float32(2.0), jnp.float32(2.0))
    with pytest.warns(
        DeprecationWarning, match=r"repro\.core\.moments\.log_posterior_grid"
    ) as rec:
        out = ref.posterior_grid_ref(grid, t, f, *args, mode="alpha")
    assert "log_posterior_{alpha,beta}_ref" in str(rec[0].message)
    from repro.core.moments import log_posterior_alpha_ref

    want = log_posterior_alpha_ref(
        grid, t, f, args[0], args[1], args[2], BetaParams(args[3], args[4])
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kvh,d,s", [(2, 8, 2, 64, 300), (1, 4, 4, 32, 128), (3, 9, 3, 16, 1000)]
)
def test_decode_attention(b, h, kvh, d, s, dtype):
    key = jax.random.PRNGKey(b + h + s)
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, h, d), dtype)
    k = jax.random.normal(kk, (b, s, kvh, d), dtype)
    v = jax.random.normal(kv, (b, s, kvh, d), dtype)
    length = jax.random.randint(kl, (b,), 1, s + 1)
    got = decode_attention_pallas(q, k, v, length, block_s=128, interpret=True)
    want = ref.decode_attention_ref(q, k, v, length)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_attention_empty_tail_blocks_skipped():
    """Cache fill far below capacity: blocks past length must not contribute."""
    b, h, kvh, d, s = 2, 4, 1, 32, 2048
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, d))
    k = jax.random.normal(kk, (b, s, kvh, d))
    v = jax.random.normal(kv, (b, s, kvh, d))
    length = jnp.asarray([5, 17], jnp.int32)
    got = decode_attention_pallas(q, k, v, length, block_s=256, interpret=True)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,t,r,bt", [(2, 64, 128, 16), (1, 100, 300, 32), (3, 17, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lru_scan(b, t, r, bt, dtype):
    from repro.kernels.lru_scan import lru_scan_pallas

    key = jax.random.PRNGKey(b * t + r)
    ka, kb, kh = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(ka, (b, t, r))).astype(dtype)
    x = jax.random.normal(kb, (b, t, r), dtype)
    h0 = jax.random.normal(kh, (b, r), dtype)
    got = lru_scan_pallas(a, x, h0, block_t=bt, interpret=True)
    want = ref.lru_scan_ref(a, x, h0)
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_lru_scan_continuation_matches_single_pass():
    """Scanning [0:k] then [k:] with the carried state == one pass (the
    prefill->decode state-handoff property)."""
    from repro.kernels.lru_scan import lru_scan_pallas

    key = jax.random.PRNGKey(0)
    ka, kb = jax.random.split(key)
    b, t, r, k = 2, 48, 64, 20
    a = jax.nn.sigmoid(jax.random.normal(ka, (b, t, r)))
    x = jax.random.normal(kb, (b, t, r))
    h0 = jnp.zeros((b, r))
    full = lru_scan_pallas(a, x, h0, block_t=16, interpret=True)
    first = lru_scan_pallas(a[:, :k], x[:, :k], h0, block_t=16, interpret=True)
    second = lru_scan_pallas(a[:, k:], x[:, k:], first[:, -1], block_t=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(second), np.asarray(full[:, k:]), rtol=1e-5, atol=1e-5
    )
