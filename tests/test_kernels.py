"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm_fused
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd.ops import ssd as ssd_op
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.ssd.ssd import ssd_kernel
from repro.apps.tsunami import _transect_step
from repro.kernels.swe.ref import swe_step_ref

FLASH_CASES = [
    # (B, nq, nkv, S, hd, causal, dtype, tol)
    (2, 4, 2, 256, 64, True, jnp.float32, 2e-5),
    (1, 4, 4, 128, 128, True, jnp.float32, 2e-5),
    (2, 8, 2, 256, 64, False, jnp.float32, 2e-5),
    (1, 2, 1, 512, 64, True, jnp.float32, 2e-5),
    (1, 4, 2, 256, 64, True, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
def test_flash_attention_vs_ref(case):
    B, nq, nkv, S, hd, causal, dtype, tol = case
    ks = jax.random.split(jax.random.key(S + nq), 3)
    q = jax.random.normal(ks[0], (B, nq, S, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, nkv, S, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, nkv, S, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, impl="interpret")
    ref = attention_ref(q, k, v, causal=causal)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < tol, err


SSD_CASES = [
    # (B, H, G, S, P, N, dtype, tol)
    (2, 4, 2, 256, 32, 16, jnp.float32, 1e-4),
    (1, 8, 1, 128, 64, 32, jnp.float32, 1e-4),
    (1, 2, 2, 384, 32, 16, jnp.float32, 1e-4),
]


@pytest.mark.parametrize("case", SSD_CASES, ids=str)
def test_ssd_vs_ref(case):
    B, H, G, S, P, N, dtype, tol = case
    ks = jax.random.split(jax.random.key(H + S), 5)
    x = jax.random.normal(ks[0], (B, H, S, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))).astype(dtype)
    Bm = (jax.random.normal(ks[2], (B, G, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[3], (B, G, S, N)) * 0.5).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=1.5))
    s0 = jnp.zeros((B, H, N, P), jnp.float32)
    y, s = ssd_kernel(x, dt, Bm, Cm, A, s0, interpret=True)
    yr, sr = ssd_ref(x, dt, Bm, Cm, A, s0)
    rel = float(jnp.max(jnp.abs(y - yr)) / (jnp.max(jnp.abs(yr)) + 1e-9))
    rel_s = float(jnp.max(jnp.abs(s - sr)) / (jnp.max(jnp.abs(sr)) + 1e-9))
    assert rel < tol and rel_s < tol, (rel, rel_s)


def test_ssd_adapter_matches_model_layout():
    from repro.configs import get_config
    from repro.models.ssm import ssd_scan

    cfg = get_config("mamba2-1.3b", reduced=True)
    g, r = cfg.ssm_ngroups, cfg.ssm_nheads // cfg.ssm_ngroups
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (2, 64, g, r, cfg.ssm_headdim), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 64, g, r)))
    Bm = jax.random.normal(ks[2], (2, 64, g, cfg.ssm_state)) * 0.5
    Cm = jax.random.normal(ks[3], (2, 64, g, cfg.ssm_state)) * 0.5
    A = -jnp.exp(jax.random.uniform(ks[4], (g, r)))
    y1, s1 = ssd_op(cfg, x, dt, Bm, Cm, A, impl="interpret")
    y2, s2 = ssd_scan(cfg, x, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=5e-4, atol=5e-4)


RMS_CASES = [
    (64, 128, jnp.float32, 1e-5),
    (256, 256, jnp.float32, 1e-5),
    (128, 512, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("case", RMS_CASES, ids=str)
def test_rmsnorm_vs_ref(case):
    n, d, dtype, tol = case
    x = jax.random.normal(jax.random.key(0), (n, d)).astype(dtype)
    w = (jax.random.normal(jax.random.key(1), (d,)) + 1.0).astype(dtype)
    out = rmsnorm_fused(x, w, impl="interpret")
    ref = rmsnorm_ref(x, w)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < tol, err


# -- SWE Rusanov step ---------------------------------------------------------

def _swe_state(kind, C=48, N=32):
    """[cells, batch] shallow-water states exercising the limiter branches."""
    x = np.linspace(0.0, 1.0, C)[:, None]
    batch = 1.0 + 0.1 * np.arange(N)[None, :] / N
    b = 0.1 * np.sin(3 * np.pi * x[:, 0])[:, None]  # [C, 1] bathymetry
    if kind == "lake_at_rest":
        h = np.maximum(0.8 - b, 0.0) * np.ones((1, N))
        hu = np.zeros((C, N))
    elif kind == "dam_break":
        h = np.where(x < 0.5, 1.2, 0.4) * batch
        hu = np.zeros((C, N))
    elif kind == "dry_bed":
        # right half below the dry threshold: wet/dry front hits the
        # desingularized velocity and the hu zeroing branch
        h = np.where(x < 0.5, 0.6 * batch, 1e-4)
        hu = np.where(x < 0.5, 0.05 * batch, 0.0)
    else:  # moving
        h = 0.7 + 0.2 * np.sin(2 * np.pi * x) * batch
        hu = 0.1 * np.cos(2 * np.pi * x) * batch
    return jnp.asarray(h), jnp.asarray(hu), jnp.asarray(b)


# the 1-D step every tsunami program runs, as a program traces it
_program_step = jax.jit(lambda h, hu, b: _transect_step(b, 0.02)(h, hu))


@pytest.mark.parametrize("kind", ["lake_at_rest", "dam_break", "dry_bed", "moving"])
def test_swe_step_vs_ref(kind):
    h, hu, b = _swe_state(kind)
    out_h, out_hu = _program_step(h, hu, b)
    ref_h, ref_hu = swe_step_ref(h, hu, b, 0.02)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(ref_h),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_hu), np.asarray(ref_hu),
                               rtol=1e-4, atol=1e-4)


def test_swe_step_bitwise_vs_jitted_ref():
    # eager and jitted differ by XLA's FMA contraction inside jit; the
    # jitted ref compiles to the same contractions, and `_sqrt_safe` is
    # `jnp.sqrt` in the primal, so this is bit-exact
    jref = jax.jit(lambda a, q, bb: swe_step_ref(a, q, bb, 0.02))
    for kind in ("dam_break", "dry_bed"):
        h, hu, b = _swe_state(kind)
        ref_h, ref_hu = jref(h, hu, b)
        out_h, out_hu = _program_step(h, hu, b)
        assert np.array_equal(np.asarray(out_h), np.asarray(ref_h))
        assert np.array_equal(np.asarray(out_hu), np.asarray(ref_hu))


def test_swe_step_well_balanced_and_dry_invariants():
    # lake at rest stays at rest (well-balanced hydrostatic reconstruction)
    h, hu, b = _swe_state("lake_at_rest")
    out_h, out_hu = _program_step(h, hu, b)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_hu), 0.0, atol=1e-6)
    # dry cells: depth stays non-negative, momentum zeroed below threshold
    h, hu, b = _swe_state("dry_bed")
    out_h, out_hu = _program_step(h, hu, b)
    oh, ohu = np.asarray(out_h), np.asarray(out_hu)
    assert (oh >= 0.0).all()
    assert (ohu[oh <= 0.05] == 0.0).all()


def test_swe_step_odd_batch_tile_clamp():
    # N=24: a batch that is not a power of two, as a trimmed wave can be
    h, hu, b = _swe_state("moving", N=24)
    out_h, out_hu = _program_step(h, hu, b)
    ref_h, ref_hu = swe_step_ref(h, hu, b, 0.02)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(ref_h),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_hu), np.asarray(ref_hu),
                               rtol=1e-4, atol=1e-4)
