"""Device-resident fused sampler blocks (ROADMAP item 3).

The host lockstep samplers in `uq.mcmc` made waves WIDE: one `[K, d]`
model wave per MCMC step instead of K single-point calls. But the hot loop
still pays one dispatch — and one full device round trip — per step, so on
a fast posterior the sampler is latency-bound at the driver/solver boundary
(exactly where QUEENS/UQpy-style frameworks stop). This module makes waves
DEEP as well: S sampler steps are fused into ONE jitted `jax.lax.scan`
block with

* on-device proposal generation — a `jax.random` key stream threaded
  through the scan carry (split per step, never reused),
* log-posterior evaluation through the model's native JAX batch path
  (any traceable ``[K, d] -> [K]`` callable; see the target builders),
* Metropolis accept/reject, and Robbins-Monro step-size adaptation for
  MALA, all inside the block,

so only every S-th state crosses the host boundary. The ``[K, d]`` chain
block and ``[K]`` log-density carry are sharded over the ctx mesh with the
same ``in_shardings`` / pow2-bucketing discipline the evaluate path uses
(`core.pool.ModelPool._dispatch_fn`), and the per-step-dispatch reference
path is the SAME compiled S=1 block driven from a host loop — which makes
the S=1 bit-exactness invariant (CONTRIBUTING) hold by construction and
keeps the fused-vs-per-step benchmark an apples-to-apples dispatch-cost
measurement.

Checkpointing reconciles with `core.fleet.CampaignCheckpoint` at block
boundaries: the carry arrays land as npy leaves and the PRNG key rides as
its raw key-data manifest (`CampaignCheckpoint.pack_key`), so a killed
campaign resumed with the same block size replays the identical key stream
— bit-exact, not just statistically indistinguishable.

The host numpy loops in `uq.mcmc` remain the reference implementation and
the only path for non-JAX backends (HTTP models, subprocess fleets); the
`ensemble_*` entry points there expose this module as ``fused_steps=S``.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.interface import next_pow2, pad_to_bucket
from repro.uq.mcmc import EnsembleResult

#: compile-once memo for (step closure, jitted block) pairs: the public
#: runners are called repeatedly in campaigns/benchmarks, and a fresh step
#: closure per call would defeat the jit cache and recompile the whole
#: S-length scan every time. Keyed on the sampler config (logpost_fn
#: IDENTITY included — a new target is a new program); LRU-bounded so
#: sweeping many configs cannot leak executables.
_BLOCK_MEMO: OrderedDict = OrderedDict()
_BLOCK_MEMO_MAX = 32


def _memo(key, build):
    got = _BLOCK_MEMO.get(key)
    if got is None:
        got = build()
        _BLOCK_MEMO[key] = got
        while len(_BLOCK_MEMO) > _BLOCK_MEMO_MAX:
            _BLOCK_MEMO.popitem(last=False)
    else:
        _BLOCK_MEMO.move_to_end(key)
    return got


def _f():
    """Carry dtype: float32 by default, float64 under jax_enable_x64."""
    return jnp.result_type(float)


# ---------------------------------------------------------------------------
# Traceable target builders
# ---------------------------------------------------------------------------


def gaussian_target(mean, cov=None) -> Callable:
    """Traceable ``[K, d] -> [K]`` log-density of N(mean, cov) (cov=None: I).
    The analytic target used by the exactness tests and the dispatch-cost
    benchmark — evaluation is a handful of FLOPs, so steps/s measures the
    sampler loop itself."""
    mean = jnp.asarray(mean, _f())
    prec = None if cov is None else jnp.asarray(np.linalg.inv(np.atleast_2d(cov)), _f())

    def logpost(xs: jax.Array) -> jax.Array:
        r = xs - mean
        if prec is None:
            return -0.5 * jnp.sum(r * r, axis=-1)
        return -0.5 * jnp.einsum("ki,ij,kj->k", r, prec, r)

    return logpost


def gaussian_likelihood_target(
    forward_fn: Callable, data, noise_sd, prior_bounds=None
) -> Callable:
    """Traceable log-posterior from a native JAX batch forward model:
    Gaussian likelihood on the observables plus an optional uniform box
    prior (out-of-box rows get -inf BEFORE the accept step, mirroring the
    host `batched_logpost` prior mask). `forward_fn` must be a lockstep
    ``[K, d] -> [K, m]`` program (e.g. `apps.tsunami._solve_batch` under
    `functools.partial`) — per-row independence is also what lets the MALA
    block take per-chain gradients with one vjp (block-diagonal Jacobian).
    """
    data = jnp.asarray(np.asarray(data, float), _f())
    noise_sd = jnp.asarray(np.asarray(noise_sd, float), _f())
    if prior_bounds is not None:
        lo = jnp.asarray([b[0] for b in prior_bounds], _f())
        hi = jnp.asarray([b[1] for b in prior_bounds], _f())

    def logpost(xs: jax.Array) -> jax.Array:
        ys = jnp.asarray(forward_fn(xs), _f())
        ll = -0.5 * jnp.sum(((ys - data) / noise_sd) ** 2, axis=-1)
        if prior_bounds is None:
            return ll
        inbox = jnp.all((xs >= lo) & (xs <= hi), axis=-1)
        return jnp.where(inbox, ll, -jnp.inf)

    return logpost


def _value_and_grad_rows(logpost_fn: Callable):
    """(lps [K], dlps/dx [K, d]) in one vjp: the log-posterior rows depend
    only on their own chain's row (lockstep batch => block-diagonal
    Jacobian), so pulling back a vector of ones IS the per-row gradient."""

    def value_grad(xs: jax.Array) -> tuple[jax.Array, jax.Array]:
        lps, pull = jax.vjp(logpost_fn, xs)
        return lps, pull(jnp.ones_like(lps))[0]

    return value_grad


# ---------------------------------------------------------------------------
# Step kernels (scan bodies)
# ---------------------------------------------------------------------------


def _rwm_step(logpost_fn, L, active=None):
    L = jnp.asarray(L, _f())

    def step(carry, _):
        key, k_prop, k_u = jax.random.split(carry["key"], 3)
        xs, lps = carry["xs"], carry["lps"]
        props = xs + jax.random.normal(k_prop, xs.shape, xs.dtype) @ L.T
        lp_props = logpost_fn(props)
        log_alpha = lp_props - lps
        log_alpha = jnp.where(jnp.isnan(log_alpha), -jnp.inf, log_alpha)
        log_u = jnp.log(jax.random.uniform(k_u, lps.shape, lps.dtype))
        accept = log_u < log_alpha
        if active is not None:
            accept = accept & active
        xs = jnp.where(accept[:, None], props, xs)
        lps = jnp.where(accept, lp_props, lps)
        out = {"key": key, "xs": xs, "lps": lps,
               "acc": carry["acc"] + accept.astype(lps.dtype)}
        return out, (xs, lps)

    return step


def _pcn_step(loglik_fn, prior_chol, beta, active):
    L0 = jnp.asarray(prior_chol, _f())
    beta = float(beta)
    root = np.sqrt(1.0 - beta**2)

    def step(carry, _):
        key, k_prop, k_u = jax.random.split(carry["key"], 3)
        xs, lls = carry["xs"], carry["lps"]
        xi = jax.random.normal(k_prop, xs.shape, xs.dtype) @ L0.T
        props = root * xs + beta * xi
        ll_props = loglik_fn(props)
        log_alpha = ll_props - lls
        log_alpha = jnp.where(jnp.isnan(log_alpha), -jnp.inf, log_alpha)
        log_u = jnp.log(jax.random.uniform(k_u, lls.shape, lls.dtype))
        accept = (log_u < log_alpha) & active
        xs = jnp.where(accept[:, None], props, xs)
        lls = jnp.where(accept, ll_props, lls)
        out = {"key": key, "xs": xs, "lps": lls,
               "acc": carry["acc"] + accept.astype(lls.dtype)}
        return out, (xs, lls)

    return step


def _mala_step(logpost_fn, C, L, Cinv, active, adapt_steps, target_accept):
    value_grad = _value_and_grad_rows(logpost_fn)
    C, L, Cinv = (jnp.asarray(a, _f()) for a in (C, L, Cinv))
    n_active = None  # bound below (active is a concrete bool array)
    n_active = jnp.sum(active.astype(_f()))

    def _logq(diff_minus_drift, eps):
        return -0.5 / eps**2 * jnp.einsum(
            "ki,ij,kj->k", diff_minus_drift, Cinv, diff_minus_drift
        )

    def step(carry, _):
        key, k_prop, k_u = jax.random.split(carry["key"], 3)
        xs, lps, gs, eps, i = (carry[k] for k in ("xs", "lps", "gs", "eps", "i"))
        drift = 0.5 * eps**2 * gs @ C.T
        props = xs + drift + eps * jax.random.normal(k_prop, xs.shape, xs.dtype) @ L.T
        lp_props, g_props = value_grad(props)
        drift_rev = 0.5 * eps**2 * g_props @ C.T
        log_q_fwd = _logq(props - xs - drift, eps)
        log_q_rev = _logq(xs - props - drift_rev, eps)
        log_alpha = (lp_props - lps) + (log_q_rev - log_q_fwd)
        log_alpha = jnp.where(jnp.isnan(log_alpha), -jnp.inf, log_alpha)
        log_u = jnp.log(jax.random.uniform(k_u, lps.shape, lps.dtype))
        accept = (log_u < log_alpha) & active
        xs = jnp.where(accept[:, None], props, xs)
        lps = jnp.where(accept, lp_props, lps)
        gs = jnp.where(accept[:, None], g_props, gs)
        # Robbins-Monro on eps, acceptance pooled over ACTIVE lanes only
        # (pow2-padding lanes always reject and would bias the rate down)
        pooled = jnp.sum(accept.astype(lps.dtype)) / n_active
        eps = jnp.where(
            i < adapt_steps,
            eps * jnp.exp((i + 1.0) ** -0.6 * (pooled - target_accept)),
            eps,
        )
        out = {"key": key, "xs": xs, "lps": lps, "gs": gs,
               "acc": carry["acc"] + accept.astype(lps.dtype),
               "eps": eps, "i": i + 1}
        return out, (xs, lps)

    return step


# ---------------------------------------------------------------------------
# Block driver
# ---------------------------------------------------------------------------


def _run_fused(
    step_fn,
    carry: dict,
    *,
    n_steps: int,
    fused_steps: int,
    per_step: bool = False,
    ctx=None,
    telemetry=None,
    checkpoint=None,
    checkpoint_every: int = 0,
    scalar_keys: tuple = (),
):
    """Drive `n_steps` of `step_fn` in jitted blocks of `fused_steps`.

    Returns (samples [Kp, n, d], lps_out [Kp, n], final carry, n_blocks) as
    host numpy. ``per_step=True`` compiles the SAME scan program with S=1
    and dispatches it once per step with a host round trip in between — the
    per-step reference both the benchmark and the S=1 bit-exactness test
    compare against. Checkpoints land at block boundaries (effective
    interval: `checkpoint_every` rounded down to a block multiple) with the
    rng key-data manifest, so resume replays the identical key stream."""
    S = 1 if per_step else int(fused_steps)
    if S < 1:
        raise ValueError(f"fused_steps must be >= 1, got {S}")
    if n_steps % S:
        raise ValueError(f"n_steps={n_steps} not a multiple of fused_steps={S}")
    n_blocks = n_steps // S
    Kp, d = carry["xs"].shape

    def _build_block():
        def block(c):
            return jax.lax.scan(step_fn, c, None, length=S)

        if ctx is not None:
            from repro.distributed.sharding import chain_carry_shardings

            csh = chain_carry_shardings(ctx, carry, Kp)
            ysh = ctx.sharding(None, "batch")  # scan stacks [S, Kp, ...]
            return jax.jit(block, in_shardings=(csh,),
                           out_shardings=(csh, (ysh, ysh)))
        return jax.jit(block)

    # memoized on the (already memoized) step closure: a repeat call with
    # the same sampler config reuses the compiled S-length scan
    block_jit = _memo(("block", step_fn, S, Kp, ctx), _build_block)

    samples = np.empty((Kp, n_steps, d))
    lps_out = np.empty((Kp, n_steps))
    start_block = 0
    resumed = checkpoint.resume() if checkpoint is not None else None
    if resumed is not None:
        arrays, meta, _step = resumed
        done = int(meta["steps_done"])
        start_block = done // S
        for k, v in carry.items():
            if k == "key":
                carry[k] = _unpack_key(arrays["rng_key"])
            else:
                carry[k] = jnp.asarray(arrays[k], v.dtype)
        samples[:, :done] = arrays["samples"]
        lps_out[:, :done] = arrays["lps_out"]

    def dispatch(c):
        if ctx is not None:
            with ctx.mesh:
                return block_jit(c)
        return block_jit(c)

    every_blocks = max(1, checkpoint_every // S) if checkpoint_every else 0
    for b in range(start_block, n_blocks):
        with tracing.span("fused.block", steps=S):
            carry, (xs_blk, lps_blk) = dispatch(carry)
            lo = b * S
            # host pull — ONE round trip per block (per step when per_step=True)
            tracing.wait((xs_blk, lps_blk))
            samples[:, lo:lo + S] = np.moveaxis(np.asarray(xs_blk), 0, 1)
            lps_out[:, lo:lo + S] = np.asarray(lps_blk).T
        if telemetry is not None:
            telemetry.note_steps(S, waves=1)
            # service-tier campaigns meter device-resident work through this
            # optional hook (budget charge + per-tenant fused-step telemetry;
            # it does NOT re-count steps — note_steps above already did)
            nb = getattr(telemetry, "note_fused_block", None)
            if nb is not None:
                nb(len(samples), S)
        if checkpoint is not None and every_blocks and (b + 1) % every_blocks == 0:
            done = (b + 1) * S
            arrays = {k: np.asarray(v) for k, v in carry.items() if k != "key"}
            arrays["rng_key"] = _pack_key(carry["key"])
            arrays["samples"] = samples[:, :done].copy()
            arrays["lps_out"] = lps_out[:, :done].copy()
            checkpoint.save(done, arrays, {
                "steps_done": done, "fused_steps": S,
                **{k: float(np.asarray(carry[k])) for k in scalar_keys},
            })
    return samples, lps_out, carry, n_blocks


def _pack_key(key) -> np.ndarray:
    from repro.core.fleet import CampaignCheckpoint

    return CampaignCheckpoint.pack_key(key)


def _unpack_key(data) -> jax.Array:
    from repro.core.fleet import CampaignCheckpoint

    return CampaignCheckpoint.unpack_key(data)


def _pad_chains(x0s: np.ndarray, ctx) -> tuple[np.ndarray, int]:
    """(padded x0s, original K): pow2 bucketing so every mesh/tile shape is
    one of a handful of specializations — identical to the evaluate path."""
    K = len(x0s)
    if ctx is None:
        return x0s, K
    bucket = max(next_pow2(K), ctx.n_data)
    padded, _ = pad_to_bucket(x0s, bucket)
    return padded, K


def _init_carry(x0s, key, ctx):
    dt = _f()
    x0s = np.atleast_2d(np.asarray(x0s, float))
    padded, K = _pad_chains(x0s, ctx)
    Kp = len(padded)
    xs = jnp.asarray(padded, dt)
    active = jnp.arange(Kp) < K
    return xs, active, K, Kp, key


# ---------------------------------------------------------------------------
# Fused runners (EnsembleResult-compatible)
# ---------------------------------------------------------------------------


def _sampler_span(run: Callable) -> Callable:
    """A `sampler.fused` span around each call of a fused runner, from entry
    to return, with the chains and the blocks of the result."""
    @functools.wraps(run)
    def traced(*args, **kwargs):
        with tracing.span("sampler.fused") as sp:
            res = run(*args, **kwargs)
            sp.set(chains=len(res.samples), blocks=res.n_waves - 1)
        return res

    return traced


@_sampler_span
def fused_ensemble_rwm(
    logpost_fn: Callable,
    x0s: np.ndarray,
    n_steps: int,
    prop_cov: np.ndarray,
    key,
    *,
    fused_steps: int,
    per_step: bool = False,
    ctx=None,
    telemetry=None,
    checkpoint=None,
    checkpoint_every: int = 0,
) -> EnsembleResult:
    """K lockstep RWM chains, S steps per device dispatch."""
    xs, active, K, Kp, key = _init_carry(x0s, key, ctx)
    L = np.linalg.cholesky(np.atleast_2d(prop_cov))
    step = _memo(("rwm", logpost_fn, L.tobytes(), K, Kp),
                 lambda: _rwm_step(logpost_fn, L, active))
    lps0 = jax.jit(logpost_fn)(xs)
    carry = {"key": key, "xs": xs, "lps": lps0,
             "acc": jnp.zeros(Kp, _f())}
    samples, lps_out, carry, n_blocks = _run_fused(
        step, carry, n_steps=n_steps, fused_steps=fused_steps,
        per_step=per_step, ctx=ctx, telemetry=telemetry,
        checkpoint=checkpoint, checkpoint_every=checkpoint_every,
    )
    acc = np.asarray(carry["acc"])[:K]
    return EnsembleResult(
        samples[:K], lps_out[:K], acc / n_steps,
        K * (n_steps + 1), n_blocks + 1,
    )


@_sampler_span
def fused_ensemble_pcn(
    loglik_fn: Callable,
    x0s: np.ndarray,
    n_steps: int,
    beta: float,
    key,
    *,
    prior_chol: np.ndarray | None = None,
    fused_steps: int,
    per_step: bool = False,
    ctx=None,
    telemetry=None,
    checkpoint=None,
    checkpoint_every: int = 0,
) -> EnsembleResult:
    """K lockstep pCN chains (centered Gaussian prior with Cholesky factor
    `prior_chol`, default I), S steps per device dispatch."""
    xs, active, K, Kp, key = _init_carry(x0s, key, ctx)
    d = xs.shape[1]
    L0 = np.eye(d) if prior_chol is None else np.atleast_2d(prior_chol)
    step = _memo(("pcn", loglik_fn, L0.tobytes(), float(beta), K, Kp),
                 lambda: _pcn_step(loglik_fn, L0, beta, active))
    lls0 = jax.jit(loglik_fn)(xs)
    carry = {"key": key, "xs": xs, "lps": lls0,
             "acc": jnp.zeros(Kp, _f())}
    samples, lps_out, carry, n_blocks = _run_fused(
        step, carry, n_steps=n_steps, fused_steps=fused_steps,
        per_step=per_step, ctx=ctx, telemetry=telemetry,
        checkpoint=checkpoint, checkpoint_every=checkpoint_every,
    )
    acc = np.asarray(carry["acc"])[:K]
    return EnsembleResult(
        samples[:K], lps_out[:K], acc / n_steps,
        K * (n_steps + 1), n_blocks + 1,
    )


@_sampler_span
def fused_ensemble_mala(
    logpost_fn: Callable,
    x0s: np.ndarray,
    n_steps: int,
    step_size: float,
    key,
    *,
    precond: np.ndarray | None = None,
    adapt_steps: int = 0,
    target_accept: float = 0.574,
    fused_steps: int,
    per_step: bool = False,
    ctx=None,
    telemetry=None,
    checkpoint=None,
    checkpoint_every: int = 0,
) -> EnsembleResult:
    """K lockstep MALA chains, S steps per device dispatch: drift gradients
    come from ONE vjp of the traceable log-posterior per step (block-
    diagonal Jacobian, see `_value_and_grad_rows`), and Robbins-Monro
    step-size adaptation runs inside the scan on the active-lane-pooled
    acceptance rate."""
    xs, active, K, Kp, key = _init_carry(x0s, key, ctx)
    d = xs.shape[1]
    C = np.eye(d) if precond is None else np.atleast_2d(np.asarray(precond, float))
    L = np.linalg.cholesky(C)
    Cinv = np.linalg.inv(C)
    step = _memo(
        ("mala", logpost_fn, C.tobytes(), int(adapt_steps),
         float(target_accept), K, Kp),
        lambda: _mala_step(logpost_fn, C, L, Cinv, active,
                           int(adapt_steps), float(target_accept)))
    lps0, gs0 = _memo(("mala-init", logpost_fn),
                      lambda: jax.jit(_value_and_grad_rows(logpost_fn)))(xs)
    carry = {
        "key": key, "xs": xs, "lps": lps0, "gs": gs0,
        "acc": jnp.zeros(Kp, _f()),
        "eps": jnp.asarray(float(step_size), _f()),
        "i": jnp.asarray(0, jnp.int32),
    }
    samples, lps_out, carry, n_blocks = _run_fused(
        step, carry, n_steps=n_steps, fused_steps=fused_steps,
        per_step=per_step, ctx=ctx, telemetry=telemetry,
        checkpoint=checkpoint, checkpoint_every=checkpoint_every,
        scalar_keys=("eps",),
    )
    acc = np.asarray(carry["acc"])[:K]
    return EnsembleResult(
        samples[:K], lps_out[:K], acc / n_steps,
        K * (n_steps + 1), n_blocks + 1,
        n_grad_waves=n_blocks + 1,
        final_step_size=float(np.asarray(carry["eps"])),
    )


def make_fused_rwm_subchain(
    logpost_fn: Callable, n_sub: int, prop_chol: np.ndarray
) -> Callable:
    """Compile-once fused RWM subchain for MLDA coarse levels.

    Returns ``run(xs, key) -> (ys, lp_ys, lp_start, acc_counts, key)``:
    all K chains advance `n_sub` coarse steps in ONE device dispatch (plus
    one for the start log-densities) and come back with exactly the
    quantities the delayed-acceptance ratio needs. No sample collection, no
    host traffic inside the subchain, and BOTH lp_start and lp_ys come from
    the same traceable `logpost_fn`, so the DA correction stays exact. The
    block program is jitted once here, not per subchain call."""
    step = _rwm_step(logpost_fn, prop_chol)
    init_lp = jax.jit(logpost_fn)

    @jax.jit
    def block(key, xs, lps):
        carry = {"key": key, "xs": xs, "lps": lps,
                 "acc": jnp.zeros(xs.shape[0], xs.dtype)}
        out, _ = jax.lax.scan(step, carry, None, length=n_sub)
        return out

    def run(xs, key):
        xs = jnp.asarray(np.atleast_2d(np.asarray(xs, float)), _f())
        lps = init_lp(xs)
        out = block(key, xs, lps)
        return (
            np.asarray(out["xs"], float), np.asarray(out["lps"], float),
            np.asarray(lps, float), np.asarray(out["acc"]), out["key"],
        )

    return run
