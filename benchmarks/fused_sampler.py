"""Fused sampler blocks: steps/s per-step vs device-resident (`uq.fused`).

Two measurements, one per claim:

1. **Gaussian posterior** — the target costs a handful of FLOPs, so steps/s
   is a pure measurement of sampler-loop dispatch economics. Two
   comparators for the fused block:
   * ``per_step`` — the SAME compiled scan program with S=1, dispatched
     once per step with a host round trip (apples-to-apples dispatch cost;
     bit-identical trajectories).
   * ``host+fabric`` — `ensemble_random_walk_metropolis`'s host loop with a
     `batched_logpost` over an `EvaluationFabric`, i.e. the pre-fused
     campaign path every sampler in this repo used.
2. **Coarse tsunami posterior** — `apps.tsunami._solve_batch` at a reduced
   resolution chosen so the solve costs ~tens of µs and the run is
   DISPATCH-bound (at the paper's 512-cell coarse level the solve itself
   dominates and no loop restructuring can win 10x; the fused win there is
   the removed per-step latency floor, not wall-clock compute).

    PYTHONPATH=src python -m benchmarks.fused_sampler [--smoke] [--json PATH]

The two-size timing (`_net_rate`) subtracts the per-call fixed cost (init
log-density wave, host bookkeeping; the scan block itself is compiled once
and memoized in `uq.fused._BLOCK_MEMO`) — steady-state steps/s is the
honest number, matching how a campaign amortizes one large ``n_steps``.
"""
from __future__ import annotations

import argparse
import json
import time
from functools import partial
from pathlib import Path

import numpy as np


def _net_rate(run, n_big: int, n_small: int) -> float:
    """Steps/s with fixed per-call cost (compile, init wave) subtracted:
    run(n) twice at two sizes, rate = (n_big - n_small) / (t_big - t_small).
    The first small run populates persistent caches (XLA, bathymetry)."""
    run(n_small)
    t0 = time.perf_counter()
    run(n_small)
    t_small = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(n_big)
    t_big = time.perf_counter() - t0
    return float((n_big - n_small) / max(t_big - t_small, 1e-9))


def _bench_posterior(logpost_dev, loglik_host, x0s, prop_cov, *,
                     fused_steps: int, n_big: int, n_host: int) -> dict:
    """Fused vs per-step vs host+fabric steps/s on one traceable posterior."""
    import jax
    import jax.numpy as jnp

    from repro.core.fabric import EvaluationFabric
    from repro.uq.fused import fused_ensemble_rwm
    from repro.uq.mcmc import batched_logpost, ensemble_random_walk_metropolis

    key = jax.random.key(0)
    S = fused_steps

    fused = _net_rate(
        lambda n: fused_ensemble_rwm(logpost_dev, x0s, n, prop_cov, key,
                                     fused_steps=S),
        n_big, S)
    per_step = _net_rate(
        lambda n: fused_ensemble_rwm(logpost_dev, x0s, n, prop_cov, key,
                                     fused_steps=S, per_step=True),
        n_host, max(n_host // 10, 1))

    # pre-fused campaign path: host lockstep loop, one fabric wave per step
    lp_jit = jax.jit(logpost_dev)

    def model_batch(thetas, cfg=None):
        return np.atleast_2d(np.asarray(
            lp_jit(jnp.asarray(np.atleast_2d(thetas), jnp.float32)))).T

    fabric = EvaluationFabric(model_batch)
    try:
        lp_host = batched_logpost(fabric, loglik_host)
        host = _net_rate(
            lambda n: ensemble_random_walk_metropolis(
                lp_host, x0s, n, prop_cov, np.random.default_rng(0)),
            n_host, max(n_host // 10, 1))
    finally:
        fabric.shutdown()

    return {
        "fused_steps": S,
        "fused_steps_per_sec": fused,
        "per_step_steps_per_sec": per_step,
        "host_fabric_steps_per_sec": host,
        "speedup_vs_per_step": fused / per_step,
        "speedup_vs_host_fabric": fused / host,
    }


def main(quick: bool = True, smoke: bool = False) -> dict:
    import jax.numpy as jnp

    from repro.apps.tsunami import _solve_batch
    from repro.uq.fused import gaussian_likelihood_target, gaussian_target

    if smoke:
        S, n_big, n_host = 50, 500, 100
    elif quick:
        S, n_big, n_host = 200, 4000, 500
    else:
        S, n_big, n_host = 500, 20000, 2000

    # -- 1: Gaussian (dispatch economics in isolation) ------------------------
    d, K = 4, 8
    mean = np.ones(d)
    lp_gauss = gaussian_target(mean)
    x0s = np.random.default_rng(0).normal(size=(K, d))

    def loglik_gauss(y):
        return float(np.ravel(y)[0])

    gauss = _bench_posterior(
        lp_gauss, loglik_gauss, x0s, (2.4**2 / d) * np.eye(d),
        fused_steps=S, n_big=n_big, n_host=n_host)

    # -- 2: coarse tsunami posterior (dispatch-bound reduced level) ------------
    n_cells = 8 if (smoke or quick) else 16
    fwd = partial(_solve_batch, n_cells=n_cells, smoothed=True)
    data = np.asarray(fwd(jnp.asarray([[100.0, 1.0]], jnp.float32)))[0]
    lp_tsu = gaussian_likelihood_target(
        fwd, data, 0.2, prior_bounds=[(60.0, 140.0), (0.5, 1.5)])
    x0t = np.random.default_rng(1).uniform([80, 0.8], [120, 1.2], (K, 2))

    def loglik_tsu(y):
        return float(np.ravel(y)[0])

    tsunami = _bench_posterior(
        lp_tsu, loglik_tsu, x0t, np.diag([25.0, 0.01]),
        fused_steps=S, n_big=max(n_big // 2, S), n_host=n_host)
    tsunami["n_cells"] = n_cells

    doc = {
        "schema": "repro-fused-sampler-v1",
        "mode": "smoke" if smoke else ("quick" if quick else "full"),
        "chains": K,
        "gaussian": gauss,
        "tsunami_coarse": tsunami,
    }
    print(
        f"fused sampler: gaussian {gauss['fused_steps_per_sec']:.0f} steps/s "
        f"({gauss['speedup_vs_per_step']:.1f}x vs per-step, "
        f"{gauss['speedup_vs_host_fabric']:.1f}x vs host+fabric); "
        f"tsunami[{n_cells} cells] {tsunami['fused_steps_per_sec']:.0f} steps/s "
        f"({tsunami['speedup_vs_per_step']:.1f}x vs per-step, "
        f"{tsunami['speedup_vs_host_fabric']:.1f}x vs host+fabric)"
    )
    return doc


def _cli():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + loose speedup floor for CI")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the measurement document")
    args = ap.parse_args()
    doc = main(smoke=args.smoke)
    if args.json:
        # write BEFORE the gate checks: on failure the artifact is the
        # investigation's starting point
        Path(args.json).write_text(json.dumps(doc, indent=1))
        print(f"results -> {args.json}")
    # CI smoke gates: loose floors (loaded shared runners); the quick/full
    # numbers in BENCH_results.json carry the paper-level claim
    floor = 2.0 if doc["mode"] == "smoke" else 5.0
    for name in ("gaussian", "tsunami_coarse"):
        got = doc[name]["speedup_vs_host_fabric"]
        if got < floor:
            raise SystemExit(
                f"{name}: fused speedup {got:.1f}x below the {floor}x floor "
                f"— the fused block is not amortizing dispatch")


if __name__ == "__main__":
    _cli()
