#!/usr/bin/env python3
"""Run the tsunami UQ campaign end to end on a TPU, in one process.

    python chip_smoke.py             # one chip: every phase below but the last
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

Phases, each checked against a reference, at the model's full width (level 0:
512 cells, level 1: 2,048 cells):

* served: `TsunamiModel` behind the UM-Bridge HTTP server, driven by the
  `HTTPModel` client with evaluate waves at both levels, a gradient wave and
  a Hessian-vector wave. Evaluate rows must match the per-point reference
  `observables()` run on the host CPU.
* campaign: `ensemble_mlda` through `EvaluationFabric(ModelBackend(...))`,
  and one fused device-resident RWM block over the level-0 batch solver.
* kernel: the Pallas SWE stencil (`swe_impl="pallas"`) against the scan path;
  its lowering must hold `tpu_custom_call`, so it is not interpret mode.
* sharded (`--chips 4`): a `ModelPool` wave and a fused chain block over a
  4x1 `data` mesh must equal their one-device results.

A chip belongs to one process, so everything runs here. Without a TPU the
script exits non-zero and prints no result. Otherwise every phase runs, a
failing one prints its traceback, and the exit code is 0 only if all passed;
then the last line is one JSON object naming the device. Timings on earlier
lines are information, not claims.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

TRUE_THETA = np.array([90.0, 2.5])  # source position [km], amplitude [m]
PRIOR = ((30.0, 150.0), (0.5, 4.0))
NOISE_SD = np.array([0.5, 0.05, 0.5, 0.05])  # arrival [min], height [m]
PROP_COV = np.diag([8.0**2, 0.25**2])
SEED = 0  # draws every source point, chain start and proposal
# Tolerances per level, from the batch-vs-per-point tests
# (tests/test_batch_native.py): arrival [min] absolute, height relative.
# Level-1 arrivals get 0.1 min (20 fine steps) instead of the tests' 0.05:
# on a v5e chip the TPU and the host CPU, both float32, differ by up to
# 0.058 min there, while each is about 0.9 min from the float64 solution.
ARRIVAL_ATOL = {0: 0.05, 1: 0.1}
HEIGHT_RTOL = {0: 2e-2, 1: 5e-2}


def _log(tag: str, **fields) -> None:
    print(f"{tag}: {json.dumps(fields, default=float)}", flush=True)


def _points(rng: np.random.Generator, n: int) -> np.ndarray:
    """[n, 2] sources well inside the prior box, each reaching both buoys."""
    return np.stack([rng.uniform(40.0, 140.0, n), rng.uniform(0.8, 3.5, n)], axis=1)


def _in_prior(x: np.ndarray) -> bool:
    return all(
        np.all((x[..., i] >= lo) & (x[..., i] <= hi))
        for i, (lo, hi) in enumerate(PRIOR)
    )


def _compare(got: np.ndarray, ref: np.ndarray, level: int) -> dict:
    """Raise unless `got` matches `ref` to the observables' tolerances;
    return the largest errors."""
    np.testing.assert_allclose(got[:, [0, 2]], ref[:, [0, 2]], atol=ARRIVAL_ATOL[level])
    np.testing.assert_allclose(got[:, [1, 3]], ref[:, [1, 3]], rtol=HEIGHT_RTOL[level])
    rel = np.abs(got[:, [1, 3]] - ref[:, [1, 3]]) / np.abs(ref[:, [1, 3]])
    return {
        "arrival_max_abs_min": float(np.abs(got[:, [0, 2]] - ref[:, [0, 2]]).max()),
        "height_max_rel": float(rel.max()),
    }


def _check_finite_nonzero(name: str, x: np.ndarray, shape: tuple) -> None:
    if x.shape != shape:
        raise AssertionError(f"{name}: shape {x.shape}, expected {shape}")
    if not np.all(np.isfinite(x)):
        raise AssertionError(f"{name}: non-finite values")
    if not np.any(x != 0):
        raise AssertionError(f"{name}: all zero")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def served_phase(rng, n_eval: int = 64, n_grad: int = 16, n_hvp: int = 16,
                 n_ref: int = 4) -> dict:
    """Evaluate, gradient and HVP waves over HTTP; evaluate rows against the
    per-point reference on the host CPU."""
    import jax

    from repro.apps.tsunami import TsunamiModel, observables
    from repro.core.client import HTTPModel
    from repro.core.server import serve_models

    model = TsunamiModel()
    server, _ = serve_models([model], 0, background=True)
    info, evals = {}, {}
    try:
        client = HTTPModel(f"http://127.0.0.1:{server.server_address[1]}")

        def wave(name, call, *args):
            trips = client.round_trips
            t0 = time.perf_counter()
            out = call(*args)
            info[f"{name}_wall_s"] = time.perf_counter() - t0
            if client.round_trips != trips + 1:
                raise AssertionError(f"{name}: {client.round_trips - trips} round trips")
            return out

        for level in (0, 1):
            thetas = _points(rng, n_eval)
            ys = wave(f"evaluate_l{level}_x{n_eval}", client.evaluate_batch,
                      thetas, {"level": level})
            _check_finite_nonzero(f"evaluate level {level}", ys, (n_eval, 4))
            evals[level] = (thetas, ys)
        sens = np.tile([0.0, 1.0, 0.0, 1.0], (n_grad, 1))  # the height channels
        grads = wave(f"gradient_l1_x{n_grad}", client.gradient_batch,
                     evals[1][0][:n_grad], sens, {"level": 1})
        _check_finite_nonzero("gradient level 1", grads, (n_grad, 2))
        vecs = np.tile([1.0, 0.1], (n_hvp, 1))
        hvps = wave(f"hvp_l0_x{n_hvp}", client.apply_hessian_batch,
                    evals[0][0][:n_hvp], sens[:1].repeat(n_hvp, 0), vecs, {"level": 0})
        _check_finite_nonzero("hvp level 0", hvps, (n_hvp, 2))
    finally:
        server.shutdown()
        server.server_close()

    with jax.default_device(jax.devices("cpu")[0]):
        for level, (thetas, ys) in evals.items():
            n_cells = TsunamiModel.N_CELLS[level]
            ref = np.array([observables(t, n_cells, level == 0) for t in thetas[:n_ref]])
            info[f"reference_l{level}"] = _compare(ys[:n_ref], ref, level)
    return info


def campaign_phase(rng, n_chains: int = 64, n_fine: int = 3, n_sub: int = 4,
                   fused_steps: int = 8) -> dict:
    """Lockstep ensemble MLDA over the fabric, then one fused RWM block."""
    from repro.apps.tsunami import TsunamiModel, _solve_batch
    from repro.core.fabric import EvaluationFabric, ModelBackend
    from repro.uq.fused import gaussian_likelihood_target
    from repro.uq.mcmc import ensemble_random_walk_metropolis
    from repro.uq.mlda import ensemble_mlda

    model = TsunamiModel()
    data = model.evaluate_batch(TRUE_THETA[None], {"level": 1})[0]
    data = data + rng.standard_normal(4) * NOISE_SD * 0.5
    x0s = _points(rng, n_chains)

    def loglik(y):
        return float(-0.5 * np.sum(((np.asarray(y) - data) / NOISE_SD) ** 2))

    def logprior(theta):
        return 0.0 if _in_prior(np.asarray(theta)) else -np.inf

    info = {}
    fabric = EvaluationFabric(ModelBackend(model))
    try:
        t0 = time.perf_counter()
        res = ensemble_mlda(
            None, x0s, n_fine, [n_sub], PROP_COV, rng, fabric=fabric,
            level_configs=[{"level": 0}, {"level": 1}], loglik=loglik,
            logprior=logprior,
        )
        info["mlda_wall_s"] = time.perf_counter() - t0
        backend = fabric.telemetry()["backend"]
    finally:
        fabric.shutdown()
    if res.samples.shape != (n_chains, n_fine, 2):
        raise AssertionError(f"mlda samples shape {res.samples.shape}")
    if not (np.all(np.isfinite(res.samples)) and _in_prior(res.samples)):
        raise AssertionError("mlda samples not finite and inside the prior box")
    if backend["native_batches"] == 0 or backend["fallback_points"] != 0:
        raise AssertionError(f"fabric did not run native waves only: {backend}")
    info["mlda"] = {
        "waves": res.n_waves, "evals_per_level": res.evals_per_level,
        "accept_rates": res.accept_rates,
        "native_batches": backend["native_batches"],
        "fallback_points": backend["fallback_points"],
    }

    target = gaussian_likelihood_target(
        partial(_solve_batch, n_cells=TsunamiModel.N_CELLS[0], smoothed=True),
        data, NOISE_SD, PRIOR,
    )
    t0 = time.perf_counter()
    fused = ensemble_random_walk_metropolis(
        target, x0s, fused_steps, PROP_COV, rng, fused_steps=fused_steps
    )
    info["fused_wall_s"] = time.perf_counter() - t0
    if fused.samples.shape != (n_chains, fused_steps, 2):
        raise AssertionError(f"fused samples shape {fused.samples.shape}")
    if not (np.all(np.isfinite(fused.samples)) and _in_prior(fused.samples)):
        raise AssertionError("fused samples not finite and inside the prior box")
    info["fused_accept_rate"] = float(np.mean(fused.accept_rate))
    return info


def kernel_phase(rng) -> dict:
    """Pallas SWE stencil against the scan path on full-width waves."""
    import jax.numpy as jnp

    from repro.apps.tsunami import TsunamiModel, _solve_batch

    info = {}
    for level, lanes in ((0, 64), (1, 128)):
        n_cells, smoothed = TsunamiModel.N_CELLS[level], level == 0
        thetas = jnp.asarray(_points(rng, lanes), jnp.float32)
        hlo = _solve_batch.lower(thetas, n_cells, smoothed, "pallas").as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError(f"level {level}: Pallas lowering holds no tpu_custom_call")
        outs = {}
        for impl in ("pallas", "scan"):
            t0 = time.perf_counter()
            outs[impl] = np.asarray(_solve_batch(thetas, n_cells, smoothed, impl), float)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            np.asarray(_solve_batch(thetas, n_cells, smoothed, impl))
            info[f"{impl}_l{level}_x{lanes}_s"] = {
                "first_call": first, "second_call": time.perf_counter() - t0,
            }
        _check_finite_nonzero(f"pallas level {level}", outs["pallas"], (lanes, 4))
        info[f"pallas_vs_scan_l{level}"] = _compare(outs["pallas"], outs["scan"], level)
    return info


def _device_peaks(devices) -> dict:
    """Peak bytes per device where the backend reports them."""
    return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices}


def sharded_phase(rng, n_points: int = 64, fused_steps: int = 4) -> dict:
    """ModelPool wave and fused chain block on a 4x1 data mesh against the
    same inputs on one device."""
    import jax
    import jax.numpy as jnp

    from repro.apps.tsunami import TsunamiModel, _solve_batch
    from repro.core.interface import JAXModel
    from repro.core.pool import ModelPool
    from repro.distributed.sharding import ShardingCtx, make_test_mesh
    from repro.uq.fused import gaussian_likelihood_target
    from repro.uq.mcmc import ensemble_random_walk_metropolis

    devices = jax.devices()[:4]
    n_cells = TsunamiModel.N_CELLS[0]
    model = JAXModel(lambda th: _solve_batch(th[None], n_cells, True)[0], 2, 4)
    thetas = _points(rng, n_points)
    data = np.asarray(_solve_batch(jnp.asarray(TRUE_THETA[None], jnp.float32), n_cells, True))[0]
    target = gaussian_likelihood_target(
        partial(_solve_batch, n_cells=n_cells, smoothed=True), data, NOISE_SD, PRIOR
    )
    key = jax.random.key(int(rng.integers(0, 2**31 - 1)))

    def run(ctx):
        pool = ModelPool(model, ctx)
        ys = pool.evaluate(thetas)
        with ctx.mesh:  # the pool's own program, to see where its output lands
            out = pool._dispatch_fn()(jnp.asarray(thetas, jnp.float32))
        placed = {s.device.id for s in out.addressable_shards}
        fused = ensemble_random_walk_metropolis(
            target, thetas, fused_steps, PROP_COV, rng,
            fused_steps=fused_steps, fused_key=key, ctx=ctx,
        )
        return ys, placed, fused.samples

    info = {"peak_bytes_at_start": _device_peaks(devices)}
    t0 = time.perf_counter()
    ys1, placed1, samples1 = run(ShardingCtx(make_test_mesh(1, 1)))
    info["one_device_wall_s"] = time.perf_counter() - t0
    info["peak_bytes_after_one_device"] = _device_peaks(devices)
    if any(info["peak_bytes_after_one_device"][d.id] != info["peak_bytes_at_start"][d.id]
           for d in devices[1:]):
        raise AssertionError("the one-device run allocated on another device")
    t0 = time.perf_counter()
    ys4, placed4, samples4 = run(ShardingCtx(make_test_mesh(4, 1)))
    info["four_device_wall_s"] = time.perf_counter() - t0
    info["peak_bytes_after_four_devices"] = _device_peaks(devices)
    if placed1 != {devices[0].id}:
        raise AssertionError(f"one-device wave landed on devices {placed1}")
    if placed4 != {d.id for d in devices}:
        raise AssertionError(f"sharded wave landed on devices {placed4}, not all four")
    _check_finite_nonzero("pool wave", ys4, (n_points, 4))
    info["pool_max_abs_diff"] = float(np.abs(ys4 - ys1).max())
    info["fused_max_abs_diff"] = float(np.abs(samples4 - samples1).max())
    np.testing.assert_array_equal(ys4, ys1, err_msg="sharded ModelPool wave")
    np.testing.assert_array_equal(samples4, samples1, err_msg="sharded fused block")
    return info


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _cache_entries(cache_dir: Path) -> int:
    """Executables in JAX's persistent cache (one `<key>-cache` file each)."""
    return len(list(cache_dir.glob("*-cache")))


class _CompileMonitor:
    """Backend compile seconds and persistent-cache hits/misses, read from
    JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4x1 mesh")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the repository's sources are not beside this script ({ROOT / 'src'})")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        _fail(f"no TPU found: JAX's default backend is {backend!r}; not falling back")
    devices = jax.devices()
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees {len(devices)}")
    dev = devices[0]
    _log("device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
         jax=jax.__version__)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _log("compile_cache", dir=str(cache_dir), entries_at_start=_cache_entries(cache_dir))
    monitor = _CompileMonitor()

    rng = np.random.default_rng(SEED)
    phases = [("sharded", sharded_phase)] if args.chips == 4 else [
        ("served", served_phase), ("campaign", campaign_phase), ("kernel", kernel_phase),
    ]
    failed = []
    t_all = time.perf_counter()
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            info = phase(rng)
        except Exception:  # noqa: BLE001 — report every phase, fail at the end
            traceback.print_exc()
            failed.append(name)
            _log(f"phase {name}", ok=False, wall_s=time.perf_counter() - t0)
            continue
        _log(f"phase {name}", ok=True, wall_s=time.perf_counter() - t0, **info)
    _log("compile", backend_compile_s=monitor.compile_s, compiles=monitor.compiles,
         cache_hits=monitor.hits, cache_misses=monitor.misses,
         entries_at_end=_cache_entries(cache_dir), wall_s=time.perf_counter() - t_all)
    if failed:
        _fail(f"phase(s) failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
