"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--json PATH]

Prints a ``name,us_per_call,derived`` CSV line per benchmark (quick mode by
default so the suite completes in a few minutes on one CPU core; --full runs
the paper-scale protocols). Machine-readable results — one row per benchmark
with ``name`` / ``us_per_call`` / ``evals_per_sec`` / ``derived`` plus the
full result payloads — always go to the ONE canonical ``BENCH_results.json``
at the repo root (override the path with ``--json``), so the perf trajectory
is tracked across PRs from a single file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _csv(name: str, us_per_call: float, derived: str):
    print(f"CSV,{name},{us_per_call:.1f},{derived}")


def _fmt_imbalance(router: dict) -> str:
    # router_imbalance is None when no measured wave split across backends
    # (e.g. one backend sat in failure backoff for the whole window)
    def f(v):
        return f"{v:.2f}" if v is not None else "n/a"

    return (f";router_imbalance={f(router['latency']['imbalance'])}"
            f"(rr={f(router['round_robin']['imbalance'])})")


def _derived_and_rate(name: str, out) -> tuple[str, float | None]:
    """(derived summary string, evals/sec if the benchmark reports one)."""
    derived, rate = "", None
    if not out:
        return derived, rate
    if name.startswith("weak_scaling"):
        rows = out["weak_scaling"] if isinstance(out, dict) else out
        derived = f"min_efficiency={min(r['efficiency'] for r in rows):.3f}"
        if isinstance(out, dict) and "http_round_trips" in out:
            rt = out["http_round_trips"]["round_trip_reduction"]
            derived += f";http_rt_reduction={rt:.1f}x"
        if isinstance(out, dict) and "lockstep" in out:
            ls = out["lockstep"]
            derived += f";lockstep_speedup={ls['speedup']:.1f}x"
            rate = ls["ensemble_evals_per_sec"]
        if isinstance(out, dict) and "router" in out:
            derived += _fmt_imbalance(out["router"])
    elif name.startswith("batch_eval"):
        ts = out["tsunami_coarse"]
        derived = (f"tsunami_batch_speedup={ts['speedup']:.1f}x;"
                   f"fallback_points={out['fabric']['fallback_points']}")
        rate = ts["batch_evals_per_sec"]
    elif name.startswith("sparse_grid"):
        derived = f"speedup={out['speedup']:.1f};evals={out['total_evals']}"
    elif name.startswith("qmc"):
        derived = f"online_speedup={out['online_speedup']:.1f};relerr={out['rom_max_relerr']:.1e}"
    elif name.startswith("grad_mcmc"):
        derived = (f"mala_ess_per_wave={out['mala']['ess_per_wave']:.2f};"
                   f"rwm_ess_per_wave={out['rwm']['ess_per_wave']:.2f};"
                   f"ratio={out['ess_per_wave_ratio']:.2f}x")
        rate = out["mala"]["evals_per_sec"]
    elif name.startswith("surrogate_da"):
        surr = out["surrogate_three_stage"]
        derived = (
            f"coarse_evals_per_ess_reduction="
            f"{out['coarse_evals_per_ess_reduction']:.1f}x;"
            f"screen_pass_rate={surr['screen']['pass_rate']}"
        )
        rate = surr["coarse_evals_per_sec"]
    elif name.startswith("mlda"):
        derived = f"speedup={out['speedup']:.1f};evals={out['evals_per_level']}"
        if isinstance(out, dict) and "ensemble" in out:
            derived += f";ensemble_speedup={out['ensemble']['speedup']:.1f}x"
            rate = out["ensemble"]["ensemble_evals_per_sec"]
        if isinstance(out, dict) and "ensemble_mlda" in out:
            em = out["ensemble_mlda"]
            derived += f";ensemble_mlda_speedup={em['speedup']:.1f}x"
            rate = em["ensemble_evals_per_sec"]
        if isinstance(out, dict) and "router" in out:
            derived += _fmt_imbalance(out["router"])
    elif name.startswith("fused_sampler"):
        g, ts = out["gaussian"], out["tsunami_coarse"]
        derived = (
            f"fused_speedup_gaussian={g['speedup_vs_host_fabric']:.1f}x;"
            f"fused_speedup_tsunami={ts['speedup_vs_host_fabric']:.1f}x;"
            f"stencil_parity_err={out['swe_stencil']['max_abs_err_vs_jitted_ref']:.1e}"
        )
        rate = ts["fused_steps_per_sec"] * out["chains"]
    elif name.startswith("multi_tenant"):
        thr, pri = out["throughput"], out["priority"]
        derived = (
            f"throughput_ratio={thr['ratio']:.2f};"
            f"hi_p99_ratio={pri['p99_ratio']:.2f};"
            f"shared_hits={out['cache']['shared_hits_taken']};"
            f"sheds={out['admission']['sheds']};"
            f"corrupted={out['admission']['corrupted']}"
        )
        rate = thr["concurrent_evals_per_sec"]
    elif name.startswith("elastic_fleet"):
        ch, ck = out["chaos"], out["checkpoint"]
        derived = (
            f"chaos_throughput_ratio={ch['throughput_ratio']:.2f};"
            f"spec_dispatches={ch['spec_dispatches']};"
            f"resume_exact={ck['resume_exact']};"
            f"wave_savings={ck['wave_savings']:.2f}"
        )
        rate = ch["evals_per_sec"]
    elif name.startswith("second_order"):
        ml, rt, lp = out["mlda"], out["router"], out["laplace"]
        derived = (
            f"mala_ess_ratio={ml['ratio']:.2f}x;"
            f"laplace_full_wall_s={lp['full']['wall_s']:.1f};"
            f"imbalance_per_cap={rt['per_capability']:.2f}"
            f"(blended={rt['blended']:.2f})"
        )
        rate = ml["fine_evals_per_sec"]
    elif name == "roofline":
        fracs = [c["roofline_fraction"] for c in out]
        derived = f"cells={len(out)};median_frac={sorted(fracs)[len(fracs)//2]:.3f}"
    return derived, rate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="BENCH_results.json", metavar="PATH",
                    help="machine-readable results path (default: the "
                         "canonical BENCH_results.json at the repo root)")
    args, _ = ap.parse_known_args()
    quick = not args.full
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = {}
    rows = []

    from benchmarks import (
        batch_eval,
        elastic_fleet,
        fused_sampler,
        grad_mcmc,
        mlda_tsunami,
        multi_tenant,
        qmc_defects,
        roofline,
        second_order,
        sparse_grid_l2sea,
        surrogate_da,
        weak_scaling,
    )

    benches = [
        ("batch_eval", batch_eval.main),
        ("weak_scaling_fig5", weak_scaling.main),
        ("sparse_grid_l2sea_sec4.1", sparse_grid_l2sea.main),
        ("qmc_defects_sec4.2", qmc_defects.main),
        ("mlda_tsunami_sec4.3", mlda_tsunami.main),
        ("grad_mcmc_mala", grad_mcmc.main),
        ("fused_sampler", fused_sampler.main),
        ("surrogate_da_sec4.3", surrogate_da.main),
        ("second_order", second_order.main),
        ("elastic_fleet", elastic_fleet.main),
        ("multi_tenant", multi_tenant.main),
        ("roofline", roofline.main),
    ]
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====")
        t0 = time.monotonic()
        try:
            out = fn(quick=quick)
            dt = time.monotonic() - t0
            derived, rate = _derived_and_rate(name, out)
            results[name] = out
            rows.append(
                {
                    "name": name,
                    "us_per_call": round(dt * 1e6, 1),
                    "evals_per_sec": rate,
                    "derived": derived,
                }
            )
            _csv(name, dt * 1e6, derived)
        except Exception as e:  # noqa: BLE001
            _csv(name, -1, f"FAILED:{e!r}")
            if args.json:
                _write_json(args.json, quick, rows, results, failed=f"{name}: {e!r}")
            raise

    # ONE canonical results file (the old scratch copy under experiments/
    # is gone — experiments/ stays gitignored for ad-hoc local output)
    _write_json(args.json, quick, rows, results)
    print(f"\nresults -> {args.json}")


def _jsonable(o):
    try:
        return float(o)
    except Exception:  # noqa: BLE001
        return str(o)


def _write_json(path: str, quick: bool, rows: list, results: dict, failed: str | None = None):
    doc = {
        "schema": "bench-v1",
        "created_unix": time.time(),
        "mode": "quick" if quick else "full",
        "benchmarks": rows,
        "results": results,
    }
    if failed:
        doc["failed"] = failed
    Path(path).write_text(json.dumps(doc, indent=1, default=_jsonable))


if __name__ == "__main__":
    main()
