"""Ensemble MLDA over the evaluation fabric on the 2-D sea (paper §4.3): the
`mlda` driver's loop, K lockstep chains, hierarchy GP <- level 0 <- level 1,
consecutive `ensemble_mlda` calls each continuing from the last states,
through `EvaluationFabric(ModelBackend(TsunamiModel(grids)))` in one
campaign thread, where `grids` are the configuration's 2-D levels.

Set-up fits the GP level (one per observable) to one Sobol wave of level 0,
warms every chunk width through the model's public `evaluate_batch` at
points inside the prior box, and runs one MLDA call from K seeded prior starts.
The window ends at the first finest-level step after `--seconds`.
`correct` is the `mlda` driver's: returned rows at both PDE levels against
the plain reference, some chain moved in most finest-level steps, and the
share of chain-steps whose subchain proposal got no finest-level test
under the cell's limit. `run` is `mlda.run` but for the model it builds
and the warm-up points, which the 1-D driver fixes.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from bench import compare, harness
from bench.timed import TimedEvaluator, TimedModel

_mlda = harness.load_module(harness.BENCH / "drivers" / "mlda.py")
_gp_level, tested_proposals = _mlda._gp_level, _mlda.tested_proposals


def warm_widths(evaluate, K: int, level_configs, box) -> None:
    """At each level, one wave per chunk width the model's plan picks for
    waves of up to K points (the first wave size that picks it), at the
    centre of the prior box: every program a wave can run is compiled, and
    no width is solved twice."""
    from repro.apps.tsunami import chunk_plan

    first = {}
    for n in range(1, K + 1):
        for _, _, bucket in chunk_plan(n):
            first.setdefault(bucket, n)
    centre = np.mean(np.asarray(box, float), axis=1)
    for n in sorted(set(first.values())):
        for config in level_configs:
            evaluate(centre + np.arange(n)[:, None] * 1e-3, config)


def grids(cfg: dict) -> dict:
    """The configuration's levels as the program's 2-D grids."""
    from repro.apps.tsunami import Grid

    return {lv: Grid(nx=g["nx"], ny=g["ny"], smoothed=g["smoothed"], t_end=cfg["t_end_s"])
            for lv, g in enumerate(cfg["levels"])}


def run(s: harness.Session) -> harness.Outcome:
    from repro.apps.tsunami import TsunamiModel
    from repro.core.fabric import EvaluationFabric, ModelBackend
    from repro.uq.gp import GP
    from repro.uq.mcmc import batched_logpost
    from repro.uq.mlda import ensemble_mlda
    from repro.uq.qmc import sobol

    cfg, tr = s.cell.config, s.cell.traffic
    K, box, sd = tr["chains"], cfg["prior_box"], cfg["noise_sd"]
    r_start, r_sampler, r_check = harness.seed_streams(s.seed, 3)
    data = harness.observed_data(cfg)
    level_configs = [{"level": lv} for lv in range(len(cfg["levels"]))]

    model = TsunamiModel(grids(cfg))
    fabric = EvaluationFabric(ModelBackend(TimedModel(model, s.spans)))
    try:
        warm_widths(model.evaluate_batch, K, level_configs, box)
        # the GP level: one Sobol wave of level 0, one GP per observable; the
        # design is the mix's, so the GPs' programs are the same every run
        u = sobol(tr["gp_train_points"], len(box), scramble_seed=tr["gp_design_seed"])
        lo, hi = np.asarray(box).T
        X = lo + u * (hi - lo)
        Y = fabric.evaluate_batch(X, level_configs[0])
        gps = [GP.fit(X, Y[:, j], n_iters=tr["gp_fit_iters"]) for j in range(Y.shape[1])]

        def logprior(theta):
            return 0.0 if np.all((theta >= lo) & (theta <= hi)) else -np.inf

        def loglik(y):
            return float(compare.loglik(y, data, sd))

        timed = [TimedEvaluator(fabric, s.spans, f"fabric.l{c['level']}") for c in level_configs]
        levels = [_gp_level(gps, data, sd, box, s.spans)] + [
            batched_logpost(t, loglik, logprior, c) for t, c in zip(timed, level_configs)]
        prop_cov = np.diag(np.square(tr["proposal_sd"]))
        steps = tr["fine_steps_per_call"]

        def call(xs):
            res = ensemble_mlda(levels, xs, steps, tr["subsampling"], prop_cov, r_sampler)
            return res.samples[:, -1], res

        x_first = harness.prior_points(r_start, K, box)
        xs, _ = call(x_first)  # set-up: one call
        for t in timed:
            t.keep = True
        fine = timed[-1].rows
        n_calls, accept, frozen, tested = 0, [], [], 0
        with s.open_window() as win:
            while not win.over():
                prev, n_rows = xs, len(fine)
                xs, res = call(xs)
                n_calls += 1
                accept.append(res.accept_rates)
                tested += tested_proposals(fine[n_rows:], prev)
                for i in range(steps):  # did any chain move in this step?
                    frozen.append(not np.any(res.samples[:, i] != prev))
                    prev = res.samples[:, i]
                win.boundary()
        for t in timed:
            t.keep = False
        tel = fabric.telemetry()
    finally:
        fabric.shutdown()
    rows = {c["level"]: t.rows for t, c in zip(timed, level_configs)}
    untested = 1.0 - tested / (K * steps * n_calls)

    def check():
        ref = harness.reference_module(s.cell)
        lim, out = tr["limits"], []
        for level, n in tr["check_rows"].items():
            level = int(level)
            thetas = np.concatenate([th for th, _ in rows[level]])
            ys = np.concatenate([y for _, y in rows[level]])
            idx = compare.sample(r_check, len(thetas), n)
            want = compare.in_blocks(partial(ref.observables_block, cfg, level), thetas[idx])
            out.append(harness.Check(f"obs_gap_l{level}_sd",
                                     compare.obs_gap_sd(ys[idx], want, sd),
                                     lim[f"obs_gap_l{level}_sd"]))
        out.append(harness.Check("frozen_steps", float(np.mean(frozen)), lim["frozen_steps"]))
        out.append(harness.Check("untested_share", untested, lim["untested_share"]))
        return out

    return harness.Outcome(
        metrics={"samples_per_s": K * steps * n_calls / win.seconds},
        attempted=sum(len(r) for r in rows.values()), failed=0, check=check,
        notes={"fine_steps": steps * n_calls, "chains": K,
               # chains that never moved from their prior start: set-up and window
               "still_chains": float(np.mean(np.all(xs == x_first, axis=1))),
               "accept_rates": np.mean(accept, axis=0).tolist() if accept else [],
               "rows_returned": {lv: int(sum(len(th) for th, _ in r)) for lv, r in rows.items()},
               "cache_hit_rate": tel.get("cache_hit_rate"),
               "backend": {k: tel["backend"].get(k) for k in ("native_batches", "fallback_points")}},
    )
