import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_EXTRA", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("DRYRUN_DEVICES", "512")
).strip()

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
# ShapeDtypeStruct inputs (no allocation), record memory/cost analysis and the
# collective schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).
#
# Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
# Test: DRYRUN_DEVICES=8 PYTHONPATH=src python -m repro.launch.dryrun \
#           --arch qwen3-0.6b --shape train_4k --mesh tiny --reduced
#
# NOTE: the XLA_FLAGS assignment above must stay the very first statements —
# jax locks the host device count on first init.

import argparse
import json
import re
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_shape
from repro.distributed.sharding import ShardingCtx, sanitized_shardings, tree_shardings
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import model as M
from repro.models import transformer
from repro.types import SHAPES, TrainConfig, V5E

# per-arch dry-run overrides: trillion-param MoE needs bf16 optimizer moments
# to fit v5e HBM (see EXPERIMENTS.md §Dry-run notes)
OPT_DTYPE = {"kimi-k2-1t-a32b": "bfloat16"}

# ---------------------------------------------------------------------------
# Collective parsing (post-SPMD HLO text)
# ---------------------------------------------------------------------------

_DT_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_COLL_RE = re.compile(
    r"(\w[\w.\-]*) = \(?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\(",
)
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^=]*\}|\[\d+,\d+\]<=\[\d+\])")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DT_BYTES.get(dtype, 4)


def _group_size(attr_str: str, total: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attr_str)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]*)\}", attr_str)
    if m:
        return len(m.group(1).split(","))
    return total


def parse_collectives(hlo: str, n_devices: int) -> dict:
    """Ring-model per-device link bytes per collective class.

    accounting (documented in EXPERIMENTS.md):
      all-gather      : result is the gathered buffer; each device sends/recvs
                        (n-1)/n of it
      reduce-scatter  : (n-1)/n of the (pre-scatter) operand == result*n terms;
                        the HLO result is the scattered shard -> (n-1)*result
      all-reduce      : ring = reduce-scatter + all-gather = 2(n-1)/n * operand
      all-to-all      : (n-1)/n of operand
      collective-permute: full operand crosses one link
    """
    per_dev = {k: 0 for k in (
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
    )}
    counts = dict(per_dev)
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        _, dtype, dims, op = m.groups()
        if line.lstrip().startswith("ROOT"):
            pass
        res_bytes = _shape_bytes(dtype, dims)
        # tuple results: sum every element type in the line's result tuple
        if " = (" in line:
            tup = re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", line.split(" = (")[1].split(")")[0])
            res_bytes = sum(_shape_bytes(d, s) for d, s in tup)
        n = _group_size(line, n_devices)
        if n <= 1:
            continue
        if op == "all-gather":
            moved = res_bytes * (n - 1) / n
        elif op == "reduce-scatter":
            moved = res_bytes * (n - 1)
        elif op == "all-reduce":
            moved = 2 * res_bytes * (n - 1) / n
        elif op == "all-to-all":
            moved = res_bytes * (n - 1) / n
        else:  # collective-permute
            moved = res_bytes
        per_dev[op] += int(moved)
        counts[op] += 1
    return {"per_device_bytes": per_dev, "counts": counts}


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def build_cell(cfg, shape, ctx: ShardingCtx, tc: TrainConfig):
    """Returns (fn, abstract_args, in_shardings, out_shardings, donate)."""
    from repro.optim.adamw import adamw_init_abstract, opt_state_specs

    p_abs = M.abstract_params(cfg)
    p_spec = M.param_specs(cfg)
    p_sh = sanitized_shardings(ctx, p_abs, p_spec)
    b_abs, b_spec = M.batch_specs(cfg, shape, ctx)
    b_sh = sanitized_shardings(ctx, b_abs, b_spec)
    repl = NamedSharding(ctx.mesh, P())

    if shape.kind == "train":
        o_abs = adamw_init_abstract(p_abs, tc)
        o_sh = sanitized_shardings(ctx, o_abs, opt_state_specs(p_spec))

        def fn(params, opt_state, batch):
            return M.train_step(cfg, ctx, tc, params, opt_state, batch)

        out_sh = (p_sh, o_sh, {"nll": repl, "aux": repl, "loss": repl, "grad_norm": repl, "lr": repl})
        return fn, (p_abs, o_abs, b_abs), (p_sh, o_sh, b_sh), out_sh, (0, 1)

    if shape.kind == "prefill":
        cache_abs_pf, cache_specs = transformer.cache_decl(cfg, shape.global_batch, shape.seq_len, ctx)
        cache_sh = sanitized_shardings(ctx, cache_abs_pf, cache_specs)
        bat = ctx.rules["batch"] if shape.global_batch % ctx.n_data == 0 else None
        logits_sh = NamedSharding(ctx.mesh, P(bat, "model"))

        def fn(params, batch):
            return M.prefill_step(
                cfg, ctx, params, batch["tokens"], ctx_embed=batch.get("ctx_embed")
            )

        return fn, (p_abs, b_abs), (p_sh, b_sh), (logits_sh, cache_sh), ()

    # decode
    cache_abs = b_abs["cache"]
    cache_sh = sanitized_shardings(ctx, cache_abs, b_spec["cache"])
    bat = ctx.rules["batch"] if shape.global_batch % ctx.n_data == 0 else None
    logits_sh = NamedSharding(ctx.mesh, P(bat, "model"))
    tok_sh = tree_shardings(ctx, b_spec["token"])
    pos_sh = NamedSharding(ctx.mesh, P())

    def fn(params, cache, token, pos):
        return M.decode_step(cfg, ctx, params, cache, token, pos)

    return (
        fn,
        (p_abs, cache_abs, b_abs["token"], b_abs["pos"]),
        (p_sh, cache_sh, tok_sh, pos_sh),
        (logits_sh, cache_sh),
        (1,),
    )


def run_cell(arch: str, shape_name: str, mesh, *, reduced=False, save_hlo=None, overrides=None) -> dict:
    cfg = get_config(arch, reduced=reduced)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    if reduced:
        import dataclasses

        shape = dataclasses.replace(
            shape,
            seq_len=min(shape.seq_len, 128),
            global_batch=max(int(np.prod(mesh.devices.shape[:-1])), 2)
            if shape.global_batch > 16
            else shape.global_batch,
        )
    tc = TrainConfig(opt_state_dtype=OPT_DTYPE.get(arch, "float32"))
    ctx = ShardingCtx(mesh)
    fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, ctx, tc)

    t0 = time.time()
    with mesh:
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # jax returned [{...}] (one entry per program) before ~0.5, a flat dict after
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    n_dev = int(np.prod(mesh.devices.shape))
    hlo = compiled.as_text()
    if save_hlo:
        Path(save_hlo).write_text(hlo)

    # Trip-count-aware analysis: XLA's cost_analysis() counts lax.scan
    # (while-loop) bodies ONCE, undercounting layer-scanned models by ~L.
    # hlo_analysis multiplies per-computation costs by loop trip counts.
    from repro.launch.hlo_analysis import analyze as hlo_analyze

    parsed = hlo_analyze(hlo, n_dev)

    def _tree_local_bytes(abs_tree, sh_tree):
        total = 0
        for a, s in zip(jax.tree.leaves(abs_tree), jax.tree.leaves(sh_tree)):
            local = s.shard_shape(a.shape)
            n = 1
            for d in local:
                n *= d
            total += n * a.dtype.itemsize
        return total
    coll = {
        "per_device_bytes": parsed["collective_per_device_bytes"],
        "counts": parsed["collective_counts"],
    }
    flops = parsed["flops"]
    bytes_accessed = parsed["bytes_accessed"]
    xla_flops_uncorrected = float(cost.get("flops", 0.0)) if cost else 0.0
    xla_bytes_uncorrected = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    total_params, active_params = cfg.param_count()

    # ideal (must-move) bytes per device: parameters + decode KV cache r/w —
    # the floor for the memory term (used for decode roofline fractions)
    p_abs2 = M.abstract_params(cfg)
    from repro.distributed.sharding import sanitized_shardings as _ss

    p_sh2 = _ss(ctx, p_abs2, M.param_specs(cfg))
    param_local_bytes = _tree_local_bytes(p_abs2, p_sh2)
    cache_local_bytes = 0
    if shape.kind == "decode":
        cache_abs2, cache_spec2 = transformer.cache_decl(cfg, shape.global_batch, shape.seq_len, ctx)
        cache_sh2 = _ss(ctx, cache_abs2, cache_spec2)
        cache_local_bytes = _tree_local_bytes(cache_abs2, cache_sh2)

    # roofline terms (per-device program; flops/bytes from XLA are per device)
    coll_bytes = sum(coll["per_device_bytes"].values())
    terms = {
        "compute_s": flops / V5E.peak_flops_bf16,
        "memory_s": bytes_accessed / V5E.hbm_bandwidth,
        "collective_s": coll_bytes / V5E.ici_link_bandwidth,
    }
    dominant = max(terms, key=terms.get)

    # model flops: 6*N*D for train, 2*N*D for forward-only, per device
    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    mult = 6 if shape.kind == "train" else 2
    model_flops_global = mult * active_params * tokens
    model_flops = model_flops_global / n_dev

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": n_dev,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "reduced": reduced,
        "overrides": dict(overrides) if overrides else {},
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "xla_cost_analysis_flops_uncorrected": xla_flops_uncorrected,
        "xla_cost_analysis_bytes_uncorrected": xla_bytes_uncorrected,
        "collectives": coll,
        "collective_bytes_per_device": coll_bytes,
        "roofline_terms_s": terms,
        "dominant": dominant,
        "model_flops_per_device": model_flops,
        "useful_flops_fraction": (model_flops / flops) if flops else None,
        "total_params": total_params,
        "active_params": active_params,
        "param_local_bytes": param_local_bytes,
        "cache_local_bytes": cache_local_bytes,
        "memory_ideal_s": (param_local_bytes + 2 * cache_local_bytes) / V5E.hbm_bandwidth,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both", "tiny"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))
    if args.mesh == "tiny":
        n = len(jax.devices())
        if n >= 8:
            meshes.append(("tiny", make_mesh((2, 2, 2), ("pod", "data", "model"))))
        else:
            meshes.append(("tiny", make_mesh((1, max(n, 1)), ("data", "model"))))

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            if shape_name == "long_500k" and not cfg.sub_quadratic:
                print(f"SKIP {arch} x long_500k (full attention; see DESIGN.md)")
                continue
            for mesh_name, mesh in meshes:
                tag = f"{arch}__{shape_name}__{mesh_name}"
                fp = outdir / f"{tag}.json"
                if fp.exists():
                    print(f"cached {tag}")
                    continue
                print(f"=== {tag} ===", flush=True)
                try:
                    res = run_cell(
                        arch, shape_name, mesh, reduced=args.reduced,
                        save_hlo=str(outdir / f"{tag}.hlo") if args.save_hlo else None,
                    )
                    fp.write_text(json.dumps(res, indent=1))
                    print(
                        f"  ok: compile={res['t_compile_s']}s "
                        f"flops/dev={res['hlo_flops_per_device']:.3e} "
                        f"coll/dev={res['collective_bytes_per_device']:.3e}B "
                        f"dominant={res['dominant']}",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)[:500]))
                    print(f"  FAIL: {e!r}"[:600], flush=True)
    if failures:
        print("\nFAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
