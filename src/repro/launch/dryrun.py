import os
# The dry-run lowers for 512 virtual CPU devices. Both settings MUST
# precede any jax import (jax locks platform and device count at first
# init): pin the CPU, so a machine with an accelerator does not hand the
# dry-run its chips, and add the device count to whatever XLA_FLAGS the
# caller set. This file is the ONLY place the flag is set — smoke
# tests/benches see 1 CPU device.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ASSIGNED, get_config          # noqa: E402
from repro.launch import hlo_cost                       # noqa: E402
from repro.launch import mesh as mesh_lib               # noqa: E402
from repro.launch import sharding as sh                 # noqa: E402
from repro.launch import specs as specs_lib             # noqa: E402
from repro.models import shard as shard_ctx             # noqa: E402
from repro.train import state as state_lib              # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^\s]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str):
    """Per-device bytes moved by collectives: sum of output-tuple sizes of
    every collective op in the scheduled HLO (post-SPMD = per-partition)."""
    per_kind = {}
    counts = {}
    for m in _COLL_RE.finditer(hlo_text):
        ty, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(ty):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        counts[kind] = counts.get(kind, 0) + 1
    return per_kind, counts


def _shardings_for_batch(batch_specs, mesh, rules):
    dp = rules["batch"]

    def one(path, s):
        name = str(path[-1].key)
        if name in ("tokens", "labels"):
            return NamedSharding(mesh, P(dp, None))
        if name in ("embeds", "frames"):
            return NamedSharding(mesh, P(dp, None, None))
        if name == "positions":
            return NamedSharding(mesh, P(None, dp, None))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, batch_specs)


def _cache_pspec(path_keys, shape, mesh, rules):
    ax = dict(mesh.shape)
    model = ax.get("model", 1)
    dp = rules["batch"]
    name = path_keys[-1]

    def seq_ax(sz):
        return "model" if sz % model == 0 else None
    if name in ("k", "v"):              # [L,B,S,hk,dh]
        return P(None, dp, seq_ax(shape[2]), None, None)
    if name == "pos":                   # [L,B,S]
        return P(None, dp, seq_ax(shape[2]))
    if name == "h":                     # [L,B,H,P,N]
        return P(None, dp, "model" if shape[2] % model == 0 else None,
                 None, None)
    if name == "conv":                  # [L,B,K,C]
        return P(None, dp, None, "model" if shape[3] % model == 0 else None)
    return P(*([None] * len(shape)))


def _shardings_for_cache(cache_specs, mesh, rules):
    def one(path, s):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        return NamedSharding(mesh, _cache_pspec(keys, s.shape, mesh, rules))
    return jax.tree_util.tree_map_with_path(one, cache_specs)


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes")}
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def _cost_dict(compiled):
    try:
        ca = compiled.cost_analysis()
        return {"flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
                "transcendentals": float(ca.get("transcendentals", -1))}
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def run_cell(arch: str, shape: str, multi_pod: bool, *,
             save_hlo: str = "", hoist: bool = False) -> dict:
    skip = specs_lib.cell_skip_reason(arch, shape)
    if skip:
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "skipped": skip}
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    info = specs_lib.SHAPES[shape]
    rules = None
    t0 = time.time()

    with jax.set_mesh(mesh):
        if info["kind"] == "train":
            tcfg = specs_lib.train_config_for(arch, mesh)
            if hoist:
                import dataclasses as _dc
                tcfg = _dc.replace(tcfg, hoist_weight_quant=True)
            state_specs, cfg = specs_lib.train_state_specs(arch, tcfg)
            rules = sh.activation_rules(cfg, mesh, batch=info["batch"])
            state_sh = sh.tree_shardings(state_specs, cfg, mesh,
                                         serve=False, rules=rules)
            bspecs = specs_lib.batch_specs(arch, shape)
            if cfg.family == "vlm":
                bspecs = dict(bspecs)
                bspecs.pop("tokens")
                bspecs["embeds"] = jax.ShapeDtypeStruct(
                    (info["batch"], info["seq"], cfg.d_model), jnp.bfloat16)
            batch_sh = _shardings_for_batch(bspecs, mesh, rules)
            rng_spec = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            step = specs_lib.make_train_step(cfg, tcfg)
            with shard_ctx.sharding_rules(rules):
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh,
                                  NamedSharding(mesh, P())),
                    donate_argnums=(0,),
                ).lower(state_specs, bspecs, rng_spec)
        elif info["kind"] == "prefill":
            params_specs, cfg = specs_lib.param_specs(arch, serve=True)
            rules = sh.activation_rules(cfg, mesh, batch=info["batch"])
            p_sh = sh.tree_shardings(params_specs, cfg, mesh, serve=True,
                                     rules=rules)
            bspecs = specs_lib.batch_specs(arch, shape)
            if cfg.family == "vlm":
                bspecs = dict(bspecs)
                bspecs.pop("tokens")
                bspecs["embeds"] = jax.ShapeDtypeStruct(
                    (info["batch"], info["seq"], cfg.d_model), jnp.bfloat16)
            bspecs.pop("labels")
            batch_sh = _shardings_for_batch(bspecs, mesh, rules)
            step = specs_lib.make_prefill_step(cfg)
            with shard_ctx.sharding_rules(rules):
                lowered = jax.jit(
                    step, in_shardings=(p_sh, batch_sh),
                ).lower(params_specs, bspecs)
        else:  # decode
            params_specs, cfg = specs_lib.param_specs(arch, serve=True)
            rules = sh.activation_rules(cfg, mesh, batch=info["batch"])
            p_sh = sh.tree_shardings(params_specs, cfg, mesh, serve=True,
                                     rules=rules)
            dspecs = specs_lib.decode_specs(arch, shape)
            cache_sh = _shardings_for_cache(dspecs["cache"], mesh, rules)
            dp = rules["batch"]
            tok_sh = NamedSharding(mesh, P(dp))
            step = specs_lib.make_serve_step(cfg)
            with shard_ctx.sharding_rules(rules):
                lowered = jax.jit(
                    step,
                    in_shardings=(p_sh, cache_sh, tok_sh, tok_sh),
                    donate_argnums=(1,),
                ).lower(params_specs, dspecs["cache"], dspecs["tokens"],
                        dspecs["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    coll, coll_counts = collective_bytes(hlo)
    corrected = hlo_cost.analyze(hlo)   # trip-count-corrected per-device
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    out = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": info["kind"],
        "devices": int(np.prod(list(mesh.shape.values()))),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": _mem_dict(compiled),
        "cost": _cost_dict(compiled),
        "corrected": {
            "dot_flops": corrected.dot_flops,
            "bytes_accessed": corrected.bytes_accessed,
            "collective_bytes": corrected.collective_bytes,
            "collective_counts": corrected.collective_counts,
            "warnings": corrected.warnings[:10],
        },
        "collective_bytes": coll,
        "collective_counts": coll_counts,
        "fallbacks": sh.fallbacks(get_config(arch), mesh,
                                  batch=info["batch"]),
        "model_params": get_config(arch).param_count(),
        "active_params": get_config(arch).active_param_count(),
        "tokens_per_step": (specs_lib.SHAPES[shape]["batch"]
                            * specs_lib.SHAPES[shape]["seq"]
                            if info["kind"] == "train"
                            else specs_lib.SHAPES[shape]["batch"]),
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all"] + list(specs_lib.SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--hoist", action="store_true",
                    help="hoist weight fake-quant out of the microbatch "
                         "scan (perf experiment)")
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else args.arch.split(",")
    shapes = list(specs_lib.SHAPES) if args.shape == "all" \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if multi else '16x16'}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] {tag}: cached", flush=True)
                    n_ok += 1
                    continue
                print(f"[dryrun] {tag}: lowering...", flush=True)
                try:
                    res = run_cell(arch, shape, multi, hoist=args.hoist,
                                   save_hlo=args.save_hlo and
                                   os.path.join(args.save_hlo, tag + ".hlo"))
                    if "skipped" in res:
                        n_skip += 1
                        print(f"[dryrun] {tag}: SKIP ({res['skipped'][:60]})",
                              flush=True)
                    else:
                        n_ok += 1
                        m = res["memory"]
                        print(f"[dryrun] {tag}: OK compile={res['compile_s']}s"
                              f" arg={m.get('argument_size_in_bytes', 0)/2**30:.2f}GiB"
                              f" temp={m.get('temp_size_in_bytes', 0)/2**30:.2f}GiB"
                              f" flops={res['cost'].get('flops', 0):.3g}",
                              flush=True)
                except Exception:
                    n_fail += 1
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi else "16x16",
                           "error": traceback.format_exc()}
                    print(f"[dryrun] {tag}: FAIL\n{res['error']}",
                          flush=True)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed",
          flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
