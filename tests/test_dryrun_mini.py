"""Multi-device dry-run smoke in a subprocess (this test process must keep
1 CPU device; the subprocess forces 16 host devices and lowers a reduced
arch on a 4x4 mesh with the production sharding rules)."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, sys, dataclasses
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch import mesh as mesh_lib, sharding as sh, hlo_cost
from repro.models import lm, shard as shard_ctx
from repro.optim import adamw
from repro.train import state as state_lib

arch = sys.argv[1]
cfg = get_config(arch).reduced()
cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, mode="qat"))
mesh = mesh_lib.make_mesh((4, 4), ("data", "model"))
B, S = 8, 32
tcfg = state_lib.TrainConfig(num_microbatches=2)

with jax.set_mesh(mesh):
    state_specs = jax.eval_shape(
        lambda: state_lib.init_state(jax.random.PRNGKey(0), cfg, tcfg))
    rules = sh.activation_rules(cfg, mesh, batch=B)
    state_sh = sh.tree_shardings(state_specs, cfg, mesh, serve=False,
                                 rules=rules)
    bad = sh.validate_pspecs(state_specs,
                             sh.tree_pspecs(state_specs, cfg, mesh,
                                            serve=False, rules=rules), mesh)
    assert not bad, bad
    bspecs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "vlm":
        bspecs["positions"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        bspecs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                jnp.float32)
        bspecs.pop("tokens")
    if cfg.family == "audio":
        bspecs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.frontend_dim),
                                                jnp.float32)
    dp = rules["batch"]
    bsh = {k: NamedSharding(mesh, P(None, dp, None) if k == "positions"
                            else P(dp, *([None] * (len(v.shape) - 1))))
           for k, v in bspecs.items()}
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    with shard_ctx.sharding_rules(rules):
        lowered = jax.jit(
            lambda s, b, r: state_lib.train_step(s, b, cfg, tcfg, r),
            in_shardings=(state_sh, bsh, NamedSharding(mesh, P())),
            donate_argnums=(0,)).lower(state_specs, bspecs, rng)
        compiled = lowered.compile()
    t = hlo_cost.analyze(compiled.as_text())
    out = {
        "flops": t.dot_flops,
        "bytes": t.bytes_accessed,
        "coll": sum(t.collective_bytes.values()),
        "mem": int(compiled.memory_analysis().temp_size_in_bytes),
    }
    json.dump(out, open(sys.argv[2], "w"))
"""

ARCHS = ["h2o-danube-1.8b", "mixtral-8x22b", "mamba2-2.7b",
         "jamba-1.5-large-398b", "whisper-medium"]


def test_dryrun_mini_subprocess(tmp_path):
    script = str(tmp_path / "mini.py")
    with open(script, "w") as f:
        f.write(SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    for arch in ARCHS[:2]:      # two families is enough for CI time
        out = str(tmp_path / f"{arch}.json")
        subprocess.run([sys.executable, script, arch, out], env=env,
                       cwd=os.getcwd(), check=True, timeout=900)
        res = json.load(open(out))
        assert res["flops"] > 0
        assert res["bytes"] > 0
        assert res["coll"] > 0          # the mesh actually communicates


def test_full_dryrun_artifacts_present():
    """The production 40-cell x 2-mesh sweep must exist and be green."""
    d = "results/dryrun"
    if not os.path.isdir(d):
        import pytest
        pytest.skip("run python -m repro.launch.dryrun first")
    cells = [json.load(open(os.path.join(d, f)))
             for f in os.listdir(d) if f.endswith(".json")]
    assert len(cells) == 80
    errors = [c for c in cells if "error" in c]
    assert not errors, [(c["arch"], c["shape"], c["mesh"]) for c in errors]
    ok = [c for c in cells if "skipped" not in c]
    skipped = [c for c in cells if "skipped" in c]
    assert len(ok) == 68 and len(skipped) == 12     # 6 long_500k skips/mesh
    for c in ok:
        assert c["corrected"]["dot_flops"] > 0
        assert c["memory"]["temp_size_in_bytes"] >= 0
