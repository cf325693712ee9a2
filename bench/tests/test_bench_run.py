"""``bench/run.py`` end to end on the CPU at a tiny size: the chip look is
skipped (``run_cell`` is called directly on the ``xla_ref`` backend) and
the rest of a run is driven, once as it is and once with the timed path
broken underneath, where ``correct`` has to come out false. Also: the
command itself refuses to run without a TPU, and in a checkout that holds
only the benchmark, and prints no result either time."""
import copy
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchlib import cell as cell_lib

RUN = cell_lib.BENCH / "run.py"
# The correctness limit at the test size, set like a cell's from readings
# of the cell's number, the mean logit gap (over 398 served tokens, seeds
# 1, 2, 3, 2**31 + 5, 77, on the CPU, danube cut to TINY): the program
# read 0.0125 to 0.0157 on both CPU backends, the float8 control 0.140 to
# 0.188. The cells' own limits, at their sizes, are in
# ``bench/checks/<cell>.json``; a check file's ``tiny_limit`` replaces
# this one for its cell, and a configuration's ``tiny`` keys replace TINY.
TINY_LIMIT = 0.05
TINY = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab_size": 256}


def tiny_cell(name: str):
    """The cell at a size a test run holds: the configuration's test cut
    (its ``tiny`` keys, else TINY with no window) over its own overrides,
    the cell's own mix, driver and reference, the test size's limit (the
    check file's ``tiny_limit``, else TINY_LIMIT)."""
    cell = copy.deepcopy(cell_lib.load_cell(name))
    tiny = cell.config.get("tiny", dict(TINY, window=None))
    cell.config["overrides"] = {**cell.config.get("overrides", {}), **tiny}
    cell.config["model"].update(tiny)
    cell.traffic["engine"].update(max_batch=4, cache_len=128,
                                  prefill_chunk=8)
    cell.traffic["prompt_len"].update(median=16, min=4, max=48)
    cell.traffic["output_len"].update(median=8, min=4, max=32)
    if "rate_rps" in cell.traffic:
        cell.traffic["rate_rps"] = 8.0
    cell.check["limit"] = cell.check.get("tiny_limit", TINY_LIMIT)
    return cell


@pytest.fixture(scope="module", autouse=True)
def _own_compile_cache(tmp_path_factory):
    """Runs here keep their compile cache in a temporary directory and
    leave JAX's cache settings as they found them."""
    import run as bench_run
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    cache = bench_run.CACHE_DIR
    bench_run.CACHE_DIR = tmp_path_factory.mktemp("jax_cache")
    yield
    bench_run.CACHE_DIR = cache
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def run_tiny(cell, hook=None, seconds=2.0):
    import run as bench_run
    return bench_run.run_cell(cell, 2 ** 31 + 77, seconds, False,
                              backend="xla_ref", t_process=time.perf_counter(),
                              engine_hook=hook)


# ----------------------------------------------------------- faults ----
def _alter_tokens(eng):
    """A token altered where it is produced: every decode step's sampled
    tokens move to the next vocabulary id."""
    step, v = eng._decode, eng.cfg.vocab_size
    eng._decode = lambda *a: (lambda o: ((o[0] + 1) % v, o[1]))(step(*a))


def _state_unchanged(eng):
    """A step that returns its state unchanged: decode steps hand back the
    KV cache they were given (a copy, since the step donates it)."""
    step = eng._decode

    def frozen(p, c, *a):
        keep = jax.tree.map(jnp.copy, c)
        return step(p, c, *a)[0], keep
    eng._decode = frozen


def _half_batch(eng):
    """Half of the batch left out: the upper half of the slots get the
    lower half's tokens instead of their own."""
    step = eng._decode

    def half(*a):
        toks, c = step(*a)
        h = toks.shape[0] // 2
        return jnp.concatenate([toks[:h], toks[:h]]), c
    eng._decode = half


@pytest.mark.parametrize("name", [w["name"] for w in cell_lib.load_json(
    cell_lib.ROOT / "BENCHMARK.json")["workloads"]])
def test_sound_run_is_correct(name):
    res = run_tiny(tiny_cell(name))
    assert res["correct"], res["checked"]
    assert res["attempted"] > 4 and res["failed"] == 0
    assert set(res["metrics"]) == {m.name for m in
                                   cell_lib.load_cell(name).end_to_end}
    assert list(res)[-2:] == ["checked", "_notes"]
    assert res["_notes"]["kind_mismatch"] == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(fault):
    res = run_tiny(tiny_cell("h2o-danube-1.8b.chat"), hook=fault)
    assert not res["correct"]
    got = res["checked"][tiny_cell("h2o-danube-1.8b.chat").check["number"]]
    assert got["value"] > got["limit"]


def test_control_reads_not_correct():
    """``bench/control.py``'s readings at the test size: the program within
    the limit, the float8 control through the same judgement not."""
    import control
    row = control.readings(tiny_cell("h2o-danube-1.8b.chat"), 2 ** 31 + 77,
                           2.0, backend="xla_ref")
    number = row["number"]
    assert row["correct"] and not row["control_correct"]
    assert row["program"][number] < TINY_LIMIT < row["control"][number]
    assert set(row["witness"]) == {"bfloat16"}


# --------------------------------------------------------- refusals ----
def _cmd(cwd, run):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(run), "--workload", "h2o-danube-1.8b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cmd(cell_lib.ROOT, RUN)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(cell_lib.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cell_lib.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _cmd(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
