"""A configuration of any registered family goes in through new files and
new ``BENCHMARK.json`` entries only. A hybrid MoE configuration with tied
embeddings (the registered ``jamba-1.5-large-398b``, one period deep),
written with its manifest, traffic and check into a temporary directory,
loads as a cell, passes the check against the program, keeps its own cut
at the test size, and gets a work count for each of its layer kinds. Its
sound run waits for its family's reference. Also: the work counts agree
with the program's own parameter count for every registered family, and
danube's test cut is what it was before configurations carried their
own."""
import copy
import json

import pytest

from benchlib import cell as cell_lib
from benchlib import flops, system
from benchlib.session import StepRecord
from test_bench_files import check_config, check_program
from test_bench_run import TINY, TINY_LIMIT, tiny_cell

CELL = "jamba-tied.chat"
JAMBA = {
    "name": "jamba-tied", "source": "hf:ai21labs/AI21-Jamba-1.5-Large "
    "(config.json), one period, embeddings tied",
    "hidden_size": 8192, "intermediate_size": 24576,
    "num_attention_heads": 64, "num_key_value_heads": 8,
    "num_hidden_layers": 8, "vocab_size": 65536, "num_experts": 16,
    "num_experts_per_tok": 2, "mamba_d_state": 128, "mamba_expand": 2,
    "tie_word_embeddings": True,
    "reduced": ["num_hidden_layers"], "published": {"num_hidden_layers": 72},
    "registry_arch": "jamba-1.5-large-398b",
    "overrides": {"num_layers": 8, "tie_embeddings": True},
    "tiny": {"d_model": 128, "num_heads": 4, "num_kv_heads": 2,
             "head_dim": 32, "d_ff": 256, "vocab_size": 256,
             "num_experts": 4, "ssm_state": 16},
    "quant": {"mix": [0.5, 0.375, 0.125], "quantize_activations": False},
    "precision": "bfloat16", "reference": "hybrid_moe",
    "model": {
        "family": "hybrid", "num_layers": 8, "d_model": 8192,
        "num_heads": 64, "num_kv_heads": 8, "head_dim": 128, "d_ff": 24576,
        "vocab_size": 65536, "window": None, "norm": "rms", "norm_eps": 1e-5,
        "mlp_act": "swiglu", "rope_theta": 1e4, "mix": [0.5, 0.375, 0.125],
        "num_experts": 16, "top_k": 2, "moe_every": 2, "ssm_state": 128,
        "ssm_expand": 2, "attn_every": 8, "attn_offset": 3,
        "tie_embeddings": True},
}


@pytest.fixture
def jamba_cell(tmp_path, monkeypatch):
    """The cell's files under a temporary checkout, and ``cell_lib``
    pointed at it."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "checks"):
        (bench / sub).mkdir(parents=True)
    real = cell_lib.load_json(cell_lib.ROOT / "BENCHMARK.json")
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "jamba-tied", "source": "https://huggingface.co/"
                     "ai21labs/AI21-Jamba-1.5-Large",
                     "file": "bench/configs/jamba-tied.json",
                     "reduced": ["num_hidden_layers"],
                     "why": "Mamba-2 beside attention 7:1, MoE every other "
                            "layer, tied embeddings"}],
        "workloads": [{"name": CELL, "config": "jamba-tied",
                       "traffic": "chat", "chips": 1,
                       "why": "the chat mix on a hybrid MoE"}],
        "end_to_end": [e for e in real["end_to_end"]
                       if e["name"] in ("setup_s", "output_tok_s")],
        "per_layer": [{"name": "mfu.prefill", "unit": "%",
                       "better": "higher", "source": "host_clock",
                       "layer": "Whole step", "moves": "output_tok_s",
                       "workloads": [CELL]}],
    }
    files = {
        "BENCHMARK.json": manifest,
        "bench/configs/jamba-tied.json": JAMBA,
        "bench/traffic/chat.json": cell_lib.load_json(
            cell_lib.BENCH / "traffic" / "chat.json"),
        f"bench/checks/{CELL}.json": {
            "sample_requests": 8, "number": "logit_gap_mean",
            "limit": 1.1, "control": "float8_e4m3fn", "tiny_limit": 0.07,
            "tiny_readings": "none: a fixture"},
    }
    for rel, body in files.items():
        (tmp_path / rel).write_text(json.dumps(body))
    monkeypatch.setattr(cell_lib, "ROOT", tmp_path)
    monkeypatch.setattr(cell_lib, "BENCH", bench)
    return tmp_path


def _step(fed, sampled):
    return StepRecord(0.0, 1.0, "prefill", fed, sampled, sampled)


def test_new_family_loads_as_a_cell(jamba_cell):
    cell = cell_lib.load_cell(CELL)
    assert cell.chips == 1 and cell.config == JAMBA
    assert cell.traffic["driver"] == "open_loop"
    assert cell.check["limit"] == 1.1
    assert [m.name for m in cell.end_to_end] == ["setup_s", "output_tok_s"]
    assert [m.name for m in cell.per_layer] == ["mfu.prefill"]


def test_new_family_passes_the_config_check(jamba_cell):
    check_config(cell_lib.load_cell(CELL).config)
    check_program(tiny_cell(CELL).config)


@pytest.mark.parametrize("key,wrong", [
    ("top_k", 3),                 # a stated key the program disagrees with
    ("moe_every", None),          # unstated: reads as 1, the program's is 2
    ("tie_embeddings", False),
])
def test_config_check_catches_a_family_key(key, wrong):
    conf = copy.deepcopy(JAMBA)
    if wrong is None:
        del conf["model"][key]
    else:
        conf["model"][key] = wrong
    with pytest.raises(AssertionError):
        check_config(conf)


def test_tiny_cell_keeps_the_files_cut(jamba_cell):
    cell = tiny_cell(CELL)
    assert cell.config["overrides"] == {**JAMBA["overrides"],
                                        **JAMBA["tiny"]}
    assert cell.config["model"] == {**JAMBA["model"], **JAMBA["tiny"]}
    assert cell.check["limit"] == 0.07
    cfg = system.arch_config(cell.config)
    assert cfg.layer_plan() == (("hybrid_unit", 1),)
    assert cfg.tie_embeddings and cfg.num_layers == 8
    assert (cfg.d_model, cfg.num_experts, cfg.ssm_state) == (128, 4, 16)


def test_danube_test_cut_is_unchanged():
    """Without ``tiny`` and ``tiny_limit`` the cut is TINY with no window,
    over no overrides, and the limit TINY_LIMIT, as before."""
    cell = tiny_cell("h2o-danube-1.8b.chat")
    base = cell_lib.load_cell("h2o-danube-1.8b.chat")
    cut = dict(TINY, window=None)
    assert cell.config == {**base.config, "overrides": cut,
                           "model": {**base.config["model"], **cut}}
    assert cell.traffic == {
        **base.traffic, "rate_rps": 8.0,
        "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                       "min": 4, "max": 48},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 32},
        "engine": {"max_batch": 4, "prefill_chunk": 8, "cache_len": 128,
                   "kv_bits": 4, "kv_layout": "ring"}}
    assert cell.check == {**base.check, "limit": TINY_LIMIT}


def test_work_counts_by_layer_kind_by_hand():
    """The tiny hybrid unit, 8 layers: sublayers 0-7 are mamba_mlp,
    mamba_moe, mamba_mlp, attn_moe, mamba_mlp, mamba_moe, mamba_mlp,
    mamba_moe. d 128, 4 heads (2 KV) of 32, d_ff 256, 4 experts top-2,
    Mamba-2 inner 256 in 4 heads of 64, state 16, vocab 256."""
    m = {**JAMBA["model"], **JAMBA["tiny"]}
    assert flops.layer_kinds(m) == [
        ("mamba", "mlp", 256, 1), ("mamba", "moe", 256, 1),
        ("mamba", "mlp", 256, 1), ("attn", "moe", 256, 1),
        ("mamba", "mlp", 256, 1), ("mamba", "moe", 256, 1),
        ("mamba", "mlp", 256, 1), ("mamba", "moe", 256, 1)]
    attn = 128 * 128 + 128 * 64 + 128 * 64 + 128 * 128     # q, k, v, o
    mamba = 128 * (2 * 256 + 2 * 16 + 4) + 256 * 128        # in, out proj
    mlp = 3 * 128 * 256                                     # up, gate, down
    router = 128 * 4
    routed = 2 * mlp               # top-2 of 4 experts, all 4 held
    step = _step([(0, 5), (10, 3)], 2)
    lin = 1 * attn + 7 * mamba + 4 * mlp + 4 * (router + routed)
    scan = 7 * 4 * 256 * 16 * 8                      # 7 layers, 8 tokens
    ctx = sum(range(1, 6)) + sum(range(11, 14))      # keys per query
    assert flops.model_step_flops(m, step) == \
        2 * 8 * lin + 2 * 2 * 128 * 256 + 4 * 4 * 32 * ctx + scan
    # The segment GEMM takes attention, Mamba and dense MLP linears; the
    # router and the stacked expert banks are not on its path.
    f, _ = cell_lib.load_module("work", "packed_linear").step_work(m, step)
    assert f == 2 * 8 * (attn + 7 * mamba + 4 * mlp)
    f, b = cell_lib.load_module("work", "attn_decode").step_work(m, step)
    assert f == 4 * 4 * 32 * ctx
    assert b == 2 * 2 * (16 + 2) * (5 + 13) + 2 * 8 * 4 * 32 * 4
    # Holding 2 of the 4 experts halves the routed work here.
    held = flops.model_step_flops({**m, "experts_held": 2}, step)
    assert held == flops.model_step_flops(m, step) - 2 * 8 * 4 * mlp


def _model_of(cfg):
    keys = ["num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
            "vocab_size", "mlp_act", *flops.LAYOUT]
    m = {k: getattr(cfg, k) for k in keys if hasattr(cfg, k)}
    return {**m, "head_dim": cfg.hd}


@pytest.mark.parametrize("arch", [
    "deepseek-67b", "deepseek-moe-16b", "h2o-danube-1.8b",
    "jamba-1.5-large-398b", "mamba2-2.7b", "mistral-large-123b",
    "mixtral-8x22b", "qwen2-vl-72b", "starcoder2-7b"])
def test_work_counts_hold_the_programs_parameters(arch):
    """Every linear the counts find, with all experts held, plus the
    embedding (and an untied head) and the Mamba-2 convolution and gains,
    is the program's own parameter count: the layer kinds are laid out as
    the program lays them out."""
    from repro.configs import get_config
    cfg = get_config(arch)
    m = _model_of(cfg)
    linear = sum(x.k * x.n * x.copies for x in flops.linears(m))
    di, state = cfg.ssm_expand * cfg.d_model, cfg.ssm_state
    ssm_rest = 4 * (di + 2 * state) * flops.mixer_layers(m, "mamba")
    embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    assert embed + linear + ssm_rest == cfg.param_count()
