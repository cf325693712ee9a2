"""``BENCHMARK.json`` and the files it names: every name resolves to its
file, each cell's metrics exist, and each configuration file agrees with
itself and with the architecture the program registers."""
import re

import pytest

from benchlib import cell as cell_lib
from benchlib import flops

MANIFEST = cell_lib.load_json(cell_lib.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for e in MANIFEST["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [e["name"] for e in MANIFEST["end_to_end"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = cell_lib.load_cell(name)
    assert cell.chips == 1
    cell_lib.load_module("drivers", cell.traffic["driver"]).run
    cell_lib.load_module("references", cell.config["reference"]).forward
    assert cell.check["limit"] > 0 and cell.check["sample_requests"] >= 1
    e2e = [m.name for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell_lib.load_module("metrics", m.name).read)
    for m in cell.per_layer:
        assert m.moves in e2e, (m.name, m.moves)


def test_every_named_file_exists():
    for c in MANIFEST["configs"]:
        assert (cell_lib.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/configs/")
    for w in MANIFEST["workloads"]:
        assert (cell_lib.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (cell_lib.BENCH / "checks" / f"{w['name']}.json").is_file()
    for name in ("packed_linear", "attn_decode"):
        w = cell_lib.load_module("work", name)
        assert w.PATTERNS and callable(w.step_work)


# A published config.json key and the ``model`` key it gives, compared
# where the file states it. A configuration that holds part of each MoE
# layer's experts states the count held under the published count's key.
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
             "num_local_experts": "experts_held",
             "num_experts": "experts_held", "num_experts_per_tok": "top_k",
             "mamba_d_state": "ssm_state", "mamba_expand": "ssm_expand",
             "mamba_d_head": "ssm_head_dim"}


def program_value(cfg, key: str):
    """The program's value of a ``model`` key: the ``ArchConfig`` field of
    that name, but for the few the program keeps elsewhere."""
    from repro.models import ssm
    elsewhere = {"head_dim": cfg.hd, "mix": list(cfg.quant.mix),
                 "ssm_head_dim": ssm.HEAD_DIM,
                 "experts_held": getattr(cfg, "experts_held",
                                         cfg.num_experts)}
    if key in elsewhere:
        return elsewhere[key]
    assert hasattr(cfg, key), f"the program's ArchConfig has no {key!r}"
    got = getattr(cfg, key)
    return list(got) if isinstance(got, tuple) else got


def check_published(conf):
    """The file's published-style keys, as run, agree with its ``model``
    keys (the reference's), and each key it cut differs from the
    published value."""
    m = conf["model"]
    for pub, key in PUBLISHED.items():
        if pub in conf:
            assert conf[pub] == flops.value(m, key), (pub, key)
    if "head_dim" in m:
        assert m["head_dim"] == (conf.get("head_dim") or
                                 conf["hidden_size"]
                                 // conf["num_attention_heads"])
    for key in conf["reduced"]:
        assert conf["published"][key] != conf[key]


def check_program(conf):
    """The program's ``ArchConfig`` as the benchmark builds it agrees with
    the file key by key: every key of its ``model`` dict, and every layout
    key the work counts read (``flops.LAYOUT``), stated or not."""
    from benchlib import system
    m = conf["model"]
    cfg = system.arch_config(conf)
    for key in sorted(set(m) | set(flops.LAYOUT)):
        assert program_value(cfg, key) == flops.value(m, key), key
    assert cfg.tie_embeddings == conf["tie_word_embeddings"]
    assert cfg.dtype == conf["precision"]
    assert cfg.quant.quantize_activations is False


def check_config(conf):
    check_published(conf)
    check_program(conf)


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]])
def test_config_file_matches_the_program(name):
    entry = {c["name"]: c for c in MANIFEST["configs"]}[name]
    conf = cell_lib.load_json(cell_lib.ROOT / entry["file"])
    assert conf["reduced"] == entry["reduced"]
    check_config(conf)
