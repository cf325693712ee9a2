"""Operations and bytes of the kernels' work files and the whole-step
model FLOPs, against values worked out by hand at the cells' widths, and
the peaks table."""
import pytest

from benchlib import cell as cell_lib
from benchlib import flops
from benchlib.session import StepRecord

DANUBE = cell_lib.load_json(
    cell_lib.BENCH / "configs" / "h2o-danube-1.8b.json")["model"]
# starcoder2-7b's published widths (hf bigcode/starcoder2-7b), 8 layers:
# the same serve path at a GQA ratio of 9 and a non-gated MLP.
STARCODER = {"num_layers": 8, "d_model": 4608, "num_heads": 36,
             "num_kv_heads": 4, "head_dim": 128, "d_ff": 18432,
             "vocab_size": 49152, "window": None, "mlp_act": "gelu",
             "mix": [0.5, 0.375, 0.125]}


def _step(fed, sampled):
    return StepRecord(0.0, 1.0, "decode", fed, sampled, sampled)


def test_segments_at_the_cells_widths():
    mix = DANUBE["mix"]
    # 160 groups of 16: 80 at 4 bits, round(60) at 2, the 20 left at 1.
    assert flops.segments(2560, mix) == (1280, 960, 320)
    assert flops.segments(6912, mix) == (3456, 2592, 864)   # 216/162/54
    assert flops.segments(4608, mix) == (2304, 1728, 576)   # 144/108/36
    assert flops.segments(18432, mix) == (9216, 6912, 2304)
    # carriers 1280/2 + 960/4 + 320/8 = 920 bytes per column, 160 scales
    assert flops.packed_weight_bytes(2560, 640, mix) == 920 * 640 + 640


def test_packed_linear_decode_step_danube():
    w = cell_lib.load_module("work", "packed_linear")
    f, b = w.step_work(DANUBE, _step([(100, 1)] * 32, 32))
    # K*N per layer: q, o 2560x2560; k, v 2560x640; up, gate 2560x6912;
    # down 6912x2560.
    kn = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert kn == 69_468_160
    assert f == 2 * 32 * kn * 24
    weights = (2 * (920 * 2560 + 640) + 2 * (920 * 640 + 640)
               + 2 * (920 * 6912 + 640) + 2484 * 2560 + 432 * 4)
    assert weights == 24_970_688          # 0.6 GB over 24 layers
    acts = 32 * (6 * 2560 + 6912) * 2 + 32 * (
        2560 + 640 + 640 + 2560 + 6912 + 6912 + 2560) * 4
    assert b == (weights + acts) * 24


def test_packed_linear_prefill_counts_fed_tokens_only():
    w = cell_lib.load_module("work", "packed_linear")
    f1, _ = w.step_work(STARCODER, _step([(0, 64), (10, 3)], 1))
    kn = 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432   # no gate
    assert f1 == 2 * 67 * kn * 8


def test_attention_work_danube_window_and_bytes():
    w = cell_lib.load_module("work", "attn_decode")
    f, b = w.step_work(DANUBE, _step([(100, 1)], 1))
    assert f == 4 * 101 * 32 * 80 * 24
    # K and V: 8 heads x (40 code bytes + a float16 scale) per key
    assert b == (2 * 8 * 42 * 101 + 2 * 32 * 80 * 4) * 24
    f, _ = w.step_work(DANUBE, _step([(5000, 1)], 1))
    assert f == 4 * 4096 * 32 * 80 * 24   # the window caps the context


def test_model_step_flops_starcoder_decode():
    f = flops.model_step_flops(STARCODER, _step([(9, 1)] * 2, 2))
    lin = (2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432) * 8
    head = 4608 * 49152
    attn = 4 * 10 * 36 * 128 * 8
    assert f == 2 * 2 * lin + 2 * 2 * head + 2 * attn


def test_prefill_step_flops_read_out_one_row_per_slot():
    f = flops.model_step_flops(DANUBE, _step([(0, 2)], 1))
    lin = 69_468_160 * 24
    attn = 4 * (1 + 2) * 32 * 80 * 24
    assert f == 2 * 2 * lin + 2 * 2560 * 32000 + attn


# What the counts read before they followed the layer kinds (literals from
# that code), at a decode step of 16 slots and a mixed prefill step with a
# context past danube's window: a dense decoder's counts must not move.
_DECODE = [(100 + 37 * i, 1) for i in range(16)], 16
_PREFILL = [(0, 32), (64, 32), (4090, 32), (300, 1), (5000, 1)], 5


@pytest.mark.parametrize("model,step,model_flops,linear,attention", [
    (DANUBE, _DECODE, 57461309440.0, (53351546880.0, 651397632.0),
     (1488322560.0, 105535488.0)),
    (DANUBE, _PREFILL, 361649438720.0, (326778224640.0, 918415872.0),
     (34052014080.0, 187208448.0)),
    (STARCODER, _DECODE, 63706890240.0, (55566139392.0, 651772928.0),
     (892993536.0, 30299136.0)),
    (STARCODER, _PREFILL, 363223941120.0, (340342603776.0, 793510912.0),
     (20616413184.0, 69249024.0)),
], ids=["danube-decode", "danube-prefill", "starcoder-decode",
        "starcoder-prefill"])
def test_dense_counts_are_pinned(model, step, model_flops, linear,
                                 attention):
    rec = _step(*step)
    assert flops.model_step_flops(model, rec) == model_flops
    assert cell_lib.load_module("work", "packed_linear").step_work(
        model, rec) == linear
    assert cell_lib.load_module("work", "attn_decode").step_work(
        model, rec) == attention
    assert flops.attention_flops(model, rec.fed) == attention[0]


def test_peaks_table_knows_v5e_and_refuses_others():
    p = cell_lib.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell_lib.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        cell_lib.peaks("cpu")
