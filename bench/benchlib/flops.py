"""Operations and bytes from a configuration's widths, counting useful
work only: the tokens the harness fed, never the rows the program pads a
step to. The packed-weight byte count is copied from
``benchmarks/runtime_proxy.py`` (``gemm_bytes``).

The layers are read from the configuration's ``model`` dict the way the
program's ``ArchConfig.layer_plan`` and ``hybrid_unit_kinds`` lay them
out: each layer has a mixer (attention or Mamba-2) and a feed-forward
block (dense MLP, MoE, or none). A key the dict leaves out stands for
``LAYOUT``'s value, ``ArchConfig``'s own default, so a dense decoder's
file states none of them."""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

GROUP = 16

LAYOUT = {
    "family": "dense", "num_experts": 0, "top_k": 0,
    "num_shared_experts": 0, "first_dense_layers": 0, "dense_d_ff": 0,
    "moe_every": 1, "ssm_state": 0, "ssm_expand": 2, "attn_every": 0,
    "attn_offset": 3,
    # the program's fixed Mamba-2 head size (``models/ssm.py`` HEAD_DIM)
    "ssm_head_dim": 64,
}


def value(m: Dict, key: str):
    """``m[key]``, or what its absence stands for. ``experts_held``, the
    routed experts this chip holds of each MoE layer, defaults to all."""
    if key == "experts_held":
        return m.get(key, value(m, "num_experts"))
    if key in LAYOUT:
        return m.get(key, LAYOUT[key])
    return m[key]


class Linear(NamedTuple):
    """``copies`` [K, N] weights of one name over the whole model, each
    taking in ``share`` of the fed tokens. ``kernel`` is the program's
    path: "segment" (``packed_segment_matmul``), "grouped"
    (``packed_segment_matmul_grouped``, the stacked expert banks) or
    "float" (an unpacked matmul: the router)."""
    name: str
    k: int
    n: int
    copies: int
    share: float = 1
    kernel: str = "segment"


def segments(k: int, mix) -> Tuple[int, int, int]:
    """(K4, K2, K1) input channels of a K-wide packed weight."""
    if k < GROUP:
        return k, 0, 0
    n = k // GROUP
    g4 = min(int(round(mix[0] * n)), n)
    g2 = min(int(round(mix[1] * n)), n - g4)
    return g4 * GROUP, g2 * GROUP, (n - g4 - g2) * GROUP


def packed_weight_bytes(k: int, n: int, mix) -> int:
    """Bytes a segment GEMM reads of one packed [K, N] weight: the 4/2/1-bit
    carriers and one float32 scale per group of 16 input channels."""
    k4, k2, k1 = segments(k, mix)
    return k4 * n // 2 + k2 * n // 4 + k1 * n // 8 + (k // GROUP) * 4


def layer_kinds(m: Dict) -> List[Tuple[str, str, int, int]]:
    """(mixer, feed-forward, its width, layers) in the program's order:
    mixer "attn" or "mamba", feed-forward "mlp", "moe" or "none"."""
    v = functools.partial(value, m)
    n_layers, d_ff, family = m["num_layers"], m["d_ff"], v("family")
    if family == "ssm":
        return [("mamba", "none", 0, n_layers)]
    if family == "hybrid":
        every = v("attn_every")
        if n_layers % every:
            raise ValueError(f"{n_layers} layers hold no whole number of "
                             f"{every}-layer hybrid units")
        moe_every = v("moe_every")
        return [("attn" if i == v("attn_offset") else "mamba",
                 "moe" if v("num_experts") and i % moe_every == moe_every - 1
                 else "mlp", d_ff, n_layers // every) for i in range(every)]
    if family not in ("dense", "moe", "vlm"):
        raise ValueError(f"no work count for the {family!r} family")
    if v("num_experts"):
        lead = v("first_dense_layers")
        kinds = [("attn", "mlp", v("dense_d_ff") or d_ff, lead)] if lead \
            else []
        return kinds + [("attn", "moe", d_ff, n_layers - lead)]
    return [("attn", "mlp", d_ff, n_layers)]


def _mlp(prefix: str, d: int, ff: int, act: str, copies: int,
         share: float = 1, kernel: str = "segment") -> List[Linear]:
    out = [Linear(prefix + "up", d, ff, copies, share, kernel),
           Linear(prefix + "down", ff, d, copies, share, kernel)]
    if act == "swiglu":
        out.append(Linear(prefix + "gate", d, ff, copies, share, kernel))
    return out


def linears(m: Dict) -> List[Linear]:
    """Every linear of the model's layers (not the embedding or the head).
    Routed experts count as ``experts_held`` copies each taking
    ``top_k / num_experts`` of the fed tokens: this chip's balanced share
    of the useful work, not the rows the program computes; a shared
    expert takes every token."""
    d, act = m["d_model"], m["mlp_act"]
    out: List[Linear] = []
    for mixer, ffn, ff, count in layer_kinds(m):
        if mixer == "attn":
            h, hk, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
            out += [Linear("wq", d, h * hd, count),
                    Linear("wk", d, hk * hd, count),
                    Linear("wv", d, hk * hd, count),
                    Linear("wo", h * hd, d, count)]
        else:
            di, state = value(m, "ssm_expand") * d, value(m, "ssm_state")
            heads = di // value(m, "ssm_head_dim")
            out += [Linear("in_proj", d, 2 * di + 2 * state + heads, count),
                    Linear("out_proj", di, d, count)]
        if ffn == "mlp":
            out += _mlp("", d, ff, act, count)
        elif ffn == "moe":
            e = value(m, "num_experts")
            out.append(Linear("router", d, e, count, kernel="float"))
            out += _mlp("expert.", d, ff, act,
                        count * value(m, "experts_held"),
                        value(m, "top_k") / e, "grouped")
            if value(m, "num_shared_experts"):
                out += _mlp("shared.", d, ff * value(m, "num_shared_experts"),
                            act, count)
    return out


def mixer_layers(m: Dict, mixer: str) -> int:
    """How many layers have ``mixer`` ("attn" or "mamba")."""
    return sum(c for mx, _, _, c in layer_kinds(m) if mx == mixer)


def context(m: Dict, pos: int) -> int:
    """Keys a query at ``pos`` attends (itself included)."""
    win = m.get("window")
    return min(pos + 1, win) if win else pos + 1


def attention_flops(m: Dict, fed) -> float:
    """4 * context * H * head_dim per fed token per attention layer
    (scores and values), for ``fed`` = [(first position, tokens)] of a
    step."""
    per = 4 * m["num_heads"] * m["head_dim"] * mixer_layers(m, "attn")
    return float(sum(per * context(m, p0 + j)
                     for p0, w in fed for j in range(w)))


def scan_flops(m: Dict, tokens: int) -> int:
    """The Mamba-2 scan in its recurrent form, about 4 * H * P * N per fed
    token per layer (decay, input and readout of the [H, P, N] state),
    H * P being the inner width."""
    return 4 * value(m, "ssm_expand") * m["d_model"] \
        * value(m, "ssm_state") * mixer_layers(m, "mamba") * tokens


def model_step_flops(m: Dict, step) -> float:
    """Useful model FLOPs of one engine step: 2 x linear parameters per fed
    token (routed experts at their share), the output head for every slot
    whose logits were read out, attention over each fed token's context,
    and the Mamba-2 scans."""
    tokens = sum(w for _, w in step.fed)
    lin = sum(x.k * x.n * x.copies * x.share for x in linears(m))
    head = m["d_model"] * m["vocab_size"]
    return 2.0 * tokens * lin + 2.0 * step.sampled * head \
        + attention_flops(m, step.fed) + scan_flops(m, tokens)
