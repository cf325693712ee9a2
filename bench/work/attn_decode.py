"""Decode attention over the 4-bit ring KV cache (``kernels/attn_decode.py``
``qkv_attn_decode``): one kernel call per attention layer and step.

Work of one step, per attention layer: 4 * context * H * head_dim FLOPs
per fed token, and the bytes of the live K/V tokens each slot attends
within its window (4-bit codes, two to a byte, and one float16 scale per
token and KV head, for K and for V), plus the float32 queries and
outputs. The least time is the larger of FLOPs over the peak and bytes
over the bandwidth; decode steps are bound by the bytes."""
from benchlib import flops

PATTERNS = (r"qkv_attn_decode(\.\d+)?",)


def step_work(m, step):
    hk, hd, h = m["num_kv_heads"], m["head_dim"], m["num_heads"]
    per_key = 2 * hk * (hd // 2 + 2)            # K and V codes + scales
    b = 0.0
    for p0, w in step.fed:
        b += per_key * flops.context(m, p0 + w - 1) + 2 * w * h * hd * 4
    return (flops.attention_flops(m, step.fed),
            b * flops.mixer_layers(m, "attn"))
