"""Pallas kernel contract audit (DESIGN.md §16).

The jaxpr audits (``repro.analysis.jaxpr_checks``) hold the *traced serve
path* to its dtype contract; this module audits the kernels themselves —
the grid/BlockSpec geometry and kernel-body arithmetic of every Pallas
entry the ``pallas_interpret``/``pallas_mosaic`` backends dispatch to —
without compiling or running a single kernel. Three passes:

* **Geometry** — for every kernel entry, over every registered arch's
  shapes x the autotuner's legal block candidates
  (:func:`repro.backend.autotune.candidates_for`), intercept
  ``pl.pallas_call`` at trace time (``jax.eval_shape``; the kernel body
  is stubbed, so a sweep of hundreds of (shape, blocks) cases costs
  milliseconds each) and check every ``BlockSpec`` against its operand:
  rank match, block divides the dim exactly, and the index map is
  statically in-bounds at all ``2^ndim`` grid corners (index maps return
  *block* indices: the last block touched is ``(idx+1)*block <= dim``).
  An off-by-one index map — the classic ring-clobber shape — reads or
  stores one block past the operand on the far corner of the grid, which
  interpret-mode happily wraps and Mosaic silently clamps; neither
  backend turns it into a test failure.
* **Body dtypes** — trace each entry once at a small all-f32 geometry
  (``jax.make_jaxpr``), find the ``pallas_call`` eqn, and walk the
  *kernel body* jaxpr: no f64 anywhere, no narrowing float->float
  ``convert_element_type``, every ``dot_general`` accumulates in fp32,
  and at least one store primitive (a kernel that never stores is a
  kernel whose output block is whatever was in the buffer). The f32
  inputs matter: entries that round-trip through ``x.dtype`` on purpose
  (documented io-dtype preservation) show no narrowing at f32, so only
  *unconditional* narrowing — the parity-breaking kind — is flagged.
* **Mapping** — the kernel<->Backend-op manifest below is held 1:1
  against reality: every manifest op exists in ``backend.base.OPS`` and
  has an ``xla_ref`` parity oracle (the method the gated-equality tests
  diff against); every jit-decorated public function in
  ``repro/kernels/*.py`` is in the manifest AND referenced by
  ``backend/pallas.py`` (an orphan kernel is dead code that silently
  stops being parity-tested); every manifest entry resolves to a real
  function.

Checks run on abstract values only — no weights, no kernel execution —
so the full sweep is safe for the CI ``static-analysis`` leg.
"""
from __future__ import annotations

import ast
import dataclasses
import importlib
import itertools
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_checks import Issue, iter_eqns

_STORE_PRIMS = ("swap", "store", "masked_swap")


# --------------------------------------------------------------------------
# The kernel <-> Backend-op manifest (the contract under audit)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelEntry:
    module: str          # dotted module under repro.kernels
    func: str            # public jit-wrapped entry function
    op: str              # backend.base.OPS name this kernel serves

    @property
    def where(self) -> str:
        return f"{self.module.rsplit('.', 1)[-1]}.{self.func}"


MANIFEST: Tuple[KernelEntry, ...] = (
    KernelEntry("repro.kernels.packed_matmul", "packed_segment_matmul",
                "packed_segment_matmul"),
    KernelEntry("repro.kernels.packed_matmul", "packed_segment_matmul_grouped",
                "packed_segment_matmul_grouped"),
    KernelEntry("repro.kernels.packed_matmul", "fused_act_segment_matmul",
                "fused_act_segment_matmul"),
    # The single-segment fast path serves the same Backend op as the
    # two-pass fused kernel (dispatched on in_kernel_scale).
    KernelEntry("repro.kernels.packed_matmul", "fused_act_selfscale_matmul",
                "fused_act_segment_matmul"),
    KernelEntry("repro.kernels.quant_pack", "quantize_pack",
                "quantize_pack"),
    KernelEntry("repro.kernels.noise_inject", "noise_inject",
                "noise_inject"),
    KernelEntry("repro.kernels.fake_quant", "fake_quant", "fake_quant"),
    KernelEntry("repro.kernels.attn_decode", "qkv_attn_decode",
                "qkv_attn_decode"),
    KernelEntry("repro.kernels.attn_decode", "qkv_attn_decode_paged",
                "qkv_attn_decode_paged"),
)


def _resolve(entry: KernelEntry):
    """The raw (unjitted) entry function — ``jax.jit`` keeps the original
    under ``__wrapped__``; tracing that directly means the pallas_call
    interception below sees every call (a jit cache would swallow
    repeats) and static kwargs are plain kwargs."""
    mod = importlib.import_module(entry.module)
    fn = getattr(mod, entry.func)
    return getattr(fn, "__wrapped__", fn)


# --------------------------------------------------------------------------
# pallas_call interception
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Capture:
    """One intercepted ``pl.pallas_call``: the grid, and each operand's
    (BlockSpec, concrete shape) pair — inputs then outputs."""
    kernel_name: str
    grid: Tuple[int, ...]
    in_pairs: List[Tuple[object, Tuple[int, ...]]]
    out_pairs: List[Tuple[object, Tuple[int, ...]]]
    # Scalar-prefetch operands (``PrefetchScalarGridSpec``): they carry no
    # BlockSpec, and every index map takes them as trailing arguments.
    prefetch: List[np.ndarray] = dataclasses.field(default_factory=list)


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def capture_pallas_calls(fn, args: Sequence, kwargs: Optional[Dict] = None
                         ) -> List[Capture]:
    """Trace ``fn(*args, **kwargs)`` abstractly (``jax.eval_shape``) with
    ``pl.pallas_call`` replaced by a recorder stub that never traces the
    kernel body — it notes the grid/specs/operand shapes and returns
    zeros of ``out_shape``. Returns every capture in call order."""
    import jax.experimental.pallas as pl

    records: List[Capture] = []
    real = pl.pallas_call

    def fake(kernel, out_shape=None, *, grid=(), in_specs=None,
             out_specs=None, grid_spec=None, **_kw):
        nsp = 0
        if grid_spec is not None:
            grid, in_specs = grid_spec.grid, grid_spec.in_specs
            out_specs = grid_spec.out_specs
            nsp = getattr(grid_spec, "num_scalar_prefetch", 0)

        def runner(*operands):
            outs = _as_list(out_shape)
            records.append(Capture(
                kernel_name=getattr(kernel, "func", kernel).__name__
                if hasattr(kernel, "func") or hasattr(kernel, "__name__")
                else str(kernel),
                grid=tuple(grid) if isinstance(grid, (tuple, list))
                else (int(grid),),
                in_pairs=list(zip(_as_list(in_specs),
                                  [tuple(o.shape) for o in operands[nsp:]])),
                out_pairs=list(zip(_as_list(out_specs),
                                   [tuple(s.shape) for s in outs])),
                prefetch=[np.zeros(o.shape, o.dtype)
                          for o in operands[:nsp]],
            ))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            return tuple(zeros) if isinstance(out_shape, (tuple, list)) \
                else zeros[0]
        return runner

    pl.pallas_call = fake
    try:
        jax.eval_shape(lambda *a: fn(*a, **(kwargs or {})), *args)
    finally:
        pl.pallas_call = real
    return records


# --------------------------------------------------------------------------
# Geometry pass
# --------------------------------------------------------------------------

def _check_pair(spec, shape: Tuple[int, ...], grid: Tuple[int, ...],
                role: str, where: str, prefetch: Sequence = ()
                ) -> List[Issue]:
    issues: List[Issue] = []
    if spec is None:                       # whole-array mapping: trivially
        return issues                      # in bounds
    block = tuple(int(b) for b in spec.block_shape)
    if len(block) != len(shape):
        issues.append(Issue(
            "kernel_geometry", where,
            f"{role}: BlockSpec rank {len(block)} != operand rank "
            f"{len(shape)} (block={block}, shape={shape})"))
        return issues
    for d, (b, n) in enumerate(zip(block, shape)):
        if b <= 0 or n % b:
            issues.append(Issue(
                "kernel_geometry", where,
                f"{role}: block dim {d} = {b} does not divide operand "
                f"dim {n} (block={block}, shape={shape}) — the ragged "
                f"tail block reads/writes out of bounds (no masking in "
                f"these kernels)"))
    if any(b <= 0 or n % b for b, n in zip(block, shape)):
        return issues                      # corner math needs clean tiling
    corners = itertools.product(*[(0, g - 1) if g > 1 else (0,)
                                  for g in grid])
    for corner in corners:
        try:
            idx = spec.index_map(*corner, *prefetch)
        except Exception as e:
            issues.append(Issue(
                "kernel_geometry", where,
                f"{role}: index map not statically evaluable at grid "
                f"corner {corner}: {e!r} — the audit cannot prove the "
                f"kernel in-bounds"))
            return issues
        idx = tuple(int(i) for i in (idx if isinstance(idx, tuple)
                                     else (idx,)))
        if len(idx) != len(block):
            issues.append(Issue(
                "kernel_geometry", where,
                f"{role}: index map returns {len(idx)} indices for a "
                f"rank-{len(block)} block at corner {corner}"))
            return issues
        for d, (i, b, n) in enumerate(zip(idx, block, shape)):
            if i < 0 or (i + 1) * b > n:
                issues.append(Issue(
                    "kernel_geometry", where,
                    f"{role}: index map out of bounds at grid corner "
                    f"{corner}: dim {d} block index {i} spans elements "
                    f"[{i * b}, {(i + 1) * b}) of a {n}-wide operand — "
                    f"interpret mode wraps and Mosaic clamps, so this "
                    f"block silently reads/clobbers the wrong data"))
    return issues


def check_capture_geometry(cap: Capture, where: str) -> List[Issue]:
    """Divisibility + static in-bounds for one intercepted pallas_call."""
    issues: List[Issue] = []
    for k, (spec, shape) in enumerate(cap.in_pairs):
        issues.extend(_check_pair(spec, shape, cap.grid,
                                  f"in_specs[{k}]", where, cap.prefetch))
    for k, (spec, shape) in enumerate(cap.out_pairs):
        issues.extend(_check_pair(spec, shape, cap.grid,
                                  f"out_specs[{k}]", where, cap.prefetch))
    return issues


# --------------------------------------------------------------------------
# Kernel-body dtype pass
# --------------------------------------------------------------------------

def check_entry_body(fn, args: Sequence, kwargs: Optional[Dict],
                     where: str) -> List[Issue]:
    """Trace ``fn`` (for real — ``jax.make_jaxpr``) and audit every
    pallas_call's *kernel body* jaxpr: fp32 accumulation, no f64, no
    narrowing float converts, at least one store. Call with all-f32
    operands so intentional io-dtype round-trips vanish (module
    docstring)."""
    issues: List[Issue] = []
    f32 = jnp.dtype(jnp.float32)
    f64 = jnp.dtype(jnp.float64)
    try:
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **(kwargs or {})))(*args)
    except Exception as e:
        return [Issue("kernel_dtype", where,
                      f"entry failed to trace at the audit geometry: "
                      f"{e!r}")]
    bodies = [eqn.params["jaxpr"] for eqn, _ in iter_eqns(jaxpr)
              if eqn.primitive.name == "pallas_call"]
    if not bodies:
        return [Issue("kernel_dtype", where,
                      "no pallas_call in the traced entry — the kernel "
                      "path silently fell through, so nothing below it "
                      "is audited")]
    for body in bodies:
        stores = 0
        for eqn, _ in iter_eqns(body):
            name = eqn.primitive.name
            if name in _STORE_PRIMS:
                stores += 1
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "dtype", None) == f64:
                    issues.append(Issue(
                        "kernel_dtype", where,
                        f"float64 value produced by `{name}` inside the "
                        f"kernel body — an x64 promotion breaks parity "
                        f"with every fp32 backend"))
            if name == "convert_element_type":
                new = jnp.dtype(eqn.params["new_dtype"])
                olds = [v.aval.dtype for v in eqn.invars
                        if hasattr(getattr(v, "aval", None), "dtype")]
                old = olds[0] if olds else None
                if old is not None \
                        and jnp.issubdtype(old, jnp.floating) \
                        and jnp.issubdtype(new, jnp.floating) \
                        and new.itemsize < jnp.dtype(old).itemsize:
                    issues.append(Issue(
                        "kernel_dtype", where,
                        f"narrowing float convert {old}->{new} inside "
                        f"the kernel body at f32 io — unconditional "
                        f"precision loss in the quantized arithmetic"))
            elif name == "dot_general":
                pref = eqn.params.get("preferred_element_type")
                outs = [v.aval.dtype for v in eqn.outvars
                        if hasattr(getattr(v, "aval", None), "dtype")]
                bad_out = any(jnp.issubdtype(d, jnp.floating) and d != f32
                              for d in outs)
                if (pref is not None and jnp.dtype(pref) != f32) or bad_out:
                    issues.append(Issue(
                        "kernel_dtype", where,
                        f"kernel dot_general does not accumulate in fp32 "
                        f"(preferred_element_type={pref}, out={outs})"))
        if stores == 0:
            issues.append(Issue(
                "kernel_dtype", where,
                "kernel body contains no store primitive — the output "
                "block is never written"))
    return issues


# --------------------------------------------------------------------------
# Mapping pass
# --------------------------------------------------------------------------

def _jit_decorated_public_functions(path: Path) -> List[str]:
    """Module-level public ``def``s carrying a jit decorator (plain
    ``@jax.jit`` or ``@functools.partial(jax.jit, ...)``)."""
    tree = ast.parse(path.read_text(), filename=str(path))
    out = []
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef) \
                or node.name.startswith("_"):
            continue
        for deco in node.decorator_list:
            if any(isinstance(n, (ast.Attribute, ast.Name))
                   and (getattr(n, "attr", None) == "jit"
                        or getattr(n, "id", None) == "jit")
                   for n in ast.walk(deco)):
                out.append(node.name)
                break
    return out


def check_kernel_mapping(root: Optional[Path] = None) -> List[Issue]:
    """Hold MANIFEST 1:1 against backend.base.OPS, the xla_ref parity
    oracle, the kernels package on disk, and backend/pallas.py."""
    from repro.backend import base as backend_base
    from repro.backend.xla_ref import XLA_REF

    if root is None:
        root = Path(__file__).resolve().parents[1]   # src/repro
    issues: List[Issue] = []
    pallas_src = (root / "backend" / "pallas.py").read_text()

    for entry in MANIFEST:
        where = entry.where
        if entry.op not in backend_base.OPS:
            issues.append(Issue(
                "kernel_mapping", where,
                f"manifest op '{entry.op}' is not in backend.base.OPS — "
                f"the kernel serves an op no Backend declares"))
        hook = backend_base._OP_IMPL_HOOK.get(entry.op, entry.op)
        if not callable(getattr(XLA_REF, hook, None)):
            issues.append(Issue(
                "kernel_mapping", where,
                f"op '{entry.op}' has no xla_ref parity oracle "
                f"(missing method '{hook}') — nothing to gate the "
                f"kernel's numerics against"))
        try:
            fn = getattr(importlib.import_module(entry.module),
                         entry.func, None)
        except ImportError as e:
            fn, err = None, e
            issues.append(Issue("kernel_mapping", where,
                                f"manifest module does not import: {e!r}"))
            continue
        if fn is None:
            issues.append(Issue(
                "kernel_mapping", where,
                "manifest names a function that does not exist"))
        if f".{entry.func}" not in pallas_src:
            issues.append(Issue(
                "kernel_mapping", where,
                "kernel entry is never referenced by backend/pallas.py — "
                "an orphan: it runs in no backend, so the parity gate "
                "never sees it"))

    manifest_funcs = {e.func for e in MANIFEST}
    for path in sorted((root / "kernels").glob("*.py")):
        for name in _jit_decorated_public_functions(path):
            if name not in manifest_funcs:
                issues.append(Issue(
                    "kernel_mapping", f"kernels/{path.name}",
                    f"public jit entry '{name}' is not in the kernel "
                    f"audit manifest — unaudited kernel surface"))
    return issues


# --------------------------------------------------------------------------
# Shape-case sweep over registered archs x autotune candidates
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _arch_cases(cfg) -> List[Dict]:
    """Audit cases this arch contributes: one per kernel entry, with the
    operand avals and the autotune (op, shape) key that enumerates its
    block candidates. Degenerate dims (attention-free SSM archs, zero
    d_ff) contribute nothing for the affected entries."""
    from repro.core.qtypes import GROUP_SIZE
    f32, f16 = jnp.float32, jnp.float16
    u8, i32 = jnp.uint8, jnp.int32
    p, m = 4, 16
    kp, n = int(cfg.d_model), int(cfg.d_ff)
    cases: List[Dict] = []
    if kp > 0 and n > 0 and kp % GROUP_SIZE == 0:
        x = _sds((m, kp), f32)
        wp = _sds((kp * p // 8, n), u8)
        sc = _sds((kp // GROUP_SIZE,), f32)
        cases += [
            dict(func="packed_segment_matmul", op="packed_segment_matmul",
                 shape=(m, kp, n), args=(x, wp, sc),
                 static={"p": p, "act_quant": True}),
            dict(func="fused_act_segment_matmul",
                 op="fused_act_segment_matmul", shape=(m, kp, n),
                 args=(x, _sds((m, 1), f32), wp, sc), static={"p": p}),
            dict(func="fused_act_selfscale_matmul",
                 op="fused_act_segment_matmul", shape=(m, kp, n),
                 args=(x, wp, sc), static={"p": p}),
            dict(func="quantize_pack", op="quantize_pack", shape=(kp, n),
                 args=(_sds((kp, n), f32), sc), static={"p": p}),
            dict(func="noise_inject", op="noise_inject", shape=(kp, n),
                 args=(_sds((kp, n), f32), sc, np.uint32(0)), static={}),
            dict(func="fake_quant", op="fake_quant", shape=(m, kp),
                 args=(_sds((m, kp), f32), _sds((kp // GROUP_SIZE,), f32),
                       _sds((m, 1), f32)), static={"row_scale": True}),
            dict(func="fake_quant", op="fake_quant", shape=(m, kp),
                 args=(_sds((m, kp), f32), _sds((kp // GROUP_SIZE,), f32),
                       _sds((kp // GROUP_SIZE,), f32)),
                 static={"row_scale": False}),
        ]
        # Grouped (stacked-expert) segment GEMM: the MoE serve path's
        # per-segment dispatch over [E, ...] packed banks. Non-MoE archs
        # audit a synthetic E=2 stack so every manifest kernel contributes
        # at least one geometry per arch (the kernel itself is E-agnostic).
        ne = int(getattr(cfg, "num_experts", 0) or 0) or 2
        cases.append(dict(
            func="packed_segment_matmul_grouped",
            op="packed_segment_matmul_grouped", shape=(ne, m, kp, n),
            args=(_sds((ne, m, kp), f32),
                  _sds((ne, kp * p // 8, n), u8),
                  _sds((ne, kp // GROUP_SIZE), f32)),
            static={"p": p, "act_quant": True}))
    hk, d = int(cfg.num_kv_heads), int(cfg.head_dim)
    g = int(cfg.num_heads) // hk if hk > 0 else 0
    if hk > 0 and g > 0 and d > 0 and d % 2 == 0:
        b, s, t = 2, 1, 512
        q = _sds((b, s, hk, g, d), f32)
        cases.append(dict(
            func="qkv_attn_decode", op="qkv_attn_decode",
            shape=(b * hk * s * g, t, d),
            args=(q, _sds((b, t, hk, d // 2), u8),
                  _sds((b, t, hk, d // 2), u8), _sds((b, t, hk, 1), f16),
                  _sds((b, t, hk, 1), f16), _sds((b, t), i32),
                  _sds((b, s), i32)),
            static={"window": None}))
        npages, ps, npg = 9, 16, 4
        cases.append(dict(
            func="qkv_attn_decode_paged", op="qkv_attn_decode_paged",
            shape=(b * hk * s * g, npg, ps, d),
            args=(q, _sds((npages, ps, hk, d // 2), u8),
                  _sds((npages, ps, hk, d // 2), u8),
                  _sds((npages, ps, hk, 1), f16),
                  _sds((npages, ps, hk, 1), f16),
                  _sds((npages, ps), i32), _sds((b, npg), i32),
                  _sds((b, s), i32)),
            static={"window": None}))
    return cases


def _spread(seq: List, limit: int) -> List:
    """At most ``limit`` items, evenly spread (endpoints kept) — the
    candidate grid's extremes are where tiling bugs live."""
    if len(seq) <= limit:
        return list(seq)
    if limit == 1:
        return [seq[0]]
    idxs = sorted({round(i * (len(seq) - 1) / (limit - 1))
                   for i in range(limit)})
    return [seq[i] for i in idxs]


# Small all-f32 geometries for the per-entry body dtype pass; block
# kwargs are omitted (the entries' fit_block snapping handles defaults).
def _body_cases() -> List[Dict]:
    f32, f16 = jnp.float32, jnp.float16
    u8, i32 = jnp.uint8, jnp.int32
    m, kp, n, p = 8, 32, 16, 4
    x = _sds((m, kp), f32)
    wp = _sds((kp * p // 8, n), u8)
    sc = _sds((kp // 16,), f32)
    b, s, hk, g, d, t = 1, 1, 1, 2, 8, 16
    q = _sds((b, s, hk, g, d), f32)
    npages, ps, npg = 3, 8, 2
    return [
        dict(func="packed_segment_matmul", args=(x, wp, sc),
             static={"p": p, "act_quant": True}),
        dict(func="packed_segment_matmul_grouped",
             args=(_sds((2, m, kp), f32), _sds((2, kp * p // 8, n), u8),
                   _sds((2, kp // 16), f32)),
             static={"p": p, "act_quant": True}),
        dict(func="fused_act_segment_matmul",
             args=(x, _sds((m, 1), f32), wp, sc), static={"p": p}),
        dict(func="fused_act_selfscale_matmul", args=(x, wp, sc),
             static={"p": p}),
        dict(func="quantize_pack", args=(_sds((kp, n), f32), sc),
             static={"p": p}),
        dict(func="noise_inject",
             args=(_sds((kp, n), f32), sc, np.uint32(0)), static={}),
        dict(func="fake_quant",
             args=(x, _sds((kp // 16,), f32), _sds((m, 1), f32)),
             static={"row_scale": True}),
        dict(func="fake_quant",
             args=(x, _sds((kp // 16,), f32), _sds((kp // 16,), f32)),
             static={"row_scale": False}),
        dict(func="qkv_attn_decode",
             args=(q, _sds((b, t, hk, d // 2), u8),
                   _sds((b, t, hk, d // 2), u8), _sds((b, t, hk, 1), f16),
                   _sds((b, t, hk, 1), f16), _sds((b, t), i32),
                   _sds((b, s), i32)),
             static={"window": None, "block_t": t}),
        dict(func="qkv_attn_decode_paged",
             args=(q, _sds((npages, ps, hk, d // 2), u8),
                   _sds((npages, ps, hk, d // 2), u8),
                   _sds((npages, ps, hk, 1), f16),
                   _sds((npages, ps, hk, 1), f16),
                   _sds((npages, ps), i32), _sds((b, npg), i32),
                   _sds((b, s), i32)),
             static={"window": None}),
    ]


def run_kernel_audit(archs: Optional[Iterable[str]] = None, *,
                     max_candidates: int = 6
                     ) -> Tuple[Dict, List[Issue]]:
    """The CI entry point. Geometry-sweeps every manifest kernel over
    every registered arch's shapes x (capped, endpoint-preserving) block
    candidates, body-audits each entry once at a small f32 geometry, and
    checks the kernel<->op mapping. Returns (report, issues)."""
    from repro.backend import autotune
    from repro.configs import registry

    import repro.configs  # noqa: F401  (trigger arch registrations)

    if archs is None:
        archs = registry.list_archs()
    raw = {e.func: _resolve(e) for e in MANIFEST}
    issues: List[Issue] = []
    entries: Dict[str, Dict[str, int]] = {
        e.func: {"cases": 0, "candidates": 0} for e in MANIFEST}
    seen = set()
    truncated = 0
    for name in archs:
        for case in _arch_cases(registry.get_config(name)):
            key = (case["func"], case["shape"],
                   tuple(sorted(case["static"].items())))
            if key in seen:
                continue
            seen.add(key)
            cands = autotune.candidates_for(case["op"], case["shape"])
            kept = _spread(cands, max_candidates)
            truncated += len(cands) - len(kept)
            entries[case["func"]]["cases"] += 1
            for blocks in kept:
                entries[case["func"]]["candidates"] += 1
                where = (f"{case['func']}[shape="
                         f"{'x'.join(map(str, case['shape']))},"
                         f"{','.join(f'{k}={v}' for k, v in sorted(blocks.items()))}]")
                kwargs = {**case["static"], **blocks, "interpret": True}
                try:
                    caps = capture_pallas_calls(raw[case["func"]],
                                                case["args"], kwargs)
                except Exception as e:
                    issues.append(Issue(
                        "kernel_geometry", where,
                        f"entry failed to trace: {e!r}"))
                    continue
                if not caps:
                    issues.append(Issue(
                        "kernel_geometry", where,
                        "no pallas_call captured — the entry silently "
                        "skipped its kernel"))
                for cap in caps:
                    issues.extend(check_capture_geometry(cap, where))
    body_audited = []
    for case in _body_cases():
        where = f"{case['func']}[body]"
        body_audited.append(case["func"])
        issues.extend(check_entry_body(
            raw[case["func"]], case["args"],
            {**case["static"], "interpret": True}, where))
    issues.extend(check_kernel_mapping())
    report = {
        "archs": sorted(archs),
        "cases": sum(e["cases"] for e in entries.values()),
        "candidates": sum(e["candidates"] for e in entries.values()),
        "candidates_truncated": truncated,
        "max_candidates": max_candidates,
        "entries": entries,
        "body_audited": sorted(set(body_audited)),
        "manifest_size": len(MANIFEST),
    }
    return report, issues
