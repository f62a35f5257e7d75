"""Pallas kernel checker: symbolic grid/BlockSpec audit per kernel family.

Each ``kernels/<family>/`` package is probed with tiny valid inputs while
``pl.pallas_call`` (and the TPU grid-spec/scratch constructors) are swapped
for capture shims — the kernel body never runs; what the shim records is
exactly the launch geometry the real call would hand the compiler. The
checks then evaluate every BlockSpec index map against the real grid and
the real scalar-prefetch operands (page tables, seq_lens), so page-gather
indirection is audited with genuine indices, not symbols:

* ``pallas-grid``              — grid dims must be positive ints.
* ``pallas-oob-index``         — an index map sends some grid point's block
  beyond its operand: ``(idx+1)*block > dim`` (every grid corner is
  evaluated; small grids are enumerated exhaustively).
* ``pallas-block-divisibility``— a block shape that doesn't divide its
  operand dim (the repo's kernels pad to block multiples *before* the
  launch, so at call time this must hold exactly).
* ``pallas-write-race``        — two grid points differing in a
  non-trailing (parallel) axis map to the same output block: a
  write-write race. Revisits along the trailing (sequential) axis are
  legal only with a VMEM scratch accumulator carrying the running state.
* ``pallas-scratch``           — scratch shapes with non-positive dims.
* ``pallas-static-args``       — the kernel/ops/ref triple disagrees on
  the threaded static args: ``ops.STATIC_ARGS`` vs the jit decorator's
  ``static_argnames``, a static arg the kernel entry doesn't declare, or
  one the ref oracle doesn't exercise in its body.
* ``pallas-uncovered-family``  — a ``kernels/*/`` package with no
  registered probe: new kernels must buy into the audit.
"""
from __future__ import annotations

import ast
import contextlib
import dataclasses
import itertools
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .report import Finding

RULES = frozenset({
    "pallas-grid", "pallas-oob-index", "pallas-block-divisibility",
    "pallas-write-race", "pallas-scratch", "pallas-static-args",
    "pallas-uncovered-family",
})
# the serving compile keys threaded kernel <-> ops <-> ref; audited end to
# end whenever a family's ops.py declares them static
AUDITED_STATIC_ARGS = ("pages_bound", "pages_start", "window")
_MAX_ENUM = 4096    # full grid enumeration cap; larger grids use corners


# --------------------------------------------------------------- capture shims
@dataclasses.dataclass
class _BlockSpec:
    block_shape: Optional[Tuple[int, ...]] = None
    index_map: Optional[Callable] = None
    memory_space: object = None


@dataclasses.dataclass
class _VMEM:
    shape: Tuple[int, ...]
    dtype: object


@dataclasses.dataclass
class _GridSpec:
    num_scalar_prefetch: int = 0
    grid: Tuple[int, ...] = ()
    in_specs: Sequence = ()
    out_specs: object = None
    scratch_shapes: Sequence = ()


@dataclasses.dataclass
class Captured:
    """One intercepted pallas_call launch."""
    kernel: object
    grid: Tuple[int, ...]
    in_specs: List
    out_specs: List
    scratch_shapes: List
    out_shapes: List            # jax.ShapeDtypeStruct per output
    num_scalar_prefetch: int
    prefetch: Tuple             # scalar-prefetch operands (real arrays)
    operands: Tuple             # block operands, aligned with in_specs

    @property
    def static_kwargs(self) -> dict:
        return dict(getattr(self.kernel, "keywords", None) or {})


class _Recorder:
    def __init__(self) -> None:
        self.calls: List[Captured] = []

    def pallas_call(self, kernel, *, grid=None, grid_spec=None,
                    in_specs=None, out_specs=None, out_shape=None,
                    scratch_shapes=None, interpret=None, **kw):
        import jax.numpy as jnp

        if grid_spec is not None:
            npf = grid_spec.num_scalar_prefetch
            grid = tuple(grid_spec.grid)
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = grid_spec.scratch_shapes or ()
        else:
            npf = 0
            grid = tuple(grid) if grid is not None else ()
            scratch_shapes = scratch_shapes or ()
        outs = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        out_sp = out_specs if isinstance(out_specs, (list, tuple)) \
            else [out_specs]

        def runner(*operands):
            self.calls.append(Captured(
                kernel=kernel, grid=grid, in_specs=list(in_specs or ()),
                out_specs=list(out_sp), scratch_shapes=list(scratch_shapes),
                out_shapes=list(outs), num_scalar_prefetch=npf,
                prefetch=tuple(operands[:npf]),
                operands=tuple(operands[npf:])))
            zeros = [jnp.zeros(o.shape, o.dtype) for o in outs]
            return zeros if isinstance(out_shape, (list, tuple)) \
                else zeros[0]
        return runner


@contextlib.contextmanager
def capture():
    """Swap the pallas entry points the kernel modules resolve at call time
    (``pl.pallas_call``, ``pl.BlockSpec``, ``pltpu.{PrefetchScalarGridSpec,
    VMEM}``) for capture shims; yields the recorder."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rec = _Recorder()
    saved = (pl.pallas_call, pl.BlockSpec,
             pltpu.PrefetchScalarGridSpec, pltpu.VMEM)
    pl.pallas_call = rec.pallas_call
    pl.BlockSpec = _BlockSpec
    pltpu.PrefetchScalarGridSpec = _GridSpec
    pltpu.VMEM = _VMEM
    try:
        yield rec
    finally:
        (pl.pallas_call, pl.BlockSpec,
         pltpu.PrefetchScalarGridSpec, pltpu.VMEM) = saved


# -------------------------------------------------------------------- probes
# Each probe drives its family's kernel entry (the un-jitted kernel.py
# function) through every structurally distinct launch mode with tiny
# inputs. Shapes are deliberately non-square so axis mixups surface.

def _probe_paged_decode() -> None:
    from repro.kernels.paged_decode_attention import kernel as K
    B, Kh, G, D, ps, P, MP = 2, 1, 2, 8, 4, 8, 4
    q = np.zeros((B, Kh, G, D), np.float32)
    kp = np.zeros((2, P, Kh, ps, D), np.float32)
    pt = (np.arange(B * MP, dtype=np.int32).reshape(B, MP) % (P - 1)) + 1
    sl = np.array([ps * MP, ps * 2], np.int32)
    K.paged_decode_attention_gqa(q, kp, kp, 1, pt, sl)
    K.paged_decode_attention_gqa(q, kp, kp, 1, pt, sl, pages_bound=2)
    K.paged_decode_attention_gqa(q, kp, kp, 1, pt, sl, pages_bound=4,
                                 pages_start=1, window=ps)


def _probe_paged_prefill() -> None:
    from repro.kernels.paged_prefill_attention import kernel as K
    B, Kh, C, G, D, ps, P, MP = 2, 1, 2, 2, 8, 4, 8, 4
    q = np.zeros((B, Kh, C, G, D), np.float32)
    kp = np.zeros((2, P, Kh, ps, D), np.float32)
    pt = (np.arange(B * MP, dtype=np.int32).reshape(B, MP) % (P - 1)) + 1
    start = np.array([ps * 2, ps], np.int32)
    total = start + C
    K.paged_prefill_attention_gqa(q, kp, kp, 1, pt, start, total)
    K.paged_prefill_attention_gqa(q, kp, kp, 1, pt, start, total,
                                  pages_bound=3)
    K.paged_prefill_attention_gqa(q, kp, kp, 1, pt, start, total,
                                  pages_bound=4, pages_start=1, window=ps)


def _probe_decode() -> None:
    from repro.kernels.decode_attention import kernel as K
    BK, G, D, S = 2, 2, 8, 16
    q = np.zeros((BK, G, D), np.float32)
    kv = np.zeros((BK, S, D), np.float32)
    valid = np.ones((BK, S), np.int8)
    K.decode_attention_gqa(q, kv, kv, valid, bk=8)
    # irregular S exercises the internal pad-to-block path
    K.decode_attention_gqa(q, kv[:, :12], kv[:, :12], valid[:, :12], bk=8)


def _probe_flash() -> None:
    from repro.kernels.flash_attention import kernel as K
    BH, S, D = 2, 16, 8
    q = np.zeros((BH, S, D), np.float32)
    K.flash_attention_bhsd(q, q, q, bq=8, bk=8)
    K.flash_attention_bhsd(q, q, q, causal=True, window=4, bq=8, bk=8)
    K.flash_attention_bhsd(q[:, :12], q[:, :12], q[:, :12], bq=8, bk=8)


def _probe_ssd() -> None:
    from repro.kernels.ssd_scan import kernel as K
    bc, H, l, P, N = 2, 2, 8, 8, 8
    x = np.zeros((bc, H, l, P), np.float32)
    dt = np.zeros((bc, H, l, 1), np.float32)
    B = np.zeros((bc, l, N), np.float32)
    K.ssd_chunk_scan(x, dt, dt, B, B)


PROBES: Dict[str, Callable[[], None]] = {
    "paged_decode_attention": _probe_paged_decode,
    "paged_prefill_attention": _probe_paged_prefill,
    "decode_attention": _probe_decode,
    "flash_attention": _probe_flash,
    "ssd_scan": _probe_ssd,
}


# -------------------------------------------------------------------- checks
def _grid_points(grid: Tuple[int, ...]):
    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= _MAX_ENUM:
        return list(itertools.product(*(range(int(g)) for g in grid)))
    corners = itertools.product(*({0, int(g) - 1} for g in grid))
    return sorted(set(corners))


def _eval_index_map(spec, point, prefetch):
    if spec is None or spec.index_map is None:
        return None
    idx = spec.index_map(*point, *prefetch)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(i) for i in idx)


def check_records(family: str, calls: Sequence[Captured],
                  rules: Optional[frozenset] = None) -> List[Finding]:
    rules = RULES if rules is None else frozenset(rules)
    out: List[Finding] = []
    path = f"kernels/{family}/kernel.py"

    def emit(rule: str, msg: str) -> None:
        if rule in rules:
            out.append(Finding(rule=rule, path=path, line=0,
                               symbol=family, message=msg))

    for ci, call in enumerate(calls):
        tag = f"launch {ci}: "
        grid = call.grid
        if not grid or any(not isinstance(int(g), int) or int(g) <= 0
                           for g in grid):
            emit("pallas-grid", tag + f"grid {grid} has a non-positive dim")
            continue
        for shape in call.scratch_shapes:
            dims = tuple(getattr(shape, "shape", ()) or ())
            if any(int(d) <= 0 for d in dims):
                emit("pallas-scratch",
                     tag + f"scratch shape {dims} has a non-positive dim")
        points = _grid_points(grid)
        specs = [(f"in_specs[{i}]", s, np.shape(op))
                 for i, (s, op) in enumerate(zip(call.in_specs,
                                                 call.operands))]
        specs += [(f"out_specs[{i}]", s, tuple(o.shape))
                  for i, (s, o) in enumerate(zip(call.out_specs,
                                                 call.out_shapes))]
        out_hits: Dict[int, Dict[Tuple, List[Tuple]]] = {}
        for name, spec, shape in specs:
            if spec is None or spec.block_shape is None:
                # no blocks: the operand is handed over whole (pl.ANY
                # leaves it in HBM for the kernel's own DMAs)
                continue
            block = tuple(int(b) for b in (spec.block_shape or ()))
            if len(block) != len(shape):
                emit("pallas-oob-index",
                     tag + f"{name}: block rank {len(block)} != operand "
                     f"rank {len(shape)} (shape {shape})")
                continue
            for b, d in zip(block, shape):
                if b > 0 and d % b:
                    emit("pallas-block-divisibility",
                         tag + f"{name}: block {block} does not divide "
                         f"operand shape {shape} — pad before the launch "
                         "or document the padding")
                    break
            for point in points:
                idx = _eval_index_map(spec, point, call.prefetch)
                if idx is None:
                    continue
                if len(idx) != len(block):
                    emit("pallas-oob-index",
                         tag + f"{name}: index map returns rank "
                         f"{len(idx)} for block rank {len(block)}")
                    break
                bad = [d for d in range(len(idx))
                       if idx[d] < 0 or (idx[d] + 1) * block[d] > shape[d]]
                if bad:
                    emit("pallas-oob-index",
                         tag + f"{name}: grid point {point} maps block "
                         f"index {idx} out of operand shape {shape} "
                         f"(axes {bad})")
                    break
                if name.startswith("out_specs"):
                    oi = int(name[len("out_specs["):-1])
                    out_hits.setdefault(oi, {}).setdefault(
                        idx, []).append(point)
        for oi, groups in out_hits.items():
            for idx, pts in groups.items():
                if len(pts) < 2:
                    continue
                lead = {p[:-1] for p in pts}
                if len(lead) > 1:
                    emit("pallas-write-race",
                         tag + f"out_specs[{oi}]: grid points {pts[:4]}... "
                         f"(differing in a non-trailing/parallel axis) all "
                         f"write block {idx} — write-write race")
                    break
                if not call.scratch_shapes:
                    emit("pallas-write-race",
                         tag + f"out_specs[{oi}]: block {idx} is revisited "
                         f"{len(pts)}x along the sequential axis with no "
                         "VMEM scratch accumulator — later visits clobber "
                         "earlier ones")
                    break
    return out


# ------------------------------------------------------- static-arg triples
def _jit_static_argnames(tree: ast.Module) -> set:
    names: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                for kw in dec.keywords:
                    if kw.arg == "static_argnames":
                        for elt in ast.walk(kw.value):
                            if isinstance(elt, ast.Constant) \
                                    and isinstance(elt.value, str):
                                names.add(elt.value)
    return names


def _module_const_tuple(tree: ast.Module, name: str) -> Optional[set]:
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name:
            vals = set()
            for elt in ast.walk(node.value):
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, str):
                    vals.add(elt.value)
            return vals
    return None


def _public_fns(tree: ast.Module) -> List[ast.FunctionDef]:
    return [n for n in tree.body if isinstance(n, ast.FunctionDef)
            and not n.name.startswith("_")]


def _fn_params(fn: ast.FunctionDef) -> set:
    a = fn.args
    return {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}


def _body_names(fn: ast.FunctionDef) -> set:
    names = set()
    for node in fn.body:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                names.add(sub.id)
    return names


def check_static_args(family: str, family_dir: Path,
                      rules: Optional[frozenset] = None) -> List[Finding]:
    rules = RULES if rules is None else frozenset(rules)
    out: List[Finding] = []
    if "pallas-static-args" not in rules:
        return out
    ops_p, ker_p, ref_p = (family_dir / n
                           for n in ("ops.py", "kernel.py", "ref.py"))

    def emit(path: Path, msg: str) -> None:
        out.append(Finding(
            rule="pallas-static-args", line=0, symbol=family,
            path=f"kernels/{family}/{path.name}", message=msg))

    if not ops_p.is_file():
        return out
    ops_tree = ast.parse(ops_p.read_text())
    jit_names = _jit_static_argnames(ops_tree)
    declared = _module_const_tuple(ops_tree, "STATIC_ARGS")
    if declared is None:
        emit(ops_p, "missing STATIC_ARGS declaration (the family's "
             "threaded compile keys; () when none)")
    elif declared != jit_names:
        emit(ops_p, f"STATIC_ARGS {sorted(declared)} != jit "
             f"static_argnames {sorted(jit_names)}")
    audit = jit_names & set(AUDITED_STATIC_ARGS)
    if not audit:
        return out
    for p, what in ((ker_p, "kernel"), (ref_p, "ref")):
        if not p.is_file():
            emit(p, f"{what}.py missing for a family with static args")
            continue
        fns = _public_fns(ast.parse(p.read_text()))
        if not fns:
            emit(p, f"no public function in {what}.py")
            continue
        for name in sorted(audit):
            if not any(name in _fn_params(f) for f in fns):
                emit(p, f"static arg {name!r} threaded by ops.py is not "
                     f"declared by any public {what}.py function")
            elif what == "ref" and not any(
                    name in _fn_params(f) and name in _body_names(f)
                    for f in fns):
                emit(p, f"static arg {name!r} is declared but never "
                     "exercised by the ref oracle's body")
    return out


# ---------------------------------------------------------------------- run
def run(root: Path, rules: Optional[frozenset] = None) -> List[Finding]:
    rules = RULES if rules is None else frozenset(rules)
    out: List[Finding] = []
    kernels = root / "kernels"
    families = sorted(d.name for d in kernels.iterdir()
                      if d.is_dir() and (d / "kernel.py").is_file())
    for family in families:
        probe = PROBES.get(family)
        if probe is None:
            if "pallas-uncovered-family" in rules:
                out.append(Finding(
                    rule="pallas-uncovered-family", line=0, symbol=family,
                    path=f"kernels/{family}/kernel.py",
                    message="no probe registered in analysis.pallas_check."
                            "PROBES — new kernel families must buy into "
                            "the launch audit"))
            continue
        with capture() as rec:
            probe()
        if not rec.calls:
            out.append(Finding(
                rule="pallas-uncovered-family", line=0, symbol=family,
                path=f"kernels/{family}/kernel.py",
                message="probe captured no pallas_call launch"))
        out.extend(check_records(family, rec.calls, rules))
        out.extend(check_static_args(family, kernels / family, rules))
    return out
