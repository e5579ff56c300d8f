"""jaxpr -> Workload tracer: derive the workload IR from a real model.

The registry's ``arch/<id>`` traces are hand-transcribed formulas; the
real forward passes live in ``repro.models``.  This module closes the
gap: :func:`trace_workload` runs ``jax.make_jaxpr`` over *abstract*
arguments (``jax.ShapeDtypeStruct`` pytrees -- no allocation, so
full-size models trace in milliseconds), walks the equations, and lowers
every primitive to the workload IR:

====================== ====================================================
jax primitive          Op lowering
====================== ====================================================
``dot_general``        ``matmul`` with the true contraction dims
                       (m = batch x lhs-free, k = contracting, n =
                       rhs-free) and a precision resolved from the
                       per-param-path width map; a batched product
                       whose rhs is a stack of expert weights
                       (``expert_paths``) is one ``matmul`` per expert
                       (m = lhs-free), marked ``expert``
``conv_general_dilated`` ``conv`` (n = output elements, k = taps x
                       C_in/groups, ``in_elems`` = input elements)
``gather`` / ``scatter`` / ``movement`` of the transferred elements at the
``dynamic_update_slice`` operand's dtype width
elementwise / reduce   ``compute`` with explicit per-layout cycles from
                       the Table-2/3 primitive costs (baked at ``sys``,
                       like the registry's ``compute`` ops)
shape/layout plumbing  transparent (reshape, transpose, broadcast, slice,
                       convert_element_type, ...): zero cost, origins and
                       producer edges propagate through
====================== ====================================================

``deps`` edges come from the jaxpr def-use graph, so
``plan.compile_plan`` sees the true DAG (min-cut scheduling), not a
chain.  Nested jaxprs (pjit / custom_jvp / remat / cond / while) are
inlined; ``scan`` bodies are lowered **once** by default
(``scan_mode="once"``) -- the traced workload describes one
representative layer / KV chunk, matching the per-layer semantics of the
hand-written ``arch/<id>`` formulas.

Precision resolution order (normative; DESIGN.md Sec. 12):

1. ``precision_map`` -- ``{path-substring: width_bits}`` matched against
   the operand's *origin paths* (the flattened-arg key paths its value
   was derived from through transparent ops); the minimum width over all
   matching entries wins.
2. integer operands: the dtype's bit width.
3. ``default_width`` (16) -- floats without a map entry, including f32
   softmax/router arithmetic, model at the paper's 16-bit word width.

A matmul's width is the minimum over its operands (a 4-bit weight makes
the op 4-bit, matching the quantized-serving formulas).
"""
from __future__ import annotations

import math
from typing import Callable, Optional

from repro.core import cost_model as cm
from repro.core.params import SystemParams, PAPER_SYSTEM
from repro.workloads.ir import Op, Workload

__all__ = ["trace_workload", "param_path_widths"]

# primitives that neither cost cycles nor break origin/dep propagation
TRANSPARENT_PRIMITIVES = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "convert_element_type", "bitcast_convert_type", "slice",
    "dynamic_slice", "concatenate", "pad", "rev", "iota",
    "stop_gradient", "copy", "device_put", "sharding_constraint",
    "reduce_precision", "split", "real", "imag", "tie_in",
})

#: primitives lowered to ``movement`` ops (row-serial bus transfer of the
#: produced / updated elements)
MOVEMENT_PRIMITIVES = frozenset({
    "gather", "dynamic_update_slice", "scatter", "scatter-add",
    "scatter_add", "scatter-mul", "scatter-min", "scatter-max",
})

#: call-like primitives whose inner jaxpr is inlined 1:1
_CALL_PRIMITIVES = frozenset({
    "jit", "pjit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
})

# per-element compute-cost table: primitive -> width -> (bp, bs) cycles
_TRANSCENDENTALS = frozenset({
    "exp", "exp2", "expm1", "log", "log1p", "log2", "tanh", "logistic",
    "erf", "erfc", "erf_inv", "sin", "cos", "tan", "asin", "acos",
    "atan", "atan2", "sinh", "cosh", "rsqrt", "sqrt", "cbrt",
})
_CMP = frozenset({"lt", "le", "gt", "ge"})
_LOGIC = frozenset({"and", "or", "xor", "not", "population_count"})
_ROUNDING = frozenset({"floor", "ceil", "round", "nextafter",
                       "is_finite", "sign"})


def _elem_cost(prim: str, w: int) -> tuple[int, int]:
    """Per-element (BP, BS) cycles of one elementwise primitive at width
    ``w`` (Table-2/3 vocabulary; DESIGN.md Sec. 12)."""
    if prim == "add" or prim in _ROUNDING:
        return cm.BP_ADD, cm.bs_add(w)
    if prim in ("sub", "neg"):
        return cm.BP_SUB, cm.bs_sub(w)
    if prim == "mul":
        return cm.bp_mult(w), cm.bs_mult(w)
    if prim in ("div", "rem"):
        return cm.div_bp(w), cm.div_bs(w)
    if prim in ("pow", "integer_pow"):
        return 2 * cm.bp_mult(w), 2 * cm.bs_mult(w)
    if prim in _TRANSCENDENTALS:
        # 4-term polynomial/Newton evaluation: 4 x (mult + add)
        return (4 * (cm.bp_mult(w) + cm.BP_ADD),
                4 * (cm.bs_mult(w) + cm.bs_add(w)))
    if prim in ("max", "min"):
        return cm.minmax_bp(w), cm.minmax_bs(w)
    if prim == "clamp":
        return 2 * cm.minmax_bp(w), 2 * cm.minmax_bs(w)
    if prim == "select_n":
        return cm.if_then_else_bp(w), cm.if_then_else_bs(w)
    if prim in ("eq", "ne"):
        return cm.equal_bp(w), cm.equal_bs(w)
    if prim in _CMP:
        # general compare = subtract + sign test
        return cm.BP_SUB + cm.ge0_bp(w), cm.bs_sub(w) + cm.ge0_bs(w)
    if prim in _LOGIC:
        return cm.BP_LOGIC, w
    if prim in ("shift_left", "shift_right_logical",
                "shift_right_arithmetic"):
        return cm.bp_shift(w), cm.BS_SHIFT
    if prim == "abs":
        return cm.abs_bp(w), cm.abs_bs(w)
    # unknown elementwise primitive: conservatively a multiply
    return cm.bp_mult(w), cm.bs_mult(w)


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(2, int(n)))))


def _dtype_bits(dtype) -> int:
    import numpy as np

    if dtype == bool or getattr(dtype, "kind", "") == "b":
        return 1
    return np.dtype(dtype).itemsize * 8


def _elems(aval) -> int:
    return max(1, int(math.prod(aval.shape)))


# ---------------------------------------------------------------------------
# Precision maps
# ---------------------------------------------------------------------------

def _format_path(path) -> str:
    """Key path -> canonical ``a/b/0/c`` string (the precision-map and
    origin-path vocabulary)."""
    from jax import tree_util as jtu

    parts = []
    for k in path:
        if isinstance(k, jtu.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jtu.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jtu.GetAttrKey):
            parts.append(str(k.name))
        else:  # FlattenedIndexKey and friends
            parts.append(str(getattr(k, "key", k)))
    return "/".join(parts)


def param_path_widths(params, *, weight_bits: int, dtype=None,
                      exclude: tuple[str, ...] = ()) -> dict[str, int]:
    """Build a precision map: every >=2-D leaf of ``params`` whose dtype
    matches ``dtype`` (default: the leaf dtype of the first such leaf)
    maps to ``weight_bits``; paths containing any ``exclude`` substring
    are left at model precision.  This is the quantized-serving
    convention of ``registry.arch_workload`` (weight matrices at
    ``weight_bits``, activations/normalizers at 16-bit).
    """
    from jax import tree_util as jtu

    leaves = jtu.tree_flatten_with_path(params)[0]
    if dtype is None:
        for _, leaf in leaves:
            if getattr(leaf, "ndim", 0) >= 2:
                dtype = leaf.dtype
                break
    out: dict[str, int] = {}
    for path, leaf in leaves:
        if getattr(leaf, "ndim", 0) < 2 or leaf.dtype != dtype:
            continue
        p = _format_path(path)
        if any(tok in p for tok in exclude):
            continue
        out[p] = weight_bits
    return out


# ---------------------------------------------------------------------------
# The tracer
# ---------------------------------------------------------------------------

class _VarInfo:
    """What the tracer knows about one jaxpr value: which flattened-arg
    paths it derives from (through transparent ops only) and which
    emitted op indices produced it."""

    __slots__ = ("origins", "producers")

    def __init__(self, origins=frozenset(), producers=frozenset()):
        self.origins = origins      # frozenset[str] arg key paths
        self.producers = producers  # frozenset[int] op indices

    @staticmethod
    def union(infos) -> "_VarInfo":
        o: frozenset = frozenset()
        p: frozenset = frozenset()
        for i in infos:
            o = o | i.origins
            p = p | i.producers
        return _VarInfo(o, p)


_EMPTY = _VarInfo()


class _Tracer:
    def __init__(self, *, precision_map, default_width, sys, scan_mode,
                 matmul_chunk, matmul_working_set, expert_paths=()):
        self.precision_map = dict(precision_map or {})
        self.expert_paths = frozenset(expert_paths)
        self.default_width = default_width
        self.sys = sys
        self.scan_mode = scan_mode
        self.matmul_chunk = matmul_chunk
        self.matmul_working_set = matmul_working_set
        self.ops: list[Op] = []
        self.deps: set[tuple[int, int]] = set()
        self.env: dict = {}          # jaxpr Var -> _VarInfo
        self._name_counts: dict[str, int] = {}

    # ----------------------------------------------------------- plumbing
    def read(self, atom) -> _VarInfo:
        from jax.extend.core import Literal

        if isinstance(atom, Literal):
            return _EMPTY
        return self.env.get(atom, _EMPTY)

    def write(self, var, info: _VarInfo) -> None:
        from jax.extend.core import Literal

        if not isinstance(var, Literal):  # a constant carry fed back
            self.env[var] = info

    def _unique(self, base: str) -> str:
        n = self._name_counts.get(base, 0)
        self._name_counts[base] = n + 1
        return base if n == 0 else f"{base}#{n}"

    def emit(self, op: Op, inputs: list[_VarInfo]) -> _VarInfo:
        idx = len(self.ops)
        self.ops.append(op)
        for producer in sorted(_VarInfo.union(inputs).producers):
            if producer < idx:
                self.deps.add((producer, idx))
        return _VarInfo(frozenset(), frozenset({idx}))

    # ---------------------------------------------------------- precision
    def _operand_width(self, info: _VarInfo, aval) -> int:
        matched = [w for key, w in self.precision_map.items()
                   if any(key in path for path in info.origins)]
        if matched:
            return min(matched)
        if aval.dtype.kind in ("i", "u"):
            return _dtype_bits(aval.dtype)
        return self.default_width

    # ------------------------------------------------------------ lowering
    def trace(self, jaxpr, invar_infos) -> None:
        for var, info in zip(jaxpr.invars, invar_infos):
            self.write(var, info)
        for var in jaxpr.constvars:
            self.write(var, _EMPTY)
        for eqn in jaxpr.eqns:
            self.eqn(eqn)

    def _inline(self, inner, eqn_invars, eqn_outvars) -> None:
        """Inline a nested jaxpr with a positional invar mapping."""
        jx = inner.jaxpr if hasattr(inner, "jaxpr") else inner
        self.trace(jx, [self.read(v) for v in eqn_invars])
        for outer, inner_out in zip(eqn_outvars, jx.outvars):
            self.write(outer, self.read(inner_out))

    def eqn(self, eqn) -> None:
        prim = eqn.primitive.name
        infos = [self.read(v) for v in eqn.invars]

        if prim in TRANSPARENT_PRIMITIVES:
            merged = _VarInfo.union(infos)
            for v in eqn.outvars:
                self.write(v, merged)
            return
        if prim in _CALL_PRIMITIVES:
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            self._inline(inner, eqn.invars, eqn.outvars)
            return
        if prim == "scan":
            return self._scan(eqn)
        if prim == "while":
            return self._while(eqn)
        if prim == "cond":
            return self._cond(eqn)
        if prim == "dot_general":
            return self._dot_general(eqn, infos)
        if prim == "conv_general_dilated":
            return self._conv(eqn, infos)
        if prim in MOVEMENT_PRIMITIVES:
            return self._movement(eqn, infos, prim)
        if prim.startswith("reduce_window"):
            return self._reduce_window(eqn, infos, prim)
        if prim.startswith(("reduce_", "argmax", "argmin")):
            return self._reduce(eqn, infos, prim)
        if prim in ("cumsum", "cumprod", "cummax", "cummin",
                    "cumlogsumexp"):
            return self._cumulative(eqn, infos, prim)
        if prim in ("top_k", "sort", "approx_top_k"):
            return self._topk(eqn, infos, prim)
        return self._elementwise(eqn, infos, prim)

    # ------------------------------------------------------- control flow
    def _scan(self, eqn) -> None:
        body = eqn.params["jaxpr"]
        n_iter = int(eqn.params.get("length") or 1)
        reps = n_iter if self.scan_mode == "unroll" else 1
        for _ in range(reps):
            jx = body.jaxpr
            self.trace(jx, [self.read(v) for v in eqn.invars])
            # feed carries back so unrolled iterations chain correctly
            n_consts = eqn.params["num_consts"]
            n_carry = eqn.params["num_carry"]
            carry_out = jx.outvars[:n_carry]
            for outer, inner_out in zip(eqn.invars[n_consts:
                                                   n_consts + n_carry],
                                        carry_out):
                self.write(outer, self.read(inner_out))
        jx = body.jaxpr
        for outer, inner_out in zip(eqn.outvars, jx.outvars):
            self.write(outer, self.read(inner_out))

    def _while(self, eqn) -> None:
        body = eqn.params["body_jaxpr"]
        n_cond = eqn.params["cond_nconsts"]
        self._inline(body, eqn.invars[n_cond:], eqn.outvars)

    def _cond(self, eqn) -> None:
        branches = eqn.params["branches"]
        biggest = max(branches, key=lambda b: len(b.jaxpr.eqns))
        self._inline(biggest, eqn.invars[1:], eqn.outvars)

    # ------------------------------------------------------------ matmuls
    def _dot_general(self, eqn, infos) -> None:
        (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        batch = math.prod(lhs.shape[d] for d in lhs_b) if lhs_b else 1
        lhs_free = math.prod(
            lhs.shape[d] for d in range(lhs.ndim)
            if d not in lhs_c and d not in lhs_b) or 1
        rhs_free = math.prod(
            rhs.shape[d] for d in range(rhs.ndim)
            if d not in rhs_c and d not in rhs_b) or 1
        k = math.prod(lhs.shape[d] for d in lhs_c) or 1
        # a stack of expert weights: each expert is its own product
        experts = batch if (lhs_b and infos[1].origins
                            & self.expert_paths) else 0
        m = max(1, lhs_free if experts else batch * lhs_free)
        n = max(1, rhs_free)
        widths = [self._operand_width(i, v.aval)
                  for i, v in zip(infos, eqn.invars)]
        width = min(widths)
        # name after the weight operand's param leaf when unambiguous
        leaves = sorted({path.rsplit("/", 1)[-1]
                         for i in infos for path in i.origins})
        base = leaves[0] if len(leaves) == 1 else "dot"
        ws = (self.matmul_working_set(width)
              if self.matmul_working_set else None)
        emitted = [self.emit(Op(
            name=self._unique(base), kind="matmul", m=m, k=k, n=n,
            width=width, chunk=min(self.matmul_chunk, k),
            mixed_precision=(len(set(widths)) > 1), working_set_bits=ws,
            expert=bool(experts)), infos) for _ in range(experts or 1)]
        info = _VarInfo.union(emitted)
        for v in eqn.outvars:
            self.write(v, info)

    def _conv(self, eqn, infos) -> None:
        dn = eqn.params["dimension_numbers"]
        groups = int(eqn.params.get("feature_group_count", 1))
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        out = eqn.outvars[0].aval
        spatial_taps = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
        c_in = rhs.shape[dn.rhs_spec[1]]
        k = max(1, spatial_taps * c_in)  # taps per output (C_in included)
        del groups  # C_in is already the per-group input-channel count
        widths = [self._operand_width(i, v.aval)
                  for i, v in zip(infos, eqn.invars)]
        leaves = sorted({path.rsplit("/", 1)[-1]
                         for i in infos for path in i.origins})
        base = leaves[0] if len(leaves) == 1 else "conv"
        op = Op(name=self._unique(base), kind="conv",
                n=_elems(out), k=k, in_elems=_elems(lhs),
                width=min(widths))
        info = self.emit(op, infos)
        for v in eqn.outvars:
            self.write(v, info)

    # ----------------------------------------------------------- movement
    def _movement(self, eqn, infos, prim) -> None:
        if prim == "dynamic_update_slice":
            moved = eqn.invars[1].aval  # the update operand
        elif prim.startswith("scatter"):
            moved = eqn.invars[2].aval  # updates
        else:  # gather
            moved = eqn.outvars[0].aval
        bits = _elems(moved) * _dtype_bits(moved.dtype)
        op = Op(name=self._unique(prim), kind="movement", bits=float(bits))
        info = self.emit(op, infos)
        for v in eqn.outvars:
            if prim == "dynamic_update_slice" or prim.startswith("scatter"):
                # the destination's origins survive the in-place update
                self.write(v, _VarInfo(infos[0].origins, info.producers))
            else:
                self.write(v, info)

    # --------------------------------------------------------- reductions
    def _compute(self, eqn, infos, name, bp, bs, width,
                 control=0.0) -> None:
        op = Op(name=self._unique(name), kind="compute",
                bp_cycles=int(bp), bs_cycles=int(bs), width=width,
                control_intensity=control)
        info = self.emit(op, infos)
        for v in eqn.outvars:
            self.write(v, info)

    def _reduce(self, eqn, infos, prim) -> None:
        src = eqn.invars[0].aval
        out = eqn.outvars[0].aval
        outs = _elems(out)
        ratio = max(2, _elems(src) // outs)
        w = _dtype_bits(src.dtype)
        bpb = self.sys.bp_batches(outs, min(w, 32))
        bsb = self.sys.bs_batches(outs)
        if prim in ("reduce_sum", "reduce_prod"):
            bp = cm.reduction_bp(ratio) * bpb
            bs = cm.reduction_bs(w) * bsb
            if prim == "reduce_prod":
                bp *= cm.bp_mult(w)
                bs *= cm.bs_mult(w)
            return self._compute(eqn, infos, prim, bp, bs, w)
        if prim in ("reduce_and", "reduce_or", "reduce_xor"):
            steps = _ceil_log2(ratio)
            return self._compute(eqn, infos, prim,
                                 steps * cm.BP_LOGIC * bpb,
                                 steps * w * bsb, w)
        # reduce_max / reduce_min / argmax / argmin: comparison trees
        steps = _ceil_log2(ratio)
        bp = steps * cm.minmax_bp(w) * bpb
        bs = steps * cm.minmax_bs(w) * bsb
        control = 0.4 if prim.startswith("arg") else 0.0
        return self._compute(eqn, infos, prim, bp, bs, w, control)

    def _reduce_window(self, eqn, infos, prim) -> None:
        out = eqn.outvars[0].aval
        src = eqn.invars[0].aval
        window = max(2, _elems(src) // _elems(out))
        w = _dtype_bits(src.dtype)
        per = (window - 1)
        bp = per * cm.minmax_bp(w) * self.sys.bp_batches(_elems(out),
                                                         min(w, 32))
        bs = per * cm.minmax_bs(w) * self.sys.bs_batches(_elems(out))
        return self._compute(eqn, infos, prim, bp, bs, w)

    def _cumulative(self, eqn, infos, prim) -> None:
        src = eqn.invars[0].aval
        axis = eqn.params.get("axis", 0)
        length = src.shape[axis] if src.shape else 1
        steps = _ceil_log2(max(2, length))
        n = _elems(src)
        w = _dtype_bits(src.dtype)
        per_bp, per_bs = _elem_cost(
            "mul" if prim == "cumprod" else "add", w)
        bp = steps * per_bp * self.sys.bp_batches(n, min(w, 32))
        bs = steps * per_bs * self.sys.bs_batches(n)
        return self._compute(eqn, infos, prim, bp, bs, w)

    def _topk(self, eqn, infos, prim) -> None:
        src = eqn.invars[0].aval
        w = _dtype_bits(src.dtype)
        kk = int(eqn.params.get("k", 1)) if prim != "sort" else 1
        length = src.shape[-1] if src.shape else 1
        outs = max(1, _elems(src) // max(1, length))
        steps = (kk * _ceil_log2(max(2, length)) if prim != "sort"
                 else _ceil_log2(max(2, length)) ** 2)
        bp = steps * cm.minmax_bp(w) * self.sys.bp_batches(outs, min(w, 32))
        bs = steps * cm.minmax_bs(w) * self.sys.bs_batches(outs)
        return self._compute(eqn, infos, prim, bp, bs, w, control=0.4)

    def _elementwise(self, eqn, infos, prim) -> None:
        out = eqn.outvars[0].aval
        n = _elems(out)
        w = _dtype_bits(out.dtype)
        per_bp, per_bs = _elem_cost(prim, w)
        bp = per_bp * self.sys.bp_batches(n, min(w, 32))
        bs = per_bs * self.sys.bs_batches(n)
        if bp == 0 and bs == 0:
            merged = _VarInfo.union(infos)
            for v in eqn.outvars:
                self.write(v, merged)
            return
        return self._compute(eqn, infos, prim, bp, bs, w)


def trace_workload(fn: Callable, *example_args,
                   precision_map: Optional[dict[str, int]] = None,
                   name: str = "traced", description: str = "",
                   source: str = "traced", default_width: int = 16,
                   sys: SystemParams = PAPER_SYSTEM,
                   scan_mode: str = "once", matmul_chunk: int = 64,
                   matmul_streamed_working_set: bool = True,
                   expert_paths: tuple[str, ...] = ()) -> Workload:
    """Trace ``fn(*example_args)`` into a :class:`Workload` DAG.

    ``example_args`` may be (pytrees of) ``jax.ShapeDtypeStruct`` --
    tracing is abstract, nothing is allocated.  ``precision_map`` maps
    param-path substrings (``blocks/0/attn/wqkv``; see
    :func:`param_path_widths`) to operand widths in bits.

    ``scan_mode``: ``"once"`` (default) lowers every ``lax.scan`` body a
    single time -- the traced workload is one representative layer / KV
    chunk, directly comparable to the per-layer ``arch/<id>`` formulas;
    ``"unroll"`` replicates the body ``length`` times.

    ``matmul_streamed_working_set=True`` pins matmul
    ``working_set_bits`` to the streamed-MAC live set (``8 * width``),
    the serving convention of ``registry.arch_workload``; pass False to
    keep the weight-stationary default of ``Op.features()``.

    ``expert_paths``: flattened-argument key paths of stacked expert
    weights (``[E, k, n]``); a batched product with one of them as its
    rhs lowers to one ``expert`` matmul per stacked expert.
    """
    import jax

    from repro import spans

    if scan_mode not in ("once", "unroll"):
        raise ValueError(f"scan_mode must be 'once' or 'unroll', "
                         f"got {scan_mode!r}")
    with spans.span("workload.trace", workload=name):
        closed = jax.make_jaxpr(fn)(*example_args)
        paths = jax.tree_util.tree_flatten_with_path(example_args)[0]
        t = _Tracer(precision_map=precision_map, default_width=default_width,
                    sys=sys, scan_mode=scan_mode, matmul_chunk=matmul_chunk,
                    matmul_working_set=(
                        (lambda w: w * 8) if matmul_streamed_working_set
                        else None), expert_paths=expert_paths)
        invar_infos = [
            _VarInfo(origins=frozenset({_format_path(path)}))
            for path, _leaf in paths]
        if len(invar_infos) != len(closed.jaxpr.invars):  # pragma: no cover
            raise AssertionError(
                f"flattened args ({len(invar_infos)}) != jaxpr invars "
                f"({len(closed.jaxpr.invars)})")
        t.trace(closed.jaxpr, invar_infos)
    if not t.ops:
        raise ValueError(f"trace of {name!r} produced no ops "
                         "(nothing costable in the jaxpr)")
    return Workload(name=name, ops=tuple(t.ops), source=source,
                    description=description or
                    f"jaxpr-traced workload ({len(t.ops)} ops)",
                    deps=tuple(sorted(t.deps)))
