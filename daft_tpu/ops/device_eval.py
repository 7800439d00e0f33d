"""Compile Expression trees into JAX functions over (values, validity) pairs.

This is the device half of expression evaluation (host half:
daft_tpu/expressions/eval.py). The stage compiler traces a whole
Project/Filter/Agg chain through these builders into ONE jit program, so XLA fuses
elementwise work into a single HBM pass — the TPU replacement for the reference's
per-operator vectorized kernels (src/daft-recordbatch eval_expression +
daft-core/array/ops), per SURVEY.md §7.

Null semantics mirror the host kernels exactly: validity masks propagate through
arithmetic, Kleene logic for and/or, divide-by-zero nulls, SQL CASE semantics for
if_else. Padding rows ride along as invalid and are masked out at aggregation.
"""

from __future__ import annotations

import datetime
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import jax_setup  # noqa: F401  — enables x64 before any jnp use
import jax.numpy as jnp

from ..datatype import DataType
from ..device.residency import literal_nodes
from ..expressions.expressions import (
    AggExpr,
    Alias,
    Between,
    BinaryOp,
    Cast,
    ColumnRef,
    Expression,
    Function,
    IfElse,
    IsIn,
    Literal,
    UnaryOp,
)
from ..schema import Schema

# (values, validity) pair; validity is bool[n]
DCol = Tuple[jnp.ndarray, jnp.ndarray]

_DEVICE_FNS: Dict[str, Callable] = {
    "exp": jnp.exp,
    "sqrt": jnp.sqrt,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arctan": jnp.arctan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "cbrt": jnp.cbrt,
    "expm1": jnp.expm1,
    "log1p": jnp.log1p,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "sign": jnp.sign,
}

_FLOAT_RESULT_FNS = set(_DEVICE_FNS) - {"floor", "ceil", "sign"}


def is_device_evaluable(expr: Expression, schema: Schema) -> bool:
    """True if the whole expression tree can run on device for this input schema."""
    try:
        out_dt = expr.to_field(schema).dtype
    except Exception:  # lint: ignore[broad-except] -- untypeable = not device-evaluable
        return False
    if not _dtype_on_device(out_dt):
        return False
    for node in expr.walk():
        if isinstance(node, ColumnRef):
            if not _dtype_on_device(schema[node._name].dtype):
                return False
        elif isinstance(node, Literal):
            ok = (node.dtype.is_numeric() or node.dtype.is_boolean()
                  or node.dtype.is_null() or node.dtype.is_temporal())
            if not ok or node.dtype.is_decimal():
                return False
        elif isinstance(node, Between):
            if not _temporal_operands_aligned([node.child, node.lower, node.upper], schema):
                return False
        elif isinstance(node, (Alias, IfElse, IsIn)):
            pass
        elif isinstance(node, Cast):
            if not _dtype_on_device(node.dtype):
                return False
        elif isinstance(node, BinaryOp):
            if node.op not in (
                "add", "sub", "mul", "div", "floordiv", "mod", "pow",
                "eq", "neq", "lt", "le", "gt", "ge", "and", "or", "xor",
                "fill_null", "eq_null_safe",
            ):
                return False
            if not _temporal_operands_aligned([node.left, node.right], schema):
                return False
        elif isinstance(node, UnaryOp):
            if node.op not in ("not", "neg", "abs", "is_null", "not_null"):
                return False
        elif isinstance(node, Function):
            if node.fname not in _DEVICE_FNS and node.fname not in ("is_nan", "is_inf", "not_nan", "fill_nan", "round", "clip", "log"):
                return False
        elif isinstance(node, AggExpr):
            if node.op not in ("sum", "mean", "min", "max", "count"):
                return False
        else:
            return False
    return True


def _dtype_on_device(dt: DataType) -> bool:
    return (dt.is_numeric() and not dt.is_decimal()) or dt.is_boolean() or dt.is_temporal()


def _temporal_operands_aligned(exprs, schema: Schema) -> bool:
    """Temporal values live on device as raw storage ints (days / epoch-in-unit),
    so mixed-unit or mixed-kind temporal operands would compare wrong numbers.
    Require every temporal operand in an operation to have the identical dtype."""
    dts = []
    for e in exprs:
        try:
            dts.append(e.to_field(schema).dtype)
        except Exception:  # lint: ignore[broad-except] -- untypeable = not device-evaluable
            return False
    temporal = [dt for dt in dts if dt.is_temporal()]
    if not temporal:
        return True
    return all(dt == temporal[0] for dt in temporal)


_DATE_EPOCH_ORDINAL = datetime.date(1970, 1, 1).toordinal()


def literal_jax_dtype(dtype: DataType, fdt):
    """The dtype a literal of `dtype` has inside a device program whose float
    compute dtype is `fdt`."""
    dt = dtype.to_jax()
    return fdt if dt in (jnp.float64, jnp.float32) else dt


def literal_host_value(dtype: DataType, value):
    """A non-null literal's value as it goes to the device: temporal columns
    live there as their arrow storage ints (date32 -> days, timestamp -> epoch
    in the column's unit), so a temporal literal is converted on the host."""
    if not dtype.is_temporal():
        return value
    if dtype.kind == "date" and type(value) is datetime.date:
        return value.toordinal() - _DATE_EPOCH_ORDINAL
    import pyarrow as pa

    storage = pa.int32() if dtype.kind == "date" else pa.int64()
    return pa.scalar(value, type=dtype.to_arrow()).cast(storage).as_py()


class _Slot(Expression):
    """A literal's place in a compiled expression: `index` is its position
    among the literals in walk order (device/residency.literal_nodes), and
    whether it is the null literal is all that is kept of its value."""

    def __init__(self, index: int, null: bool):
        self.index = index
        self.null = null


class LiteralSlots:
    """The literal slots of a stage's expressions, taken in the order
    `exprs_structure(exprs)` lists their literals: what of them is part of the
    program's shape (each slot's dtype; a null literal, which stays a
    constant) and how one execution's values travel to it. A launch pays one
    small transfer whatever the number of literals: every value goes as one
    or two 32-bit words of one uint32 host array (`pack`), and the traced
    function rebuilds the slots' 0-d values from the words (`unpack`); a
    float64 slot (a stage that computes in float64) rides in a second array,
    since the chip cannot rebuild one from its bits. `run_values` more
    int64s a launch (a grouped run's row offset) take the array's last words
    (`with_run_values`, `run_value`). Holds no value."""

    def __init__(self, exprs: Sequence[Expression], float_dtype, run_values: int = 0):
        per_expr = [literal_nodes(e) for e in exprs]
        nodes = [n for of_expr in per_expr for n in of_expr]
        # the first slot of each expression: build_device_expr's `first_slot`
        self.offsets = list(itertools.accumulate(
            (len(of_expr) for of_expr in per_expr), initial=0))[:-1]
        self.dtypes = [n.dtype for n in nodes]
        # per slot: None (a null literal), ("f64", position) or
        # ("words", first word, the slot's dtype in the program)
        self._where: List[Optional[tuple]] = []
        n_words = n_f64 = 0
        for n in nodes:
            if n.value is None:
                self._where.append(None)
                continue
            dt = np.dtype(literal_jax_dtype(n.dtype, float_dtype))
            if dt == np.float64:
                self._where.append(("f64", n_f64))
                n_f64 += 1
            else:
                self._where.append(("words", n_words, dt))
                n_words += 2 if dt.kind in "iu" and dt.itemsize == 8 else 1
        self._literal_words, self._n_f64 = n_words, n_f64
        self.run_values = run_values
        # values an execution passes: every slot but the null literals
        self.n_args = sum(w is not None for w in self._where)

    def __len__(self) -> int:
        return len(self.dtypes)

    def pack(self, literals: Sequence[Tuple[str, object]]) -> Tuple[np.ndarray, ...]:
        """One execution's literals ((dtype-repr, value) pairs, as
        exprs_structure gives them) as host arrays: the literals' words, and
        the float64 values where there are any."""
        if len(literals) != len(self.dtypes) or any(
                (where is None) != (literals[i][1] is None)
                for i, where in enumerate(self._where)):
            raise ValueError(
                f"the program has {len(self.dtypes)} literal slots "
                f"({self.n_args} with a value); the run was given {literals!r}")
        words = np.zeros(self._literal_words, dtype=np.uint32)
        f64s = np.zeros(self._n_f64, dtype=np.float64)
        for where, dtype, (_repr, value) in zip(self._where, self.dtypes, literals):
            if where is None:
                continue
            value = literal_host_value(dtype, value)
            if where[0] == "f64":
                f64s[where[1]] = value
                continue
            _kind, at, dt = where
            if dt.kind == "f":
                words[at] = np.float32(value).view(np.uint32)
            elif dt.kind == "b":
                words[at] = bool(value)
            else:
                _put_int(words, at, int(value), wide=dt.itemsize == 8)
        return (words, f64s) if self._n_f64 else (words,)

    def with_run_values(self, packed: Tuple[np.ndarray, ...], values: Sequence[int] = ()
                        ) -> Tuple[np.ndarray, ...]:
        """The program's literal argument for one launch: `pack`'s arrays,
        the launch's run values behind the literals' words (a fresh array: a
        transfer may still read the last launch's); nothing where the program
        takes no word at all."""
        if len(values) != self.run_values:
            raise ValueError(f"the program takes {self.run_values} run values, not {values!r}")
        words = packed[0]
        if values:
            tail = np.zeros(2 * len(values), dtype=np.uint32)
            for k, v in enumerate(values):
                _put_int(tail, 2 * k, int(v), wide=True)
            words = np.concatenate([words, tail])
        return ((words,) if len(words) else ()) + packed[1:]

    def arg_shapes(self) -> Tuple[Tuple[tuple, np.dtype], ...]:
        """(shape, dtype) of each array of a launch's literal argument: what a
        program is lowered with where there are no values
        (tests/test_chip_compile.py)."""
        n_words = self._literal_words + 2 * self.run_values
        return (((((n_words,), np.dtype(np.uint32)),) if n_words else ())
                + ((((self._n_f64,), np.dtype(np.float64)),) if self._n_f64 else ()))

    def _words_and_f64s(self, arrays):
        has_words = bool(self._literal_words + 2 * self.run_values)
        return (arrays[0] if has_words else None,
                arrays[-1] if self._n_f64 else None)

    def unpack(self, arrays) -> list:
        """The slots' 0-d values in the program's dtypes, from a launch's
        literal argument (traceable); None at a null literal's slot."""
        words, f64s = self._words_and_f64s(arrays)
        out: list = []
        for where in self._where:
            if where is None:
                out.append(None)
            elif where[0] == "f64":
                out.append(f64s[where[1]])
            else:
                _kind, at, dt = where
                if dt.kind == "f":
                    out.append(_bits_as(words[at], jnp.float32).astype(dt))
                elif dt.kind == "b":
                    out.append(words[at] != 0)
                elif dt.itemsize == 8:
                    out.append(_int64_of(words, at).astype(dt))
                else:
                    out.append(_bits_as(words[at], jnp.int32).astype(dt))
        return out

    def run_value(self, arrays, k: int):
        """The k-th run value of a launch, a 0-d int64 (traceable)."""
        return _int64_of(self._words_and_f64s(arrays)[0], self._literal_words + 2 * k)


def _put_int(words: np.ndarray, at: int, value: int, wide: bool) -> None:
    """`value` in two's complement: its low word at `at`, its high behind."""
    words[at] = value & 0xFFFFFFFF
    if wide:
        words[at + 1] = (value >> 32) & 0xFFFFFFFF


def _bits_as(word, dtype):
    import jax

    return jax.lax.bitcast_convert_type(word, dtype)


def _int64_of(words, at: int):
    """The int64 whose low and high words stand at `at` (no 64-bit bitcast:
    the chip's compiler has none that changes a shape)."""
    low = words[at].astype(jnp.int64)
    high = _bits_as(words[at + 1], jnp.int32).astype(jnp.int64)
    return (high << 32) | low


def build_device_expr(expr: Expression, schema: Schema,
                      float_dtype=None, first_slot: int = 0
                      ) -> Callable[[Dict[str, DCol], Sequence], DCol]:
    """Return fn(cols, lits) -> (values, validity); traceable under jit.

    What is compiled is the expression's skeleton: its non-null literals are
    slots, numbered from `first_slot` in walk order (the order of
    `expr_structure`'s literals), and `lits[k]` is the 0-d value of slot k in
    the program's dtype (LiteralSlots.unpack), so a program traced once
    serves every value. No value of `expr` itself is read: a caller whose
    program is keyed on the values uses build_constant_device_expr. A slot's
    dtype, a null literal, the number of an IsIn's items and cast targets are
    part of the skeleton.

    ``float_dtype`` sets the device float compute dtype (default float64).
    The stage compilers pass float32: TPU f64 is software-emulated (~5x slower,
    measured on v5e) and column data is f32-exact in practice; sums keep
    precision with f64 partial combines (ops/grouped_stage.py chunked merge).
    """
    fdt = float_dtype or jnp.float64
    numbering = itertools.count(first_slot)
    # transform reaches the literals, which are leaves, in walk order
    skeleton = expr.transform(
        lambda n: _Slot(next(numbering), n.value is None) if isinstance(n, Literal) else None)

    def fcast(v):
        return v.astype(fdt) if v.dtype in (jnp.float64, jnp.float32) and v.dtype != fdt else v

    def run(cols: Dict[str, DCol], lits: Sequence) -> DCol:
        def ev(node: Expression, cols: Dict[str, DCol]) -> DCol:
            if isinstance(node, ColumnRef):
                v, m = cols[node._name]
                return fcast(v), m
            if isinstance(node, _Slot):
                if node.null:
                    return jnp.zeros((), dtype=fdt), jnp.zeros((), dtype=bool)
                return lits[node.index], jnp.ones((), dtype=bool)
            if isinstance(node, Alias):
                return ev(node.child, cols)
            if isinstance(node, Cast):
                v, m = ev(node.child, cols)
                target = node.dtype.to_jax()
                if target in (jnp.float64, jnp.float32):
                    target = fdt
                return v.astype(target), m
            if isinstance(node, UnaryOp):
                v, m = ev(node.child, cols)
                if node.op == "not":
                    return ~v.astype(bool), m
                if node.op == "neg":
                    return -v, m
                if node.op == "abs":
                    return jnp.abs(v), m
                if node.op == "is_null":
                    val = ~m & jnp.ones(jnp.shape(v), dtype=bool)
                    return val, jnp.ones_like(val)
                if node.op == "not_null":
                    val = m & jnp.ones(jnp.shape(v), dtype=bool)
                    return val, jnp.ones_like(val)
                raise ValueError(node.op)
            if isinstance(node, BinaryOp):
                lv, lm = ev(node.left, cols)
                rv, rm = ev(node.right, cols)
                return _binop(node.op, lv, lm, rv, rm, fdt)
            if isinstance(node, Between):
                v, m = ev(node.child, cols)
                lo, lom = ev(node.lower, cols)
                hi, him = ev(node.upper, cols)
                val = (v >= lo) & (v <= hi)
                return val, m & lom & him
            if isinstance(node, IsIn):
                # host semantics: null input -> False, result never null
                v, m = ev(node.child, cols)
                acc = jnp.zeros(jnp.shape(v), dtype=bool)
                for item in node.items:
                    iv, im = ev(item, cols)
                    acc = acc | ((v == iv) & im)
                val = acc & m
                return val, jnp.ones_like(val)
            if isinstance(node, IfElse):
                pv, pm = ev(node.predicate, cols)
                tv, tm = ev(node.if_true, cols)
                fv, fm = ev(node.if_false, cols)
                cond = pv.astype(bool)
                tv, fv = _promote_pair(tv, fv)
                val = jnp.where(cond, tv, fv)
                # arrow semantics (matches host pc.if_else): null predicate -> null
                valid = pm & jnp.where(cond, tm & jnp.ones_like(cond), fm & jnp.ones_like(cond))
                return val, valid
            if isinstance(node, Function):
                return _fn_node(node, ev, cols, fdt)
            raise ValueError(f"not device-evaluable: {type(node).__name__}")

        return ev(skeleton, cols)

    return run


def build_constant_device_expr(expr: Expression, schema: Schema,
                               float_dtype=None) -> Callable[[Dict[str, DCol]], DCol]:
    """Return fn(cols) -> (values, validity) with the literal values of
    `expr` itself as constants of the traced program: for the callers whose
    compiled programs are kept under the values (parallel/distributed.py's
    aggregate step). The aggregate stages and a join's dim filters
    (ops/device_join.py _visibility_program) keep theirs under the skeleton,
    use build_device_expr and pass each execution's values."""
    fdt = float_dtype or jnp.float64
    fn = build_device_expr(expr, schema, float_dtype=fdt)
    constants = [None if n.value is None
                 else (literal_host_value(n.dtype, n.value), literal_jax_dtype(n.dtype, fdt))
                 for n in literal_nodes(expr)]

    def run(cols: Dict[str, DCol]) -> DCol:
        return fn(cols, [None if c is None else jnp.asarray(c[0], dtype=c[1])
                         for c in constants])

    return run


def _promote_pair(a, b):
    dt = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(dt), b.astype(dt)


def _broadcast_valid(v, m):
    """Ensure validity mask has the same shape as values."""
    return m & jnp.ones(jnp.shape(v), dtype=bool) if jnp.shape(m) != jnp.shape(v) else m


def _binop(op: str, lv, lm, rv, rm, fdt=jnp.float64) -> DCol:
    if op in ("add", "sub", "mul"):
        lv2, rv2 = _promote_pair(lv, rv)
        val = {"add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply}[op](lv2, rv2)
        return val, _broadcast_valid(val, lm & rm)
    if op == "div":
        lvf = lv.astype(fdt)
        rvf = rv.astype(fdt)
        val = lvf / jnp.where(rv == 0, jnp.ones_like(rvf), rvf)
        valid = lm & rm & (rv != 0)
        return val, _broadcast_valid(val, valid)
    if op == "floordiv":
        lvf = lv.astype(fdt)
        rvf = rv.astype(fdt)
        q = jnp.floor(lvf / jnp.where(rv == 0, jnp.ones_like(rvf), rvf))
        if jnp.issubdtype(lv.dtype, jnp.integer) and jnp.issubdtype(rv.dtype, jnp.integer):
            q = q.astype(jnp.promote_types(lv.dtype, rv.dtype))
        valid = lm & rm & (rv != 0)
        return q, _broadcast_valid(q, valid)
    if op == "mod":
        safe_r = jnp.where(rv == 0, jnp.ones_like(rv), rv)
        val = jnp.mod(lv, safe_r)
        valid = lm & rm & (rv != 0)
        return val, _broadcast_valid(val, valid)
    if op == "pow":
        val = jnp.power(lv.astype(fdt), rv.astype(fdt))
        return val, _broadcast_valid(val, lm & rm)
    if op in ("eq", "neq", "lt", "le", "gt", "ge"):
        val = {
            "eq": lv == rv, "neq": lv != rv, "lt": lv < rv,
            "le": lv <= rv, "gt": lv > rv, "ge": lv >= rv,
        }[op]
        return val, _broadcast_valid(val, lm & rm)
    if op == "eq_null_safe":
        both_valid = lm & rm
        val = jnp.where(both_valid, lv == rv, ~(lm ^ rm))
        return val, jnp.ones_like(_broadcast_valid(val, both_valid))
    if op == "and":
        lb, rb = lv.astype(bool), rv.astype(bool)
        val = lb & rb
        # Kleene: false AND anything = false (valid); null only if both maybe-true
        valid = (lm & rm) | (lm & ~lb) | (rm & ~rb)
        return val & valid, _broadcast_valid(val, valid)
    if op == "or":
        lb, rb = lv.astype(bool), rv.astype(bool)
        val = lb & lm | rb & rm
        valid = (lm & rm) | (lm & lb) | (rm & rb)
        return val, _broadcast_valid(val, valid)
    if op == "xor":
        val = lv.astype(bool) ^ rv.astype(bool)
        return val, _broadcast_valid(val, lm & rm)
    if op == "fill_null":
        lv2, rv2 = _promote_pair(lv, rv)
        val = jnp.where(lm, lv2, rv2)
        valid = lm | rm
        return val, _broadcast_valid(val, valid)
    raise ValueError(f"unsupported device binop {op!r}")


def _fn_node(node: Function, ev, cols, fdt=jnp.float64) -> DCol:
    name = node.fname
    if name in _DEVICE_FNS:
        v, m = ev(node.args[0], cols)
        if name in _FLOAT_RESULT_FNS:
            v = v.astype(fdt) if not jnp.issubdtype(v.dtype, jnp.floating) else v
        return _DEVICE_FNS[name](v), m
    if name == "log":
        v, m = ev(node.args[0], cols)
        v = v.astype(fdt)
        base = node.kwargs.get("base")
        out = jnp.log(v) if not base else jnp.log(v) / np.log(base)
        return out, m
    if name == "round":
        v, m = ev(node.args[0], cols)
        return jnp.round(v, node.kwargs.get("decimals", 0)), m
    if name == "clip":
        v, m = ev(node.args[0], cols)
        return jnp.clip(v, node.kwargs.get("clip_min"), node.kwargs.get("clip_max")), m
    if name == "is_nan":
        v, m = ev(node.args[0], cols)
        return jnp.isnan(v), m
    if name == "not_nan":
        v, m = ev(node.args[0], cols)
        return ~jnp.isnan(v), m
    if name == "is_inf":
        v, m = ev(node.args[0], cols)
        return jnp.isinf(v), m
    if name == "fill_nan":
        v, m = ev(node.args[0], cols)
        fv, fm = ev(node.args[1], cols)
        # null rows carry NaN in the dense values array — only replace *valid* NaNs
        nan = jnp.isnan(v) & m
        val = jnp.where(nan, fv.astype(v.dtype), v)
        valid = jnp.where(nan, _broadcast_valid(val, fm), _broadcast_valid(val, m))
        return val, valid
    raise ValueError(f"function {name!r} has no device kernel")


# ---- segment (grouped) reduction on device ---------------------------------------


def segment_reduce(op: str, values: jnp.ndarray, mask: jnp.ndarray,
                   seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Masked segment reduce. Invalid rows contribute the op's identity.

    Integer/bool inputs accumulate in int64 (exact for the full int64 domain,
    including min/max identities via iinfo); floats in float64. Shared by the
    single-chip grouped stage (ops/grouped_stage.py) and the mesh-sharded
    groupby (parallel/distributed.py) so both paths agree bit-for-bit.
    """
    import jax

    is_int = jnp.issubdtype(values.dtype, jnp.integer) or values.dtype == jnp.bool_
    if op == "count":
        return jax.ops.segment_sum(mask.astype(jnp.int64), seg, num_segments=num_segments)
    if op == "sum":
        acc = jnp.int64 if is_int else jnp.float64
        v = jnp.where(mask, values.astype(acc), jnp.zeros((), acc))
        return jax.ops.segment_sum(v, seg, num_segments=num_segments)
    if op in ("min", "max"):
        acc = jnp.int64 if is_int else jnp.float64
        if is_int:
            ident = jnp.iinfo(jnp.int64).max if op == "min" else jnp.iinfo(jnp.int64).min
        else:
            ident = jnp.inf if op == "min" else -jnp.inf
        v = jnp.where(mask, values.astype(acc), jnp.asarray(ident, acc))
        fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        return fn(v, seg, num_segments=num_segments)
    raise ValueError(f"no segment reduce for {op!r}")


# ---- whole-column (ungrouped) aggregation on device -------------------------------


def device_agg(op: str, v: jnp.ndarray, m: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Aggregate a masked column to a scalar: returns (value, valid) 0-d arrays."""
    count = jnp.sum(m)
    if op == "count":
        return count.astype(jnp.uint64), jnp.asarray(True)
    if op == "sum":
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.uint64)
        elif jnp.issubdtype(v.dtype, jnp.floating):
            # accumulate float sums in f64 like `mean` does: an f32 whole-bucket
            # reduction would cap the partial at ~7 significant digits
            v = v.astype(jnp.float64)
        s = jnp.sum(jnp.where(m, v, jnp.zeros_like(v)))
        if jnp.issubdtype(s.dtype, jnp.signedinteger):
            s = s.astype(jnp.int64)
        elif jnp.issubdtype(s.dtype, jnp.unsignedinteger):
            s = s.astype(jnp.uint64)
        return s, count > 0
    if op == "mean":
        s = jnp.sum(jnp.where(m, v.astype(jnp.float64), 0.0))
        return s / jnp.maximum(count, 1), count > 0
    if op == "min":
        big = _extreme(v.dtype, True)
        return jnp.min(jnp.where(m, v, big)), count > 0
    if op == "max":
        small = _extreme(v.dtype, False)
        return jnp.max(jnp.where(m, v, small)), count > 0
    raise ValueError(f"no device agg {op!r}")


def _extreme(dtype, positive: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if positive else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(positive, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if positive else info.min, dtype=dtype)
