"""Expression IR.

Reference parity: src/daft-dsl/src/expr/mod.rs:222-307 (Expr enum: Column, Alias,
Agg, BinaryOp, Cast, Function, Not, IsNull, FillNull, IsIn, Between, Literal,
IfElse, ScalarFn, ...) and daft/expressions/expressions.py (the Python Expression
class with .str/.dt/.list/.float/.embedding namespaces).

One Python class hierarchy serves as both the user-facing Expression and the plan
IR. Host evaluation lives in daft_tpu/expressions/eval.py, device (JAX) evaluation
in daft_tpu/ops/device_eval.py; both dispatch over these node types.
"""

from __future__ import annotations

import datetime
import decimal
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..datatype import DataType, Field
from ..schema import Schema


class Expression:
    """Base class; subclasses are the IR nodes."""

    # ---- naming -------------------------------------------------------------------
    def name(self) -> str:
        raise NotImplementedError(type(self).__name__)

    def alias(self, name: str) -> "Expression":
        return Alias(self, name)

    def cast(self, dtype: DataType) -> "Expression":
        return Cast(self, dtype)

    # ---- structure ----------------------------------------------------------------
    def children(self) -> List["Expression"]:
        return []

    def with_children(self, children: List["Expression"]) -> "Expression":
        if children:
            raise ValueError(f"{type(self).__name__} takes no children")
        return self

    def walk(self):
        yield self
        for c in self.children():
            yield from c.walk()

    def transform(self, fn: Callable[["Expression"], Optional["Expression"]]) -> "Expression":
        """Bottom-up rewrite: fn returns a replacement or None to keep."""
        old_children = self.children()
        new_children = [c.transform(fn) for c in old_children]
        changed = any(a is not b for a, b in zip(new_children, old_children))
        node = self.with_children(new_children) if changed else self
        out = fn(node)
        return out if out is not None else node

    def referenced_columns(self) -> List[str]:
        out: List[str] = []
        seen = set()
        for node in self.walk():
            if isinstance(node, ColumnRef) and node._name not in seen:
                seen.add(node._name)
                out.append(node._name)
        return out

    def has_agg(self) -> bool:
        return any(isinstance(n, AggExpr) for n in self.walk())

    def has_udf(self) -> bool:
        from ..udf.expr import UdfCall

        return any(isinstance(n, UdfCall) for n in self.walk())

    def is_literal_true(self) -> bool:
        return isinstance(self, Literal) and self.value is True

    # ---- typing -------------------------------------------------------------------
    def to_field(self, schema: Schema) -> Field:
        raise NotImplementedError(type(self).__name__)

    def get_type(self, schema: Schema) -> DataType:
        return self.to_field(schema).dtype

    # ---- operators ----------------------------------------------------------------
    def _other(self, other) -> "Expression":
        return other if isinstance(other, Expression) else lit(other)

    def __add__(self, other):
        return BinaryOp("add", self, self._other(other))

    def __radd__(self, other):
        return BinaryOp("add", self._other(other), self)

    def __sub__(self, other):
        return BinaryOp("sub", self, self._other(other))

    def __rsub__(self, other):
        return BinaryOp("sub", self._other(other), self)

    def __mul__(self, other):
        return BinaryOp("mul", self, self._other(other))

    def __rmul__(self, other):
        return BinaryOp("mul", self._other(other), self)

    def __truediv__(self, other):
        return BinaryOp("div", self, self._other(other))

    def __rtruediv__(self, other):
        return BinaryOp("div", self._other(other), self)

    def __floordiv__(self, other):
        return BinaryOp("floordiv", self, self._other(other))

    def __rfloordiv__(self, other):
        return BinaryOp("floordiv", self._other(other), self)

    def __mod__(self, other):
        return BinaryOp("mod", self, self._other(other))

    def __rmod__(self, other):
        return BinaryOp("mod", self._other(other), self)

    def __pow__(self, other):
        return BinaryOp("pow", self, self._other(other))

    def __eq__(self, other):  # type: ignore[override]
        return BinaryOp("eq", self, self._other(other))

    def __ne__(self, other):  # type: ignore[override]
        return BinaryOp("neq", self, self._other(other))

    def __lt__(self, other):
        return BinaryOp("lt", self, self._other(other))

    def __le__(self, other):
        return BinaryOp("le", self, self._other(other))

    def __gt__(self, other):
        return BinaryOp("gt", self, self._other(other))

    def __ge__(self, other):
        return BinaryOp("ge", self, self._other(other))

    def __and__(self, other):
        return BinaryOp("and", self, self._other(other))

    def __rand__(self, other):
        return BinaryOp("and", self._other(other), self)

    def __or__(self, other):
        return BinaryOp("or", self, self._other(other))

    def __ror__(self, other):
        return BinaryOp("or", self._other(other), self)

    def __xor__(self, other):
        return BinaryOp("xor", self, self._other(other))

    def __invert__(self):
        return UnaryOp("not", self)

    def __neg__(self):
        return UnaryOp("neg", self)

    def __hash__(self):
        return hash(repr(self))

    def __bool__(self):
        raise ValueError(
            "Expressions are lazy; cannot convert to bool. Use & | ~ instead of and/or/not."
        )

    # ---- null / conditional -------------------------------------------------------
    def is_null(self) -> "Expression":
        return UnaryOp("is_null", self)

    def not_null(self) -> "Expression":
        return UnaryOp("not_null", self)

    def fill_null(self, value) -> "Expression":
        return BinaryOp("fill_null", self, self._other(value))

    def eq_null_safe(self, other) -> "Expression":
        return BinaryOp("eq_null_safe", self, self._other(other))

    def is_in(self, values) -> "Expression":
        if isinstance(values, Expression):
            items = [values]
        else:
            items = [v if isinstance(v, Expression) else lit(v) for v in values]
        return IsIn(self, items)

    def between(self, lower, upper) -> "Expression":
        return Between(self, self._other(lower), self._other(upper))

    def if_else(self, if_true, if_false) -> "Expression":
        return IfElse(self, self._other(if_true), self._other(if_false))

    def abs(self) -> "Expression":
        return UnaryOp("abs", self)

    # ---- scalar function sugar ------------------------------------------------------
    def _fn(__self, __fname: str, *args, **kwargs) -> "Expression":
        exprs = [__self] + [a if isinstance(a, Expression) else lit(a) for a in args]
        return Function(__fname, exprs, kwargs)

    def exp(self):
        return self._fn("exp")

    def log(self, base: Optional[float] = None):
        return self._fn("log", **({"base": base} if base else {}))

    def log2(self):
        return self._fn("log2")

    def log10(self):
        return self._fn("log10")

    def sqrt(self):
        return self._fn("sqrt")

    def sin(self):
        return self._fn("sin")

    def cos(self):
        return self._fn("cos")

    def tan(self):
        return self._fn("tan")

    def arctan(self):
        return self._fn("arctan")

    def arcsin(self):
        return self._fn("arcsin")

    def arccos(self):
        return self._fn("arccos")

    def floor(self):
        return self._fn("floor")

    def ceil(self):
        return self._fn("ceil")

    def round(self, decimals: int = 0):
        return self._fn("round", decimals=decimals)

    def sign(self):
        return self._fn("sign")

    def clip(self, min=None, max=None):
        return self._fn("clip", clip_min=min, clip_max=max)

    def hash(self, seed=None):
        return self._fn("hash", **({"seed": seed} if seed is not None else {}))

    def minhash(self, num_hashes: int = 16, ngram_size: int = 1, seed: int = 1):
        return self._fn("minhash", num_hashes=num_hashes, ngram_size=ngram_size, seed=seed)

    def tokenize_encode(self, tokenizer: str = "bytes"):
        """Text -> token ids ('bytes' builtin or a HF tokenizers JSON path;
        reference: src/daft-functions-tokenize)."""
        return self._fn("tokenize_encode", tokenizer=tokenizer)

    def tokenize_decode(self, tokenizer: str = "bytes"):
        """Token ids -> text (inverse of tokenize_encode)."""
        return self._fn("tokenize_decode", tokenizer=tokenizer)

    def apply(self, fn: Callable, return_dtype: DataType) -> "Expression":
        from ..udf.expr import UdfCall
        from ..udf.udf import Func

        f = Func(fn=fn, return_dtype=return_dtype, is_batch=False, name=getattr(fn, "__name__", "apply"))
        return UdfCall(f, [self], {})

    # ---- aggregation sugar ----------------------------------------------------------
    def sum(self):
        return AggExpr("sum", self)

    def mean(self):
        return AggExpr("mean", self)

    def avg(self):
        return AggExpr("mean", self)

    def min(self):
        return AggExpr("min", self)

    def max(self):
        return AggExpr("max", self)

    def count(self, mode: str = "valid"):
        return AggExpr("count", self, {"mode": mode})

    def count_distinct(self):
        return AggExpr("count_distinct", self)

    def any_value(self, ignore_nulls: bool = False):
        return AggExpr("any_value", self, {"ignore_nulls": ignore_nulls})

    def stddev(self, ddof: int = 0):
        return AggExpr("stddev", self, {"ddof": ddof} if ddof else {})

    def var(self, ddof: int = 0):
        return AggExpr("var", self, {"ddof": ddof} if ddof else {})

    def skew(self):
        return AggExpr("skew", self)

    def bool_and(self):
        return AggExpr("bool_and", self)

    def bool_or(self):
        return AggExpr("bool_or", self)

    def agg_list(self):
        return AggExpr("list", self)

    def agg_set(self) -> "AggExpr":
        """Distinct values as a list (reference: Expression.agg_set)."""
        return AggExpr("set", self)

    def agg_concat(self):
        return AggExpr("concat", self)

    def approx_count_distinct(self):
        return AggExpr("approx_count_distinct", self)

    def approx_percentile(self, *percentiles, alpha: float = 0.01):
        """DDSketch approximate percentile(s) in [0, 1]; one argument yields a
        float64, several yield a fixed list (reference: daft-sketch)."""
        if not percentiles:
            raise ValueError("approx_percentile needs at least one percentile")
        single = len(percentiles) == 1
        return AggExpr("approx_percentile", self, {
            "percentiles": float(percentiles[0]) if single else [float(p) for p in percentiles],
            "alpha": alpha,
        })

    # ---- window ---------------------------------------------------------------------
    def over(self, spec) -> "WindowExpr":
        """Evaluate this aggregation over a Window spec (reference: Expr::Over)."""
        if isinstance(self, AggExpr):
            return WindowExpr(self.op, self.child, spec, self.params)
        raise ValueError(
            f"only aggregation expressions support .over(); got {type(self).__name__} "
            "(use daft_tpu.functions.row_number()/rank()/... for ranking window fns)"
        )

    def lag(self, offset: int = 1, default=None) -> "Expression":
        return _UnboundWindowFn("lag", self, {"offset": offset, "default": default})

    def lead(self, offset: int = 1, default=None) -> "Expression":
        return _UnboundWindowFn("lead", self, {"offset": offset, "default": default})

    def first_value(self) -> "Expression":
        return _UnboundWindowFn("first_value", self, {})

    def last_value(self) -> "Expression":
        return _UnboundWindowFn("last_value", self, {})

    # ---- namespaces -----------------------------------------------------------------
    @property
    def str(self) -> "StringNamespace":
        return StringNamespace(self)

    @property
    def dt(self) -> "TemporalNamespace":
        return TemporalNamespace(self)

    @property
    def list(self) -> "ListNamespace":
        return ListNamespace(self)

    @property
    def float(self) -> "FloatNamespace":
        return FloatNamespace(self)

    @property
    def embedding(self) -> "EmbeddingNamespace":
        return EmbeddingNamespace(self)

    @property
    def struct(self) -> "StructNamespace":
        return StructNamespace(self)

    @property
    def image(self) -> "ImageNamespace":
        return ImageNamespace(self)

    @property
    def url(self) -> "UrlNamespace":
        return UrlNamespace(self)

    @property
    def binary(self) -> "BinaryNamespace":
        return BinaryNamespace(self)

    @property
    def map(self) -> "MapNamespace":
        return MapNamespace(self)

    @property
    def json(self) -> "JsonNamespace":
        return JsonNamespace(self)


class ColumnRef(Expression):
    def __init__(self, name: str):
        self._name = name

    def name(self) -> str:
        return self._name

    def to_field(self, schema: Schema) -> Field:
        return schema[self._name]

    def __repr__(self):
        return f"col({self._name})"


class Literal(Expression):
    def __init__(self, value: Any, dtype: Optional[DataType] = None):
        self.value = value
        self.dtype = dtype or _infer_literal_dtype(value)

    def name(self) -> str:
        return "literal"

    def to_field(self, schema: Schema) -> Field:
        return Field("literal", self.dtype)

    def __repr__(self):
        return f"lit({self.value!r})"


class Alias(Expression):
    def __init__(self, child: Expression, alias: str):
        self.child = child
        self._alias = alias

    def name(self) -> str:
        return self._alias

    def children(self):
        return [self.child]

    def with_children(self, children):
        return Alias(children[0], self._alias)

    def to_field(self, schema: Schema) -> Field:
        return Field(self._alias, self.child.to_field(schema).dtype)

    def __repr__(self):
        return f"{self.child!r}.alias({self._alias!r})"


class Cast(Expression):
    def __init__(self, child: Expression, dtype: DataType):
        self.child = child
        self.dtype = dtype

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child]

    def with_children(self, children):
        return Cast(children[0], self.dtype)

    def to_field(self, schema: Schema) -> Field:
        return Field(self.child.to_field(schema).name, self.dtype)

    def __repr__(self):
        return f"{self.child!r}.cast({self.dtype})"


_COMPARISON_OPS = {"eq", "neq", "lt", "le", "gt", "ge", "eq_null_safe"}
_LOGICAL_OPS = {"and", "or", "xor"}
_ARITH_OPS = {"add", "sub", "mul", "div", "floordiv", "mod", "pow"}


class BinaryOp(Expression):
    def __init__(self, op: str, left: Expression, right: Expression):
        self.op = op
        self.left = left
        self.right = right

    def name(self) -> str:
        return self.left.name()

    def children(self):
        return [self.left, self.right]

    def with_children(self, children):
        return BinaryOp(self.op, children[0], children[1])

    def to_field(self, schema: Schema) -> Field:
        lf = self.left.to_field(schema)
        rf = self.right.to_field(schema)
        name = lf.name if not isinstance(self.left, Literal) else rf.name
        op = self.op
        if op in _COMPARISON_OPS:
            return Field(name, DataType.bool())
        if op in _LOGICAL_OPS:
            if not (lf.dtype.is_boolean() or lf.dtype.is_null()) or not (rf.dtype.is_boolean() or rf.dtype.is_null()):
                raise ValueError(f"logical op {op!r} requires boolean operands, got {lf.dtype} and {rf.dtype}")
            return Field(name, DataType.bool())
        if op == "fill_null":
            return Field(lf.name, lf.dtype if not lf.dtype.is_null() else rf.dtype)
        if op in _ARITH_OPS:
            return Field(name, _arith_result_type(op, lf.dtype, rf.dtype))
        raise ValueError(f"unknown binary op {op!r}")

    def __repr__(self):
        sym = {
            "add": "+", "sub": "-", "mul": "*", "div": "/", "floordiv": "//", "mod": "%",
            "pow": "**", "eq": "==", "neq": "!=", "lt": "<", "le": "<=", "gt": ">",
            "ge": ">=", "and": "&", "or": "|", "xor": "^",
        }.get(self.op)
        if sym:
            return f"({self.left!r} {sym} {self.right!r})"
        return f"{self.op}({self.left!r}, {self.right!r})"


class UnaryOp(Expression):
    def __init__(self, op: str, child: Expression):
        self.op = op
        self.child = child

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child]

    def with_children(self, children):
        return UnaryOp(self.op, children[0])

    def to_field(self, schema: Schema) -> Field:
        f = self.child.to_field(schema)
        if self.op in ("is_null", "not_null", "not"):
            return Field(f.name, DataType.bool())
        if self.op in ("neg", "abs"):
            if not f.dtype.is_numeric():
                raise ValueError(f"{self.op} requires numeric input, got {f.dtype}")
            return f
        raise ValueError(f"unknown unary op {self.op!r}")

    def __repr__(self):
        return f"{self.op}({self.child!r})"


class IsIn(Expression):
    def __init__(self, child: Expression, items: List[Expression]):
        self.child = child
        self.items = items

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child] + self.items

    def with_children(self, children):
        return IsIn(children[0], children[1:])

    def to_field(self, schema: Schema) -> Field:
        return Field(self.child.to_field(schema).name, DataType.bool())

    def __repr__(self):
        return f"{self.child!r}.is_in({self.items!r})"


class Between(Expression):
    def __init__(self, child: Expression, lower: Expression, upper: Expression):
        self.child = child
        self.lower = lower
        self.upper = upper

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child, self.lower, self.upper]

    def with_children(self, children):
        return Between(children[0], children[1], children[2])

    def to_field(self, schema: Schema) -> Field:
        return Field(self.child.to_field(schema).name, DataType.bool())

    def __repr__(self):
        return f"{self.child!r}.between({self.lower!r}, {self.upper!r})"


class IfElse(Expression):
    def __init__(self, predicate: Expression, if_true: Expression, if_false: Expression):
        self.predicate = predicate
        self.if_true = if_true
        self.if_false = if_false

    def name(self) -> str:
        try:
            return self.if_true.name()
        except Exception:  # lint: ignore[broad-except] -- nameless branch: fall back to predicate
            return self.predicate.name()

    def children(self):
        return [self.predicate, self.if_true, self.if_false]

    def with_children(self, children):
        return IfElse(children[0], children[1], children[2])

    def to_field(self, schema: Schema) -> Field:
        t = self.if_true.to_field(schema)
        f = self.if_false.to_field(schema)
        dt = _common_supertype(t.dtype, f.dtype)
        return Field(self.name(), dt)

    def __repr__(self):
        return f"{self.predicate!r}.if_else({self.if_true!r}, {self.if_false!r})"


class Function(Expression):
    """A call into the scalar function registry (reference: ScalarUDF trait,
    src/daft-dsl/src/functions/scalar.rs:205)."""

    def __init__(self, fname: str, args: List[Expression], kwargs: Optional[Dict[str, Any]] = None):
        self.fname = fname
        self.args = args
        self.kwargs = kwargs or {}

    def name(self) -> str:
        return self.args[0].name() if self.args else self.fname

    def children(self):
        return list(self.args)

    def with_children(self, children):
        return Function(self.fname, children, self.kwargs)

    def to_field(self, schema: Schema) -> Field:
        from ..functions.registry import get_function

        spec = get_function(self.fname)
        arg_fields = [a.to_field(schema) for a in self.args]
        dtype = spec.return_type(arg_fields, self.kwargs)
        return Field(self.name(), dtype)

    def __repr__(self):
        # the keyword arguments too: caches key compiled programs and resident
        # slots on an expression's repr, and round(x, 1) is not round(x, 0)
        inner = ", ".join([repr(a) for a in self.args]
                          + [f"{k}={v!r}" for k, v in sorted(self.kwargs.items())])
        return f"{self.fname}({inner})"


_AGG_OPS = {
    "sum", "mean", "min", "max", "count", "count_distinct", "any_value", "stddev",
    "var", "skew", "bool_and", "bool_or", "list", "set", "concat", "product",
    "string_agg", "approx_count_distinct",
    "approx_percentile",
}


class AggExpr(Expression):
    def __init__(self, op: str, child: Expression, params: Optional[Dict[str, Any]] = None):
        if op not in _AGG_OPS:
            raise ValueError(f"unknown aggregation {op!r}")
        self.op = op
        self.child = child
        self.params = params or {}

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child]

    def with_children(self, children):
        return AggExpr(self.op, children[0], self.params)

    def to_field(self, schema: Schema) -> Field:
        f = self.child.to_field(schema)
        op = self.op
        if op in ("sum", "product"):
            from ..core.series import _agg_sum_dtype

            return Field(f.name, _agg_sum_dtype(f.dtype))
        if op in ("mean", "stddev", "var", "skew"):
            return Field(f.name, DataType.float64())
        if op in ("count", "count_distinct", "approx_count_distinct"):
            return Field(f.name, DataType.uint64())
        if op in ("min", "max", "any_value"):
            return Field(f.name, f.dtype)
        if op in ("bool_and", "bool_or"):
            return Field(f.name, DataType.bool())
        if op == "string_agg":
            return Field(f.name, DataType.string())
        if op in ("list", "set"):
            return Field(f.name, DataType.list(f.dtype))
        if op == "concat":
            if not f.dtype.is_list():
                raise ValueError(f"agg_concat requires list dtype, got {f.dtype}")
            return Field(f.name, f.dtype)
        if op == "approx_percentile":
            single = not isinstance(self.params.get("percentiles"), list)
            return Field(f.name, DataType.float64() if single
                         else DataType.list(DataType.float64()))
        raise ValueError(op)

    def __repr__(self):
        return f"{self.child!r}.{self.op}()"


class _UnboundWindowFn(Expression):
    """A window function (lag/lead/first/last/row_number/rank/...) before .over()
    binds it to a Window spec."""

    def __init__(self, func: str, child: Optional[Expression], params: Dict[str, Any]):
        self.func = func
        self.child = child
        self.params = params

    def name(self) -> str:
        return self.child.name() if self.child is not None else self.func

    def children(self):
        return [self.child] if self.child is not None else []

    def with_children(self, children):
        return _UnboundWindowFn(self.func, children[0] if children else None, self.params)

    def over(self, spec) -> "WindowExpr":
        return WindowExpr(self.func, self.child, spec, self.params)

    def to_field(self, schema: Schema) -> Field:
        raise ValueError(f"{self.func}() must be bound with .over(window)")

    def __repr__(self):
        return f"{self.child!r}.{self.func}({self.params})"


# ranking functions need no child; value functions (lag/lead/first/last) take one
_WINDOW_FNS = {
    "row_number", "rank", "dense_rank", "percent_rank", "cume_dist", "ntile",
    "lag", "lead", "first_value", "last_value",
}


class WindowExpr(Expression):
    """A window function or windowed aggregation bound to a Window spec.

    Reference parity: src/daft-dsl/src/expr/mod.rs:464 (WindowExpr) +
    Expr::Over. `func` is either a name from _WINDOW_FNS or an AggExpr op; `child`
    is the value expression (None for pure ranking fns).
    """

    def __init__(self, func: str, child: Optional[Expression], spec: Any,
                 params: Optional[Dict[str, Any]] = None, out_name: Optional[str] = None):
        if func not in _WINDOW_FNS and func not in _AGG_OPS:
            raise ValueError(f"unknown window function {func!r}")
        self.func = func
        self.child = child
        self.spec = spec
        self.params = params or {}
        self._out_name = out_name

    def name(self) -> str:
        if self._out_name:
            return self._out_name
        return self.child.name() if self.child is not None else self.func

    def alias(self, name: str) -> "WindowExpr":
        return WindowExpr(self.func, self.child, self.spec, self.params, name)

    def children(self):
        """Includes the spec's partition/order expressions so column-reference
        analysis (pruning, SQL qualified-name resolution) sees them."""
        out = [self.child] if self.child is not None else []
        out.extend(self.spec.partition_by_exprs)
        out.extend(self.spec.order_by_exprs)
        return out

    def with_children(self, children):
        i = 0
        child = None
        if self.child is not None:
            child = children[0]
            i = 1
        np_ = len(self.spec.partition_by_exprs)
        no = len(self.spec.order_by_exprs)
        spec = self.spec._copy()
        spec.partition_by_exprs = list(children[i:i + np_])
        spec.order_by_exprs = list(children[i + np_:i + np_ + no])
        return WindowExpr(self.func, child, spec, self.params, self._out_name)

    def to_field(self, schema: Schema) -> Field:
        name = self.name()
        if self.func in ("row_number", "rank", "dense_rank", "ntile"):
            return Field(name, DataType.uint64())
        if self.func in ("percent_rank", "cume_dist"):
            return Field(name, DataType.float64())
        if self.func in ("lag", "lead", "first_value", "last_value"):
            return Field(name, self.child.to_field(schema).dtype)
        agg = AggExpr(self.func, self.child, self.params)
        return Field(name, agg.to_field(schema).dtype)

    def __repr__(self):
        base = f"{self.child!r}.{self.func}" if self.child is not None else self.func
        return f"{base}.over({self.spec!r})"


# ---- namespaces -------------------------------------------------------------------


class _Namespace:
    def __init__(self, expr: Expression):
        self._e = expr


class StringNamespace(_Namespace):
    def upper(self):
        return self._e._fn("utf8_upper")

    def title(self):
        return self._e._fn("utf8_title")

    def levenshtein(self, other):
        return self._e._fn("levenshtein", other)

    def jaccard_similarity(self, other, ngram: int = 2):
        return self._e._fn("jaccard_similarity", other, ngram=ngram)

    def md5(self):
        return self._e._fn("md5")

    def sha256(self):
        return self._e._fn("sha256")

    def lower(self):
        return self._e._fn("utf8_lower")

    def length(self):
        return self._e._fn("utf8_length")

    def length_bytes(self):
        return self._e._fn("utf8_length_bytes")

    def contains(self, pat):
        return self._e._fn("utf8_contains", pat)

    def startswith(self, pat):
        return self._e._fn("utf8_startswith", pat)

    def endswith(self, pat):
        return self._e._fn("utf8_endswith", pat)

    def split(self, pat, regex: bool = False):
        return self._e._fn("utf8_split", pat, regex=regex)

    def concat(self, other):
        return BinaryOp("add", self._e, self._e._other(other))

    def substr(self, start, length=None):
        return self._e._fn("utf8_substr", start, length)

    def replace(self, pat, replacement, regex: bool = False):
        return self._e._fn("utf8_replace", pat, replacement, regex=regex)

    def match(self, pattern):
        return self._e._fn("utf8_match", pattern)

    def extract(self, pattern, index: int = 0):
        return self._e._fn("utf8_extract", pattern, index=index)

    def extract_all(self, pattern, index: int = 0):
        return self._e._fn("utf8_extract_all", pattern, index=index)

    def find(self, substr):
        return self._e._fn("utf8_find", substr)

    def lstrip(self):
        return self._e._fn("utf8_lstrip")

    def rstrip(self):
        return self._e._fn("utf8_rstrip")

    def strip(self):
        return self._e._fn("utf8_strip")

    def reverse(self):
        return self._e._fn("utf8_reverse")

    def capitalize(self):
        return self._e._fn("utf8_capitalize")

    def left(self, n):
        return self._e._fn("utf8_left", n)

    def right(self, n):
        return self._e._fn("utf8_right", n)

    def repeat(self, n):
        return self._e._fn("utf8_repeat", n)

    def like(self, pattern):
        return self._e._fn("utf8_like", pattern)

    def ilike(self, pattern):
        return self._e._fn("utf8_ilike", pattern)

    def rpad(self, length, pad=" "):
        return self._e._fn("utf8_rpad", length, pad)

    def lpad(self, length, pad=" "):
        return self._e._fn("utf8_lpad", length, pad)

    def to_date(self, format: str):
        return self._e._fn("utf8_to_date", format=format)

    def to_datetime(self, format: str, timezone: Optional[str] = None):
        return self._e._fn("utf8_to_datetime", format=format, timezone=timezone)

    def normalize(self, remove_punct=False, lowercase=False, nfd_unicode=False, white_space=False):
        return self._e._fn(
            "utf8_normalize",
            remove_punct=remove_punct, lowercase=lowercase,
            nfd_unicode=nfd_unicode, white_space=white_space,
        )

    def count_matches(self, patterns, whole_words: bool = False, case_sensitive: bool = True):
        return self._e._fn(
            "utf8_count_matches", patterns, whole_words=whole_words, case_sensitive=case_sensitive
        )

    def tokenize_encode(self, tokenizer: str = "r50k_base"):
        return self._e._fn("tokenize_encode", tokenizer=tokenizer)

    def tokenize_decode(self, tokenizer: str = "r50k_base"):
        return self._e._fn("tokenize_decode", tokenizer=tokenizer)


class TemporalNamespace(_Namespace):
    def quarter(self):
        return self._e._fn("dt_quarter")

    def is_leap_year(self):
        return self._e._fn("dt_is_leap_year")

    def days_in_month(self):
        return self._e._fn("dt_days_in_month")

    def year(self):
        return self._e._fn("dt_year")

    def month(self):
        return self._e._fn("dt_month")

    def day(self):
        return self._e._fn("dt_day")

    def hour(self):
        return self._e._fn("dt_hour")

    def minute(self):
        return self._e._fn("dt_minute")

    def second(self):
        return self._e._fn("dt_second")

    def millisecond(self):
        return self._e._fn("dt_millisecond")

    def microsecond(self):
        return self._e._fn("dt_microsecond")

    def day_of_week(self):
        return self._e._fn("dt_day_of_week")

    def day_of_month(self):
        return self._e._fn("dt_day")

    def day_of_year(self):
        return self._e._fn("dt_day_of_year")

    def week_of_year(self):
        return self._e._fn("dt_week_of_year")

    def date(self):
        return self._e._fn("dt_date")

    def time(self):
        return self._e._fn("dt_time")

    def truncate(self, interval: str):
        return self._e._fn("dt_truncate", interval=interval)

    def to_unix_epoch(self, unit: str = "s"):
        return self._e._fn("dt_to_unix_epoch", unit=unit)

    def strftime(self, format: Optional[str] = None):
        return self._e._fn("dt_strftime", format=format)


class ListNamespace(_Namespace):
    def length(self):
        return self._e._fn("list_length")

    def get(self, idx, default=None):
        return self._e._fn("list_get", idx, default)

    def sum(self):
        return self._e._fn("list_sum")

    def mean(self):
        return self._e._fn("list_mean")

    def min(self):
        return self._e._fn("list_min")

    def max(self):
        return self._e._fn("list_max")

    def count(self, mode: str = "valid"):
        return self._e._fn("list_count", mode=mode)

    def join(self, delimiter: str):
        return self._e._fn("list_join", delimiter)

    def contains(self, value):
        return self._e._fn("list_contains", value)

    def slice(self, start, end=None):
        return self._e._fn("list_slice", start, end)

    def sort(self, desc: bool = False):
        return self._e._fn("list_sort", desc=desc)

    def distinct(self):
        return self._e._fn("list_distinct")

    def value_counts(self):
        return self._e._fn("list_value_counts")

    def chunk(self, size: int):
        return self._e._fn("list_chunk", size=size)


class FloatNamespace(_Namespace):
    def is_nan(self):
        return self._e._fn("is_nan")

    def is_inf(self):
        return self._e._fn("is_inf")

    def not_nan(self):
        return self._e._fn("not_nan")

    def fill_nan(self, value):
        return self._e._fn("fill_nan", value)


class EmbeddingNamespace(_Namespace):
    def cosine_distance(self, other):
        return self._e._fn("cosine_distance", other)

    def dot(self, other):
        return self._e._fn("dot", other)

    def euclidean_distance(self, other):
        return self._e._fn("euclidean_distance", other)

    def norm(self):
        return self._e._fn("embedding_norm")


class ImageNamespace(_Namespace):
    """Image ops (reference: daft Expression.image namespace / daft-image ops.rs)."""

    def decode(self, mode: Optional[str] = None, on_error: str = "raise"):
        return self._e._fn("image_decode", mode=mode, on_error=on_error)

    def encode(self, image_format: str = "PNG"):
        return self._e._fn("image_encode", image_format=image_format)

    def resize(self, w: int, h: int):
        return self._e._fn("image_resize", w=w, h=h)

    def crop(self, bbox):
        return self._e._fn("image_crop", bbox=tuple(bbox))

    def to_mode(self, mode: str):
        return self._e._fn("image_to_mode", mode=mode)

    def to_fixed_shape(self, mode: str, h: int, w: int):
        """Dense (h, w, c) batch layout — the TPU preprocessing entry point."""
        return self._e._fn("image_to_fixed_shape", mode=mode, h=h, w=w)


class UrlNamespace(_Namespace):
    """URL fetch ops (reference: daft-functions-uri url download/upload)."""

    def download(self, on_error: str = "raise", timeout: int = 30):
        return self._e._fn("url_download", on_error=on_error, timeout=timeout)

    def upload(self, location: str):
        return self._e._fn("url_upload", location=location)


class StructNamespace(_Namespace):
    def get(self, name: str):
        return self._e._fn("struct_get", name=name)


# ---- public constructors ----------------------------------------------------------


def col(name: str) -> Expression:
    return ColumnRef(name)


def lit(value: Any, dtype: Optional[DataType] = None) -> Expression:
    return Literal(value, dtype)


def _infer_literal_dtype(v: Any) -> DataType:
    if v is None:
        return DataType.null()
    if isinstance(v, bool):
        return DataType.bool()
    if isinstance(v, (int, np.integer)):
        return DataType.int64() if not isinstance(v, np.unsignedinteger) else DataType.uint64()
    if isinstance(v, (float, np.floating)):
        return DataType.float64()
    if isinstance(v, str):
        return DataType.string()
    if isinstance(v, bytes):
        return DataType.binary()
    if isinstance(v, decimal.Decimal):
        d = v.as_tuple()
        return DataType.decimal128(max(len(d.digits), 1), max(-d.exponent, 0))
    if isinstance(v, datetime.datetime):
        return DataType.timestamp("us", v.tzinfo.tzname(None) if v.tzinfo else None)
    if isinstance(v, datetime.date):
        return DataType.date()
    if isinstance(v, datetime.timedelta):
        return DataType.duration("us")
    if isinstance(v, (list, tuple)):
        if not v:
            return DataType.list(DataType.null())
        return DataType.list(_infer_literal_dtype(v[0]))
    if isinstance(v, np.ndarray):
        inner = DataType.from_arrow(__import__("pyarrow").from_numpy_dtype(v.dtype))
        return DataType.fixed_shape_tensor(inner, v.shape)
    return DataType.python()


# ---- type promotion ---------------------------------------------------------------


def _arith_result_type(op: str, l: DataType, r: DataType) -> DataType:
    if op == "add" and l.is_string() and r.is_string():
        return DataType.string()
    if op == "div":
        if l.is_numeric() and r.is_numeric():
            return DataType.float64()
        raise ValueError(f"cannot divide {l} by {r}")
    if op == "pow":
        return DataType.float64()
    # temporal arithmetic
    if l.is_temporal() or r.is_temporal():
        return _temporal_arith_type(op, l, r)
    if l.is_null():
        return r
    if r.is_null():
        return l
    if not (l.is_numeric() and r.is_numeric()):
        raise ValueError(f"arith op {op!r} unsupported between {l} and {r}")
    if l.is_decimal() or r.is_decimal():
        return l if l.is_decimal() else r
    out = np.promote_types(l.to_numpy(), r.to_numpy())
    return DataType.from_arrow(__import__("pyarrow").from_numpy_dtype(out))


def _temporal_arith_type(op: str, l: DataType, r: DataType) -> DataType:
    if op == "sub":
        if l.kind == "timestamp" and r.kind == "timestamp":
            return DataType.duration(l.time_unit)
        if l.kind == "date" and r.kind == "date":
            return DataType.duration("s")
        if l.kind == "timestamp" and r.kind == "duration":
            return l
        if l.kind == "date" and r.kind == "duration":
            return l
    if op == "add":
        if l.kind == "timestamp" and r.kind == "duration":
            return l
        if l.kind == "duration" and r.kind == "timestamp":
            return r
        if l.kind == "date" and r.kind == "duration":
            return l
        if l.kind == "duration" and r.kind == "duration":
            return l
    raise ValueError(f"temporal arithmetic {op!r} unsupported between {l} and {r}")


def _common_supertype(a: DataType, b: DataType) -> DataType:
    if a == b:
        return a
    if a.is_null():
        return b
    if b.is_null():
        return a
    if a.is_numeric() and b.is_numeric() and not (a.is_decimal() or b.is_decimal()):
        out = np.promote_types(a.to_numpy(), b.to_numpy())
        return DataType.from_arrow(__import__("pyarrow").from_numpy_dtype(out))
    if a.is_string() and b.is_string():
        return a
    raise ValueError(f"no common supertype for {a} and {b}")


class BinaryNamespace(_Namespace):
    """Binary-column kernels (reference: daft-functions-binary)."""

    def length(self):
        return self._e._fn("binary_length")

    def concat(self, other):
        return self._e._fn("binary_concat", other)

    def slice(self, start: int, length=None):
        kw = {"start": start}
        if length is not None:
            kw["length"] = length
        return self._e._fn("binary_slice", **kw)

    def encode_hex(self):
        return self._e._fn("encode_hex")

    def decode_hex(self):
        return self._e._fn("decode_hex")

    def encode_base64(self):
        return self._e._fn("encode_base64")

    def decode_base64(self):
        return self._e._fn("decode_base64")


class MapNamespace(_Namespace):
    """Map-column kernels (reference: daft-functions map_get)."""

    def get(self, key):
        return self._e._fn("map_get", key=key)


class JsonNamespace(_Namespace):
    """JSON string kernels (reference: daft-functions-json jsonpath query)."""

    def query(self, path: str):
        return self._e._fn("json_query", path=path)


# ======================================================================================
# Flat top-level API (reference: daft/expressions/expressions.py exposes the
# namespace operations directly on Expression as well — upper() == str.upper(),
# day() == dt.day(), list_sum() == list.sum(), ... — so both call styles work)
# ======================================================================================

_FLAT_NAMESPACE_ALIASES = {
    # name -> (namespace attr, namespace method)
    "capitalize": ("str", "capitalize"), "count_matches": ("str", "count_matches"),
    "endswith": ("str", "endswith"), "find": ("str", "find"),
    "ilike": ("str", "ilike"), "left": ("str", "left"),
    "like": ("str", "like"), "lower": ("str", "lower"),
    "lpad": ("str", "lpad"), "lstrip": ("str", "lstrip"),
    "lengths_bytes": ("str", "length_bytes"), "length_bytes": ("str", "length_bytes"),
    "normalize": ("str", "normalize"), "repeat": ("str", "repeat"),
    "replace": ("str", "replace"), "reverse": ("str", "reverse"),
    "right": ("str", "right"), "rpad": ("str", "rpad"),
    "rstrip": ("str", "rstrip"), "split": ("str", "split"),
    "startswith": ("str", "startswith"), "strip": ("str", "strip"),
    "substr": ("str", "substr"), "upper": ("str", "upper"),
    "to_date": ("str", "to_date"), "to_datetime": ("str", "to_datetime"),
    "jaccard_similarity": ("str", "jaccard_similarity"),
    "regexp": ("str", "match"), "regexp_extract": ("str", "extract"),
    "regexp_extract_all": ("str", "extract_all"),
    "date": ("dt", "date"), "day": ("dt", "day"),
    "day_of_month": ("dt", "day_of_month"), "day_of_week": ("dt", "day_of_week"),
    "day_of_year": ("dt", "day_of_year"), "hour": ("dt", "hour"),
    "microsecond": ("dt", "microsecond"), "millisecond": ("dt", "millisecond"),
    "minute": ("dt", "minute"), "month": ("dt", "month"),
    "quarter": ("dt", "quarter"), "second": ("dt", "second"),
    "time": ("dt", "time"), "week_of_year": ("dt", "week_of_year"),
    "year": ("dt", "year"), "strftime": ("dt", "strftime"),
    "to_unix_epoch": ("dt", "to_unix_epoch"), "date_trunc": ("dt", "truncate"),
    "fill_nan": ("float", "fill_nan"), "is_inf": ("float", "is_inf"),
    "is_nan": ("float", "is_nan"), "not_nan": ("float", "not_nan"),
    "list_contains": ("list", "contains"), "list_count": ("list", "count"),
    "list_distinct": ("list", "distinct"), "list_join": ("list", "join"),
    "list_max": ("list", "max"), "list_mean": ("list", "mean"),
    "list_min": ("list", "min"), "list_sort": ("list", "sort"),
    "list_sum": ("list", "sum"), "value_counts": ("list", "value_counts"),
    "chunk": ("list", "chunk"),
    "cosine_distance": ("embedding", "cosine_distance"),
    "euclidean_distance": ("embedding", "euclidean_distance"),
    "dot_product": ("embedding", "dot"),
    "crop": ("image", "crop"), "resize": ("image", "resize"),
    "convert_image": ("image", "to_mode"), "encode_image": ("image", "encode"),
    "decode_image": ("image", "decode"), "image_to_tensor": ("image", "to_fixed_shape"),
    "download": ("url", "download"), "upload": ("url", "upload"),
    "map_get": ("map", "get"), "jq": ("json", "query"),
}

_FLAT_REGISTRY_FNS = [
    # direct registry calls: name -> registered function
    "arccosh", "arcsinh", "arctanh", "arctan2", "cbrt", "cosh", "sinh", "tanh",
    "cot", "sec", "csc", "degrees", "radians", "expm1", "log1p",
    "to_camel_case", "to_snake_case", "to_kebab_case", "to_title_case",
    "to_upper_camel_case", "to_upper_snake_case", "to_upper_kebab_case",
    "parse_url", "shift_left", "shift_right",
    "total_days", "total_hours", "total_minutes", "total_seconds",
    "total_milliseconds", "total_microseconds", "total_nanoseconds",
    "unix_date", "image_height", "image_width", "image_channel", "image_hash",
]


def _install_flat_api():
    def make_ns_alias(ns_attr, meth):
        def flat(self, *args, **kwargs):
            return getattr(getattr(self, ns_attr), meth)(*args, **kwargs)

        flat.__name__ = meth
        flat.__qualname__ = f"Expression.{meth}"
        flat.__doc__ = f"Alias of Expression.{ns_attr}.{meth}() (flat reference API)."
        return flat

    for name, (ns_attr, meth) in _FLAT_NAMESPACE_ALIASES.items():
        if not hasattr(Expression, name):
            setattr(Expression, name, make_ns_alias(ns_attr, meth))

    def make_registry_call(fname):
        def flat(self, *args, **kwargs):
            return self._fn(fname, *args, **kwargs)

        flat.__name__ = fname
        flat.__qualname__ = f"Expression.{fname}"
        flat.__doc__ = f"Scalar function {fname!r} from the registry (flat API)."
        return flat

    for fname in _FLAT_REGISTRY_FNS:
        if not hasattr(Expression, fname):
            setattr(Expression, fname, make_registry_call(fname))


_install_flat_api()


def _flat_length(self):
    """Dtype-dispatched length: list length for lists, codepoint length for
    strings, byte length for binary (reference flat Expression.length)."""
    return _TypeDispatch(self, {"list": ("list", "length"),
                                "string": ("str", "length"),
                                "binary": ("binary", "length")}, "length")


def _flat_get(self, key_or_index, default=None):
    """Dtype-dispatched get: list index / map key / struct field."""
    return _TypeDispatch(self, {"list": ("list", "get"), "map": ("map", "get"),
                                "struct": ("struct", "get")}, "get",
                         key_or_index)


def _flat_contains(self, item):
    """Dtype-dispatched contains: list membership or substring match."""
    return _TypeDispatch(self, {"list": ("list", "contains"),
                                "string": ("str", "contains")}, "contains", item)


def _flat_slice(self, start, end=None):
    """Dtype-dispatched slice: list or binary slice."""
    return _TypeDispatch(self, {"list": ("list", "slice"),
                                "binary": ("binary", "slice")}, "slice", start, end)


def _flat_concat(self, other):
    """Dtype-dispatched concat: string or binary elementwise concat."""
    return _TypeDispatch(self, {"string": ("str", "concat"),
                                "binary": ("binary", "concat")}, "concat", other)


class _TypeDispatch(Expression):
    """Defers namespace selection until the input dtype is known (to_field
    binds it); evaluation rewrites to the concrete namespace expression."""

    def __init__(self, child: Expression, table, opname, *args):
        self.child = child
        self.table = table
        self.opname = opname
        self.args = args

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child]

    def with_children(self, children):
        return _TypeDispatch(children[0], self.table, self.opname, *self.args)

    def _resolve(self, schema: Schema) -> Expression:
        dt = self.child.to_field(schema).dtype
        if dt.is_list():
            kind = "list"
        elif dt.is_string():
            kind = "string"
        elif dt.is_binary():
            kind = "binary"
        elif dt.is_map():
            kind = "map"
        elif dt.is_struct():
            kind = "struct"
        else:
            kind = dt.kind
        hit = self.table.get(kind)
        if hit is None:
            raise ValueError(
                f"{self.opname}() does not support dtype {dt}; "
                f"supported kinds: {sorted(self.table)}")
        ns_attr, meth = hit
        args = [a for a in self.args if a is not None] if self.opname == "slice" \
            else list(self.args)
        return getattr(getattr(self.child, ns_attr), meth)(*args)

    def to_field(self, schema: Schema) -> Field:
        return self._resolve(schema).to_field(schema)

    def __repr__(self):
        return f"{self.child!r}.{self.opname}({', '.join(map(repr, self.args))})"


Expression.length = _flat_length
Expression.get = _flat_get
Expression.contains = _flat_contains
Expression.slice = _flat_slice
Expression.concat = _flat_concat


def _flat_coalesce(self, *others):
    """First non-null across self and others (reference Expression.coalesce)."""
    return self._fn("coalesce", *others)


def _flat_pow(self, exponent):
    return self ** exponent


def _flat_negate(self):
    return -self


def _flat_ln(self):
    return self.log()


def _flat_approx_percentiles(self, percentiles, alpha: float = 0.01):
    return self.approx_percentile(percentiles, alpha)


Expression.coalesce = _flat_coalesce
Expression.pow = _flat_pow
Expression.power = _flat_pow
Expression.negate = _flat_negate
Expression.ln = _flat_ln
Expression.approx_percentiles = _flat_approx_percentiles


def _flat_is_column(self) -> bool:
    return isinstance(self, ColumnRef)


def _flat_is_literal(self) -> bool:
    return isinstance(self, Literal)


def _flat_as_py(self):
    """Literal's python value (reference Expression.as_py)."""
    if not isinstance(self, Literal):
        raise ValueError("as_py() requires a literal expression")
    return self.value


def _flat_column_name(self):
    return self.name()


Expression.is_column = _flat_is_column
Expression.is_literal = _flat_is_literal
Expression.as_py = _flat_as_py
Expression.column_name = _flat_column_name


def _flat_serialize(self, format: str = "json"):
    return self._fn("serialize", format=format)


def _flat_deserialize(self, format: str = "json", dtype=None):
    return self._fn("deserialize", format=format, dtype=dtype)


def _flat_try_deserialize(self, format: str = "json", dtype=None):
    return self._fn("try_deserialize", format=format, dtype=dtype)


def _flat_compress(self, codec: str = "gzip"):
    return self._fn("compress", codec=codec)


def _flat_decompress(self, codec: str = "gzip"):
    return self._fn("decompress", codec=codec)


def _flat_try_compress(self, codec: str = "gzip"):
    return self._fn("try_compress", codec=codec)


def _flat_try_decompress(self, codec: str = "gzip"):
    return self._fn("try_decompress", codec=codec)


def _flat_replace_time_zone(self, tz=None):
    return self._fn("replace_time_zone", tz=tz)


def _flat_convert_time_zone(self, tz: str):
    return self._fn("convert_time_zone", tz=tz)


def _flat_nanosecond(self):
    return self._fn("dt_nanosecond")


Expression.serialize = _flat_serialize
Expression.deserialize = _flat_deserialize
Expression.try_deserialize = _flat_try_deserialize
Expression.compress = _flat_compress
Expression.decompress = _flat_decompress
Expression.try_compress = _flat_try_compress
Expression.try_decompress = _flat_try_decompress
Expression.replace_time_zone = _flat_replace_time_zone
Expression.convert_time_zone = _flat_convert_time_zone
Expression.nanosecond = _flat_nanosecond


def _flat_bitwise_and(self, other):
    return self._fn("bitwise_and", other)


def _flat_bitwise_or(self, other):
    return self._fn("bitwise_or", other)


def _flat_bitwise_xor(self, other):
    return self._fn("bitwise_xor", other)


Expression.bitwise_and = _flat_bitwise_and
Expression.bitwise_or = _flat_bitwise_or
Expression.bitwise_xor = _flat_bitwise_xor


def _flat_product(self):
    """Product aggregation (reference: Expression.product)."""
    return AggExpr("product", self)


def _flat_string_agg(self, delimiter: str = ""):
    """Join string values into one string (reference: Expression.string_agg)."""
    return AggExpr("string_agg", self, {"delimiter": delimiter})


def _flat_list_agg(self):
    return AggExpr("list", self)


def _flat_list_agg_distinct(self):
    return AggExpr("set", self)


def _flat_regexp_count(self, pattern):
    """Count regex matches (reference: Expression.regexp_count)."""
    return self.str.extract_all(pattern).list.length()


def _flat_regexp_replace(self, pattern, replacement):
    return self.str.replace(pattern, replacement, regex=True)


def _flat_regexp_split(self, pattern):
    return self.str.split(pattern, regex=True)


def _flat_cosine_similarity(self, other):
    from .expressions import Literal as _Lit  # self-module; kept explicit

    return 1.0 - self.embedding.cosine_distance(other)


def _flat_encode(self, codec: str = "utf-8"):
    return self._fn("codec_encode", codec=codec)


def _flat_decode(self, codec: str = "utf-8"):
    return self._fn("codec_decode", codec=codec)


def _flat_try_encode(self, codec: str = "utf-8"):
    return self._fn("try_codec_encode", codec=codec)


def _flat_try_decode(self, codec: str = "utf-8"):
    return self._fn("try_codec_decode", codec=codec)


def _flat_list_append(self, other):
    return self._fn("list_append", other)


def _flat_list_bool_and(self):
    return self._fn("list_bool_and")


def _flat_list_bool_or(self):
    return self._fn("list_bool_or")


def _flat_image_mode(self):
    return self._fn("image_mode")


def _flat_image_attribute(self, name: str):
    table = {"height": "image_height", "width": "image_width",
             "channel": "image_channel", "mode": "image_mode"}
    if name not in table:
        raise ValueError(f"unknown image attribute {name!r}; known: {sorted(table)}")
    return self._fn(table[name])


Expression.product = _flat_product
Expression.string_agg = _flat_string_agg
Expression.list_agg = _flat_list_agg
Expression.list_agg_distinct = _flat_list_agg_distinct
Expression.regexp_count = _flat_regexp_count
Expression.regexp_replace = _flat_regexp_replace
Expression.regexp_split = _flat_regexp_split
Expression.cosine_similarity = _flat_cosine_similarity
Expression.encode = _flat_encode
Expression.decode = _flat_decode
Expression.try_encode = _flat_try_encode
Expression.try_decode = _flat_try_decode
Expression.list_append = _flat_list_append
Expression.list_bool_and = _flat_list_bool_and
Expression.list_bool_or = _flat_list_bool_or
Expression.image_mode = _flat_image_mode
Expression.image_attribute = _flat_image_attribute


class Unnest(Expression):
    """Marker expanded by DataFrame.select into one column per struct field
    (reference: Expression.unnest / col("s").unnest() wildcard expansion)."""

    def __init__(self, child: Expression):
        self.child = child

    def name(self) -> str:
        return self.child.name()

    def children(self):
        return [self.child]

    def with_children(self, children):
        return Unnest(children[0])

    def to_field(self, schema: Schema) -> Field:
        raise ValueError("unnest() can only be used directly inside select()")


def _flat_unnest(self):
    return Unnest(self)


Expression.unnest = _flat_unnest


def _flat_partition_days(self):
    return self._fn("partition_days")


def _flat_partition_hours(self):
    return self._fn("partition_hours")


def _flat_partition_months(self):
    return self._fn("partition_months")


def _flat_partition_years(self):
    return self._fn("partition_years")


def _flat_partition_iceberg_bucket(self, n: int):
    """Iceberg bucket transform: murmur3_32-based bucket id (iceberg spec)."""
    return self._fn("partition_iceberg_bucket", n=n)


def _flat_partition_iceberg_truncate(self, w: int):
    """Iceberg truncate transform (int floor-to-width / string prefix)."""
    return self._fn("partition_iceberg_truncate", w=w)


Expression.partition_days = _flat_partition_days
Expression.partition_hours = _flat_partition_hours
Expression.partition_months = _flat_partition_months
Expression.partition_years = _flat_partition_years
Expression.partition_iceberg_bucket = _flat_partition_iceberg_bucket
Expression.partition_iceberg_truncate = _flat_partition_iceberg_truncate


def _flat_file_path(self):
    """Path/URL of a file column's reference (reference: Expression.file_path)."""
    return self._fn("file_path")


def _flat_file_size(self, io_config=None):
    """Size in bytes, stat'ed lazily through the IO layer (reference:
    Expression.file_size)."""
    return self._fn("file_size", io_config=io_config)


def _flat_file_read(self, offset: int = 0, length=None, io_config=None):
    """Range-read a file column's bytes (reference: daft-file ranged reads)."""
    return self._fn("file_read", offset=offset, length=length, io_config=io_config)


Expression.file_path = _flat_file_path
Expression.file_size = _flat_file_size
Expression.file_read = _flat_file_read
