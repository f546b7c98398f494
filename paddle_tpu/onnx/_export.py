"""jaxpr -> ONNX lowering.

Reference parity: python/paddle/onnx/export.py (which shells out to the
paddle2onnx converter over the static Program). TPU-native design: the
model is traced to a jaxpr (the same trace `jit`/StableHLO export uses)
and each primitive maps to an ONNX-17 node; parameters become
initializers with their real state_dict names. Constant subgraphs
(iota masks, rope tables, ...) are folded by evaluating eagerly, so
only data-dependent ops land in the graph.

Supported op set covers the standard inference stack (linear/conv/norm/
attention/activations). Unmapped primitives raise with the primitive
named, pointing at the StableHLO AOT path which supports everything.
"""
from __future__ import annotations

import string
from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from . import _proto as P


class _Graph:
    def __init__(self):
        self.nodes: List[bytes] = []
        self.initializers: List[bytes] = []
        self.counter = 0
        self.const_cache: Dict[bytes, str] = {}

    def fresh(self, hint="t"):
        self.counter += 1
        return f"{hint}_{self.counter}"

    def add(self, op, inputs, outputs, attrs=()):
        self.nodes.append(P.node_proto(op, inputs, outputs,
                                       name=self.fresh(op.lower()),
                                       attrs=attrs))

    def const(self, arr: np.ndarray, hint="const"):
        arr = np.asarray(arr)
        key = (arr.dtype.str.encode() + str(arr.shape).encode()
               + arr.tobytes())
        if key in self.const_cache:
            return self.const_cache[key]
        name = self.fresh(hint)
        self.initializers.append(P.tensor_proto(name, arr))
        self.const_cache[key] = name
        return name


def _einsum_eq(dn, lhs_ndim, rhs_ndim):
    (lc, rc), (lb, rb) = dn
    letters = iter(string.ascii_lowercase)
    lhs = [None] * lhs_ndim
    rhs = [None] * rhs_ndim
    for i, j in zip(lb, rb):
        c = next(letters)
        lhs[i] = c
        rhs[j] = c
    for i, j in zip(lc, rc):
        c = next(letters)
        lhs[i] = c
        rhs[j] = c
    for i in range(lhs_ndim):
        if lhs[i] is None:
            lhs[i] = next(letters)
    for j in range(rhs_ndim):
        if rhs[j] is None:
            rhs[j] = next(letters)
    out = ([lhs[i] for i in lb]
           + [lhs[i] for i in range(lhs_ndim)
              if i not in set(lb) | set(lc)]
           + [rhs[j] for j in range(rhs_ndim)
              if j not in set(rb) | set(rc)])
    return "".join(lhs) + "," + "".join(rhs) + "->" + "".join(out)


_ELEMENTWISE = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
    "max": "Max", "min": "Min", "pow": "Pow", "rem": "Mod",
    "tanh": "Tanh", "exp": "Exp", "log": "Log", "logistic": "Sigmoid",
    "erf": "Erf", "sqrt": "Sqrt", "neg": "Neg", "abs": "Abs",
    "sign": "Sign", "floor": "Floor", "ceil": "Ceil", "sin": "Sin",
    "cos": "Cos",
    "eq": "Equal", "lt": "Less", "gt": "Greater", "ge": "GreaterOrEqual",
    "le": "LessOrEqual", "and": "And", "or": "Or", "not": "Not",
    "xor": "Xor",
}

_ONNX2NP = {P.FLOAT: np.float32, P.DOUBLE: np.float64,
            P.FLOAT16: np.float16, P.INT64: np.int64, P.INT32: np.int32,
            P.INT8: np.int8, P.UINT8: np.uint8, P.BOOL: np.bool_}


class _Lowerer:
    def __init__(self, graph: _Graph):
        self.g = graph
        self.env: Dict = {}     # jax Var -> name (str) or np const

    def read(self, atom):
        from jax.extend.core import Literal
        if isinstance(atom, Literal):
            return np.asarray(atom.val)
        return self.env[atom]

    def name_of(self, val, hint="c"):
        """Graph name for a value (materializing constants)."""
        if isinstance(val, str):
            return val
        return self.g.const(np.asarray(val), hint)

    # ------------------------------------------------------------------
    def lower_jaxpr(self, jaxpr, consts, in_names):
        for var, cval in zip(jaxpr.constvars, consts):
            self.env[var] = np.asarray(cval)
        for var, name in zip(jaxpr.invars, in_names):
            self.env[var] = name
        for eqn in jaxpr.eqns:
            self.eqn(eqn)
        return [self.read(o) for o in jaxpr.outvars]

    def eqn(self, eqn):
        prim = eqn.primitive.name
        ins = [self.read(v) for v in eqn.invars]

        # recurse into call-like primitives
        if prim in ("jit", "pjit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint",
                    "custom_vjp_call_jaxpr"):
            inner = (eqn.params.get("jaxpr")
                     or eqn.params.get("call_jaxpr")
                     or eqn.params.get("fun_jaxpr"))
            closed = inner if hasattr(inner, "jaxpr") else None
            jx = closed.jaxpr if closed else inner
            consts = closed.consts if closed else []
            sub = _Lowerer(self.g)
            in_names = [i if isinstance(i, str)
                        else np.asarray(i) for i in ins]
            outs = sub.lower_jaxpr(jx, consts, in_names)
            for var, o in zip(eqn.outvars, outs):
                self.env[var] = o
            return

        # constant fold when every input is concrete
        if all(not isinstance(i, str) for i in ins):
            out = eqn.primitive.bind(
                *[jnp.asarray(i) for i in ins], **eqn.params)
            outs = out if eqn.primitive.multiple_results else [out]
            for var, o in zip(eqn.outvars, outs):
                self.env[var] = np.asarray(o)
            return

        handler = getattr(self, f"_p_{prim}", None)
        if handler is None and prim in _ELEMENTWISE:
            handler = self._elementwise
        if handler is None:
            raise NotImplementedError(
                f"ONNX export: primitive '{prim}' has no mapping; use "
                "paddle_tpu.jit.save (StableHLO AOT) for full coverage")
        handler(eqn, ins)

    # ------------------------------------------------------------------
    def _out(self, eqn, idx=0, hint=None):
        name = self.g.fresh(hint or eqn.primitive.name)
        self.env[eqn.outvars[idx]] = name
        return name

    def _elementwise(self, eqn, ins):
        op = _ELEMENTWISE[eqn.primitive.name]
        names = [self.name_of(i) for i in ins]
        self.g.add(op, names, [self._out(eqn)])

    def _p_integer_pow(self, eqn, ins):
        y = np.asarray(float(eqn.params["y"]), np.float32)
        self.g.add("Pow", [self.name_of(ins[0]), self.g.const(y)],
                   [self._out(eqn)])

    def _p_erfc(self, eqn, ins):
        e = self.g.fresh("erf")
        self.g.add("Erf", [self.name_of(ins[0])], [e])
        one = self.g.const(np.asarray(
            1.0, eqn.invars[0].aval.dtype))
        self.g.add("Sub", [one, e], [self._out(eqn)])

    def _p_square(self, eqn, ins):
        x = self.name_of(ins[0])
        self.g.add("Mul", [x, x], [self._out(eqn)])

    def _p_rsqrt(self, eqn, ins):
        s = self.g.fresh("sqrt")
        self.g.add("Sqrt", [self.name_of(ins[0])], [s])
        self.g.add("Reciprocal", [s], [self._out(eqn)])

    def _p_is_finite(self, eqn, ins):
        x = self.name_of(ins[0])
        inf = self.g.fresh("isinf")
        nan = self.g.fresh("isnan")
        either = self.g.fresh("or")
        self.g.add("IsInf", [x], [inf])
        self.g.add("IsNaN", [x], [nan])
        self.g.add("Or", [inf, nan], [either])
        self.g.add("Not", [either], [self._out(eqn)])

    def _p_log1p(self, eqn, ins):
        one = self.g.const(np.asarray(1.0, eqn.invars[0].aval.dtype))
        a = self.g.fresh("add1")
        self.g.add("Add", [self.name_of(ins[0]), one], [a])
        self.g.add("Log", [a], [self._out(eqn)])

    def _p_dot_general(self, eqn, ins):
        eq = _einsum_eq(eqn.params["dimension_numbers"],
                        eqn.invars[0].aval.ndim, eqn.invars[1].aval.ndim)
        self.g.add("Einsum", [self.name_of(i) for i in ins],
                   [self._out(eqn)], attrs=[P.attr_str("equation", eq)])

    def _p_reshape(self, eqn, ins):
        shape = np.asarray(eqn.params["new_sizes"], np.int64)
        self.g.add("Reshape",
                   [self.name_of(ins[0]), self.g.const(shape, "shape")],
                   [self._out(eqn)])

    def _p_transpose(self, eqn, ins):
        self.g.add("Transpose", [self.name_of(ins[0])], [self._out(eqn)],
                   attrs=[P.attr_ints("perm", eqn.params["permutation"])])

    def _p_broadcast_in_dim(self, eqn, ins):
        shape = eqn.params["shape"]
        bdims = eqn.params["broadcast_dimensions"]
        # insert size-1 dims at non-broadcast positions, then Expand
        interim = [1] * len(shape)
        for src, dst in enumerate(bdims):
            interim[dst] = eqn.invars[0].aval.shape[src]
        r = self.g.fresh("bcast_reshape")
        self.g.add("Reshape",
                   [self.name_of(ins[0]),
                    self.g.const(np.asarray(interim, np.int64), "shape")],
                   [r])
        self.g.add("Expand",
                   [r, self.g.const(np.asarray(shape, np.int64), "shape")],
                   [self._out(eqn)])

    def _p_convert_element_type(self, eqn, ins):
        to = P.np_to_onnx_dtype(eqn.params["new_dtype"])
        self.g.add("Cast", [self.name_of(ins[0])], [self._out(eqn)],
                   attrs=[P.attr_int("to", to)])

    def _p_stop_gradient(self, eqn, ins):
        self.g.add("Identity", [self.name_of(ins[0])], [self._out(eqn)])

    def _p_copy(self, eqn, ins):
        self.g.add("Identity", [self.name_of(ins[0])], [self._out(eqn)])

    def _p_select_n(self, eqn, ins):
        if len(ins) != 3:
            raise NotImplementedError(
                "ONNX export: select_n with more than two cases; use "
                "jit.save (StableHLO) instead")
        pred, case_f, case_t = ins
        self.g.add("Where", [self.name_of(pred), self.name_of(case_t),
                             self.name_of(case_f)], [self._out(eqn)])

    def _p_concatenate(self, eqn, ins):
        self.g.add("Concat", [self.name_of(i) for i in ins],
                   [self._out(eqn)],
                   attrs=[P.attr_int("axis", eqn.params["dimension"])])

    def _p_slice(self, eqn, ins):
        starts = np.asarray(eqn.params["start_indices"], np.int64)
        ends = np.asarray(eqn.params["limit_indices"], np.int64)
        strides = eqn.params["strides"]
        axes = np.arange(len(starts), dtype=np.int64)
        inputs = [self.name_of(ins[0]), self.g.const(starts, "starts"),
                  self.g.const(ends, "ends"), self.g.const(axes, "axes")]
        if strides is not None:
            inputs.append(self.g.const(
                np.asarray(strides, np.int64), "steps"))
        self.g.add("Slice", inputs, [self._out(eqn)])

    def _p_squeeze(self, eqn, ins):
        dims = np.asarray(eqn.params["dimensions"], np.int64)
        self.g.add("Squeeze",
                   [self.name_of(ins[0]), self.g.const(dims, "axes")],
                   [self._out(eqn)])

    def _reduce(self, eqn, ins, op, axes_as_input):
        axes = np.asarray(eqn.params["axes"], np.int64)
        out = self._out(eqn)
        if axes_as_input:   # ReduceSum signature since opset 13
            self.g.add(op, [self.name_of(ins[0]),
                            self.g.const(axes, "axes")], [out],
                       attrs=[P.attr_int("keepdims", 0)])
        else:
            self.g.add(op, [self.name_of(ins[0])], [out],
                       attrs=[P.attr_ints("axes", axes.tolist()),
                              P.attr_int("keepdims", 0)])

    def _p_reduce_sum(self, eqn, ins):
        self._reduce(eqn, ins, "ReduceSum", True)

    def _p_reduce_max(self, eqn, ins):
        self._reduce(eqn, ins, "ReduceMax", False)

    def _p_reduce_min(self, eqn, ins):
        self._reduce(eqn, ins, "ReduceMin", False)

    def _p_reduce_and(self, eqn, ins):
        # all() over bool: cast -> ReduceMin -> cast back
        c = self.g.fresh("cast")
        self.g.add("Cast", [self.name_of(ins[0])], [c],
                   attrs=[P.attr_int("to", P.INT32)])
        r = self.g.fresh("rmin")
        axes = np.asarray(eqn.params["axes"], np.int64)
        self.g.add("ReduceMin", [c], [r],
                   attrs=[P.attr_ints("axes", axes.tolist()),
                          P.attr_int("keepdims", 0)])
        self.g.add("Cast", [r], [self._out(eqn)],
                   attrs=[P.attr_int("to", P.BOOL)])

    def _p_argmax(self, eqn, ins):
        axes = eqn.params["axes"]
        out = self._out(eqn)
        a = self.g.fresh("argmax")
        self.g.add("ArgMax", [self.name_of(ins[0])], [a],
                   attrs=[P.attr_int("axis", axes[0]),
                          P.attr_int("keepdims", 0)])
        to = P.np_to_onnx_dtype(eqn.outvars[0].aval.dtype)
        self.g.add("Cast", [a], [out], attrs=[P.attr_int("to", to)])

    def _p_conv_general_dilated(self, eqn, ins):
        p = eqn.params
        dn = p["dimension_numbers"]
        # only the NCHW/OIHW layout jax's lax.conv (and our Conv2D) uses
        if (dn.lhs_spec[0] != 0 or dn.lhs_spec[1] != 1
                or dn.rhs_spec[0] != 0 or dn.rhs_spec[1] != 1):
            raise NotImplementedError(
                "ONNX export: conv layout "
                f"{dn} is not NCHW/OIHW; use jit.save (StableHLO)")
        if p["lhs_dilation"] and any(d != 1 for d in p["lhs_dilation"]):
            raise NotImplementedError(
                "ONNX export: transposed conv (lhs_dilation) is not "
                "mapped; use jit.save (StableHLO)")
        pads_lo = [lo for lo, _ in p["padding"]]
        pads_hi = [hi for _, hi in p["padding"]]
        attrs = [P.attr_ints("strides", p["window_strides"]),
                 P.attr_ints("pads", list(pads_lo) + list(pads_hi)),
                 P.attr_ints("dilations", p["rhs_dilation"]),
                 P.attr_int("group", p["feature_group_count"])]
        self.g.add("Conv", [self.name_of(i) for i in ins],
                   [self._out(eqn)], attrs=attrs)

    def _p_split(self, eqn, ins):
        sizes = np.asarray(eqn.params["sizes"], np.int64)
        axis = int(eqn.params["axis"])
        outs = [self._out(eqn, i, "split") for i in range(len(sizes))]
        self.nodes_split(ins, sizes, axis, outs)

    def nodes_split(self, ins, sizes, axis, outs):
        self.g.nodes.append(P.node_proto(
            "Split", [self.name_of(ins[0]), self.g.const(sizes, "sizes")],
            outs, name=self.g.fresh("split"),
            attrs=[P.attr_int("axis", axis)]))

    def _window_2d(self, eqn):
        p = eqn.params
        wd = p["window_dimensions"]
        ws = p["window_strides"]
        pad = p["padding"]
        if (len(wd) < 3 or wd[0] != 1 or wd[1] != 1
                or p.get("base_dilation") and any(
                    d != 1 for d in p["base_dilation"])):
            raise NotImplementedError(
                "ONNX export: only NCHW spatial pooling windows are "
                "mapped; use jit.save (StableHLO)")
        kernel = list(wd[2:])
        strides = list(ws[2:])
        pads = ([lo for lo, _ in pad[2:]] + [hi for _, hi in pad[2:]])
        return kernel, strides, pads

    def _p_reduce_window_max(self, eqn, ins):
        kernel, strides, pads = self._window_2d(eqn)
        self.g.add("MaxPool", [self.name_of(ins[0])], [self._out(eqn)],
                   attrs=[P.attr_ints("kernel_shape", kernel),
                          P.attr_ints("strides", strides),
                          P.attr_ints("pads", pads)])

    def _p_reduce_window_sum(self, eqn, ins):
        # sum window = AveragePool * window_size (count_include_pad so
        # the divisor is constant)
        kernel, strides, pads = self._window_2d(eqn)
        ap = self.g.fresh("avgpool")
        self.g.add("AveragePool", [self.name_of(ins[0])], [ap],
                   attrs=[P.attr_ints("kernel_shape", kernel),
                          P.attr_ints("strides", strides),
                          P.attr_ints("pads", pads),
                          P.attr_int("count_include_pad", 1)])
        n = float(np.prod(kernel))
        self.g.add("Mul", [ap, self.g.const(np.asarray(
            n, eqn.invars[0].aval.dtype))], [self._out(eqn)])

    _p_reduce_window_add = _p_reduce_window_sum

    def _p_iota(self, eqn, ins):
        # reachable only with data-dependent inputs (never: iota has no
        # inputs so constant folding always handles it)
        raise AssertionError("iota should constant-fold")

    def _p_gather(self, eqn, ins):
        # the embedding-lookup pattern jnp.take/x[ids] produces:
        # collapsed slice on axis 0, index vector over axis 0
        dn = eqn.params["dimension_numbers"]
        op_shape = tuple(eqn.invars[0].aval.shape)
        slice_sizes = tuple(eqn.params["slice_sizes"])
        full_rows = (slice_sizes[:1] == (1,)
                     and slice_sizes[1:] == op_shape[1:])
        if (list(dn.collapsed_slice_dims) == [0]
                and list(dn.start_index_map) == [0] and full_rows):
            idx = self.name_of(ins[1], "indices")
            sq = self.g.fresh("idx_squeeze")
            self.g.add("Squeeze",
                       [idx, self.g.const(
                           np.asarray([-1], np.int64), "axes")], [sq])
            self.g.add("Gather", [self.name_of(ins[0]), sq],
                       [self._out(eqn)], attrs=[P.attr_int("axis", 0)])
            return
        raise NotImplementedError(
            "ONNX export: general lax.gather is not mapped (only "
            "axis-0 embedding lookup); use jit.save (StableHLO)")


def export_onnx_bytes(layer, input_specs, opset_version=17):
    """Trace layer.forward (eval mode) and lower to ONNX ModelProto
    bytes. input_specs: list of (shape, np dtype) with no dynamic dims."""
    from ..jit.bridge import functionalize
    from ..tensor import Tensor

    pure_fn, p_vals, b_vals, p_names, _ = functionalize(layer,
                                                        training=False)
    key = jax.random.key(0)
    examples = [jnp.zeros(s, d) for s, d in input_specs]

    def fwd(params, *xs):
        out, _, _ = pure_fn(params, b_vals, key, *xs)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        return tuple(o._value if isinstance(o, Tensor) else o
                     for o in outs)

    closed = jax.make_jaxpr(fwd)(p_vals, *examples)

    g = _Graph()
    # params -> initializers under their real state_dict names
    in_names = []
    for name, val in zip(p_names, p_vals):
        arr = np.asarray(val)
        g.initializers.append(P.tensor_proto(name, arr))
        in_names.append(name)
    graph_inputs = []
    for i, (s, d) in enumerate(input_specs):
        nm = f"input_{i}"
        in_names.append(nm)
        graph_inputs.append(P.value_info(
            nm, P.np_to_onnx_dtype(np.dtype(d)), s))

    low = _Lowerer(g)
    outs = low.lower_jaxpr(closed.jaxpr, closed.consts, in_names)

    graph_outputs = []
    out_names = []
    for i, (o, var) in enumerate(zip(outs, closed.jaxpr.outvars)):
        nm = low.name_of(o, "output")
        out_names.append(nm)
        graph_outputs.append(P.value_info(
            nm, P.np_to_onnx_dtype(var.aval.dtype),
            var.aval.shape))

    graph = P.graph_proto(g.nodes, "paddle_tpu_graph", g.initializers,
                          graph_inputs, graph_outputs)
    return P.model_proto(graph, opset=opset_version), out_names
