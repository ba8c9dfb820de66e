"""LayerHelper: shared parameter/bias/activation plumbing for layer functions
(reference: fluid/layer_helper.py:10)."""
from __future__ import annotations

from typing import Optional

from .core import unique_name
from .core.program import (Parameter, Variable, default_main_program,
                           default_startup_program)
from .initializer import (ConstantInitializer, Initializer,
                          XavierInitializer)
from .param_attr import ParamAttr


class LayerHelper:
    def __init__(self, layer_type: str, **kwargs):
        self.kwargs = kwargs
        self.layer_type = layer_type
        name = kwargs.get("name")
        self.name = name if name else unique_name.generate(layer_type)

    @property
    def main_program(self):
        return self.kwargs.get("main_program") or default_main_program()

    @property
    def startup_program(self):
        return self.kwargs.get("startup_program") or default_startup_program()

    @property
    def block(self):
        return self.main_program.current_block()

    def create_parameter(self, attr, shape, dtype, is_bias=False,
                         default_initializer: Optional[Initializer] = None
                         ) -> Parameter:
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        from .param_attr import WeightNormParamAttr
        if isinstance(attr, WeightNormParamAttr) and not is_bias:
            return self._create_weight_normed(attr, shape, dtype,
                                              default_initializer)
        suffix = "b" if is_bias else "w"
        name = attr.name or unique_name.generate(f"{self.name}.{suffix}")
        init = attr.initializer or default_initializer or (
            ConstantInitializer(0.0) if is_bias else XavierInitializer())
        shape = [int(s) for s in shape]
        # declare in main program (block 0) ...
        kw = ParamAttr(None, None, attr.learning_rate, attr.regularizer,
                       attr.trainable, attr.gradient_clip,
                       attr.sharding).to_kwargs()
        kw.pop("name", None)
        shared = isinstance(self.main_program.global_block().vars.get(name),
                            Parameter)
        p = self.block.create_parameter(name=name, shape=shape, dtype=dtype,
                                        **kw)
        if shared:      # a name that stands: drawn once, where it was made
            return p
        # ... and emit its initializer into the startup program
        sb = self.startup_program.global_block()
        sv = sb.create_var(name=name, shape=shape, dtype=dtype,
                           persistable=True)
        init(sv, sb)
        return p

    def _create_weight_normed(self, attr, shape, dtype,
                              default_initializer):
        """WeightNormParamAttr: trainable direction v and magnitude g with
        w = g * v/||v|| recomputed in-graph every step (fluid
        param_attr.py WeightNormParamAttr semantics)."""
        from .param_attr import ParamAttr as _PA
        base = _PA(name=attr.name, initializer=attr.initializer,
                   learning_rate=attr.learning_rate,
                   regularizer=attr.regularizer, trainable=attr.trainable,
                   gradient_clip=attr.gradient_clip, sharding=attr.sharding)
        v = self.create_parameter(base, shape, dtype,
                                  default_initializer=default_initializer)
        dim = attr.dim
        g_shape = [shape[dim]] if dim is not None else [1]
        g_attr = _PA(name=(attr.name + ".g") if attr.name else None,
                     initializer=ConstantInitializer(1.0),
                     learning_rate=attr.learning_rate,
                     trainable=attr.trainable)
        g = self.create_parameter(g_attr, g_shape, dtype)
        return _append_weight_norm_ops(self, v, g, dim, shape, dtype)

    def create_variable_for_type_inference(self, dtype, shape=None,
                                           lod_level=0) -> Variable:
        return self.block.create_var(
            name=unique_name.generate(f"{self.name}.tmp"), dtype=dtype,
            shape=shape, lod_level=lod_level)

    # fluid spelling
    create_tmp_variable = create_variable_for_type_inference

    def create_global_variable(self, shape, dtype, persistable=True,
                               name=None) -> Variable:
        gb = self.main_program.global_block()
        return gb.create_var(
            name=name or unique_name.generate(f"{self.name}.global"),
            shape=shape, dtype=dtype, persistable=persistable)

    def set_variable_initializer(self, var, initializer):
        sb = self.startup_program.global_block()
        sv = sb.create_var(name=var.name, shape=var.shape, dtype=var.dtype,
                           persistable=True)
        initializer(sv, sb)

    def append_op(self, **kwargs):
        return self.block.append_op(
            kwargs["type"], kwargs.get("inputs"), kwargs.get("outputs"),
            kwargs.get("attrs"))

    def append_bias_op(self, input_var: Variable, dim_start=1,
                       bias_attr=None, num_flatten_dims=None) -> Variable:
        bias_attr = self.kwargs.get("bias_attr", bias_attr)
        # reference parity: bias_attr=None means CREATE a default bias
        # (param_attr.py to_attr(None) -> ParamAttr()); only False disables
        if bias_attr is False:
            return input_var
        size = input_var.shape[-1] if input_var.shape else 1
        b = self.create_parameter(
            ParamAttr._to_attr(True if bias_attr is True else bias_attr),
            shape=[size], dtype=input_var.dtype, is_bias=True)
        out = self.create_variable_for_type_inference(
            input_var.dtype, input_var.shape,
            lod_level=input_var.lod_level)
        self.append_op(type="elementwise_add",
                       inputs={"X": [input_var], "Y": [b]},
                       outputs={"Out": [out]},
                       attrs={"axis": input_var.shape and len(input_var.shape) - 1 or -1})
        return out

    def append_activation(self, input_var: Variable, act=None) -> Variable:
        act = self.kwargs.get("act", act)
        if act is None:
            return input_var
        if isinstance(act, str):
            act = {"type": act}
        act_type = act.pop("type")
        out = self.create_variable_for_type_inference(
            input_var.dtype, input_var.shape,
            lod_level=input_var.lod_level)
        self.append_op(type=act_type, inputs={"X": [input_var]},
                       outputs={"Out": [out]}, attrs=act)
        return out

    def input_dtype(self, input_param_name="input"):
        v = self.kwargs.get(input_param_name)
        if isinstance(v, (list, tuple)):
            v = v[0]
        return v.dtype


def _append_weight_norm_ops(helper, v, g, dim, shape, dtype):
    """Emit w = g * v / ||v|| (norm over all dims except ``dim``) into the
    main program; grads flow to v and g via autodiff (fluid emulated this
    with a chain of norm/elementwise ops too, param_attr.py WeightNormParamAttr)."""
    sq = helper.create_variable_for_type_inference(dtype, tuple(shape))
    helper.append_op(type="square", inputs={"X": [v]},
                     outputs={"Out": [sq]}, attrs={})
    reduce_dims = [i for i in range(len(shape)) if i != (dim or 0)] \
        if dim is not None else list(range(len(shape)))
    norm_shape = [shape[dim]] if dim is not None else [1]
    ssum = helper.create_variable_for_type_inference(dtype, tuple(norm_shape))
    helper.append_op(type="reduce_sum", inputs={"X": [sq]},
                     outputs={"Out": [ssum]},
                     attrs={"dim": reduce_dims, "keep_dim": False})
    norm = helper.create_variable_for_type_inference(dtype, tuple(norm_shape))
    helper.append_op(type="sqrt", inputs={"X": [ssum]},
                     outputs={"Out": [norm]}, attrs={})
    scale = helper.create_variable_for_type_inference(dtype, tuple(norm_shape))
    helper.append_op(type="elementwise_div", inputs={"X": [g], "Y": [norm]},
                     outputs={"Out": [scale]}, attrs={"axis": -1})
    w = helper.create_variable_for_type_inference(dtype, tuple(shape))
    helper.append_op(type="elementwise_mul", inputs={"X": [v], "Y": [scale]},
                     outputs={"Out": [w]},
                     attrs={"axis": dim if dim is not None else 0})
    return w
