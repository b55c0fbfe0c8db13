"""Legacy symbolic RNN cells (ref: python/mxnet/rnn/rnn_cell.py) — the
pre-Gluon API used with Module/BucketingModule. Cells compose Symbol
graphs with the reference's parameter naming ("%si2h_weight" etc.) so
checkpoints and bucketing flows port over.

Unroll here is plain Python composition — the whole unrolled sequence
lowers into ONE XLA program at bind time, which is exactly the fast
shape for this backend (a residual per-step launch is a host dispatch
each; a fused program pays it once)."""
from __future__ import annotations

from .. import initializer as init
from .. import symbol
from ..base import MXNetError

__all__ = ["RNNParams", "BaseRNNCell", "RNNCell", "LSTMCell", "GRUCell",
           "FusedRNNCell", "SequentialRNNCell", "BidirectionalRNNCell",
           "DropoutCell", "ModifierCell", "ZoneoutCell", "ResidualCell"]


class RNNParams:
    """Container for cell parameter Variables, created on first use
    (ref: rnn_cell.py — RNNParams)."""

    def __init__(self, prefix=""):
        self._prefix = prefix
        self._params = {}

    def get(self, name, **kwargs):
        name = self._prefix + name
        if name not in self._params:
            self._params[name] = symbol.var(name, **kwargs)
        return self._params[name]


def _normalize_sequence(length, inputs, layout, merge, in_layout=None):
    """Single merged Symbol <-> per-step list (ref: rnn_cell.py —
    _normalize_sequence). Returns (inputs, axis)."""
    assert inputs is not None, "unroll(inputs=None) is not supported"
    axis = (in_layout or layout).find("T")
    if isinstance(inputs, symbol.Symbol):
        if merge is False:
            if len(inputs.list_outputs()) != 1:
                raise MXNetError(
                    "unroll expects a single-output merged symbol")
            inputs = list(symbol.SliceChannel(
                inputs, axis=axis, num_outputs=length, squeeze_axis=1))
    else:
        if merge is True:
            inputs = [symbol.expand_dims(i, axis=axis) for i in inputs]
            inputs = symbol.Concat(*inputs, dim=axis)
    return inputs, axis


def _zeros_like_state(ref_sym, hidden, name):
    """(B, hidden) zeros derived from a (B, I) step symbol — shape-free,
    so bucketing graphs need no static batch size."""
    z1 = symbol.zeros_like(
        symbol.slice_axis(ref_sym, axis=1, begin=0, end=1))
    return symbol.tile(z1, reps=(1, hidden), name=name)


class BaseRNNCell:
    """Abstract symbolic cell (ref: rnn_cell.py — BaseRNNCell)."""

    def __init__(self, prefix="", params=None):
        if params is None:
            params = RNNParams(prefix)
            self._own_params = True
        else:
            self._own_params = False
        self._prefix = prefix
        self._params = params
        self._modified = False
        self.reset()

    def reset(self):
        self._init_counter = -1
        self._counter = -1

    def __call__(self, inputs, states):
        raise NotImplementedError

    @property
    def params(self):
        self._own_params = False
        return self._params

    @property
    def state_info(self):
        raise NotImplementedError

    @property
    def state_shape(self):
        return [info["shape"] for info in self.state_info]

    @property
    def _gate_names(self):
        return ()

    def begin_state(self, func=None, batch_size=0, **kwargs):
        """Initial-state symbols. With the default func, ``batch_size``
        must be given (concrete zeros); unroll's internal default uses a
        shape-free zeros-from-inputs construction instead."""
        assert not self._modified, \
            "After applying modifier cells the base cell cannot be called"
        states = []
        for info in self.state_info:
            self._init_counter += 1
            name = "%sbegin_state_%d" % (self._prefix, self._init_counter)
            # substitute batch_size at the layout's N axis (fused cells
            # carry (L*D, B, H) LNC states, not (B, H))
            shape = list(info["shape"])
            n_axis = info.get("__layout__", "NC").find("N")
            shape[n_axis] = batch_size
            shape = tuple(shape)
            if func is None:
                if batch_size <= 0:
                    raise MXNetError(
                        "begin_state() needs batch_size>0 for concrete "
                        "zeros; pass begin_state=None to unroll for the "
                        "shape-free default")
                states.append(symbol.zeros(shape=shape, name=name))
            else:
                states.append(func(name=name, shape=shape, **kwargs))
        return states

    def _default_begin_state(self, first_step):
        return [_zeros_like_state(
            first_step, info["shape"][-1],
            "%sbegin_state_%d" % (self._prefix, i))
            for i, info in enumerate(self.state_info)]

    # -- checkpoint interop (ref: rnn_cell.py unpack/pack) -------------
    def unpack_weights(self, args):
        """Fused/packed -> per-gate arg dict; plain cells pass through
        (ref: rnn_cell.py — BaseRNNCell.unpack_weights)."""
        return dict(args)

    def pack_weights(self, args):
        return dict(args)

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None):
        """Unrolls the cell over ``length`` steps
        (ref: rnn_cell.py — BaseRNNCell.unroll)."""
        self.reset()
        inputs, _ = _normalize_sequence(length, inputs, layout, False)
        if begin_state is None:
            begin_state = self._default_begin_state(inputs[0])
        states = begin_state
        outputs = []
        for i in range(length):
            output, states = self(inputs[i], states)
            outputs.append(output)
        outputs, _ = _normalize_sequence(length, outputs, layout,
                                         merge_outputs)
        return outputs, states

    def _get_activation(self, inputs, activation, **kwargs):
        if isinstance(activation, str):
            return symbol.Activation(inputs, act_type=activation, **kwargs)
        return activation(inputs, **kwargs)


class RNNCell(BaseRNNCell):
    """Elman cell (ref: rnn_cell.py — RNNCell)."""

    def __init__(self, num_hidden, activation="tanh", prefix="rnn_",
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_hidden = num_hidden
        self._activation = activation
        self._iW = self.params.get("i2h_weight")
        self._iB = self.params.get("i2h_bias")
        self._hW = self.params.get("h2h_weight")
        self._hB = self.params.get("h2h_bias")

    @property
    def state_info(self):
        return [{"shape": (0, self._num_hidden), "__layout__": "NC"}]

    def __call__(self, inputs, states):
        self._counter += 1
        name = "%st%d_" % (self._prefix, self._counter)
        i2h = symbol.FullyConnected(data=inputs, weight=self._iW,
                                    bias=self._iB,
                                    num_hidden=self._num_hidden,
                                    name="%si2h" % name)
        h2h = symbol.FullyConnected(data=states[0], weight=self._hW,
                                    bias=self._hB,
                                    num_hidden=self._num_hidden,
                                    name="%sh2h" % name)
        output = self._get_activation(i2h + h2h, self._activation,
                                      name="%sout" % name)
        return output, [output]


class LSTMCell(BaseRNNCell):
    """LSTM cell, gate order [i,f,c,o]; forget_bias goes into the
    i2h_bias initializer like the reference (ref: rnn_cell.py —
    LSTMCell)."""

    def __init__(self, num_hidden, prefix="lstm_", params=None,
                 forget_bias=1.0):
        super().__init__(prefix=prefix, params=params)
        self._num_hidden = num_hidden
        self._iW = self.params.get("i2h_weight")
        self._hW = self.params.get("h2h_weight")
        self._iB = self.params.get(
            "i2h_bias", init=init.LSTMBias(forget_bias=forget_bias))
        self._hB = self.params.get("h2h_bias")

    @property
    def state_info(self):
        return [{"shape": (0, self._num_hidden), "__layout__": "NC"},
                {"shape": (0, self._num_hidden), "__layout__": "NC"}]

    @property
    def _gate_names(self):
        return ("_i", "_f", "_c", "_o")

    def __call__(self, inputs, states):
        self._counter += 1
        name = "%st%d_" % (self._prefix, self._counter)
        i2h = symbol.FullyConnected(data=inputs, weight=self._iW,
                                    bias=self._iB,
                                    num_hidden=self._num_hidden * 4,
                                    name="%si2h" % name)
        h2h = symbol.FullyConnected(data=states[0], weight=self._hW,
                                    bias=self._hB,
                                    num_hidden=self._num_hidden * 4,
                                    name="%sh2h" % name)
        gates = i2h + h2h
        slice_gates = symbol.SliceChannel(gates, num_outputs=4,
                                          name="%sslice" % name)
        in_gate = symbol.Activation(slice_gates[0], act_type="sigmoid",
                                    name="%si" % name)
        forget_gate = symbol.Activation(slice_gates[1], act_type="sigmoid",
                                        name="%sf" % name)
        in_transform = symbol.Activation(slice_gates[2], act_type="tanh",
                                         name="%sc" % name)
        out_gate = symbol.Activation(slice_gates[3], act_type="sigmoid",
                                     name="%so" % name)
        next_c = forget_gate * states[1] + in_gate * in_transform
        next_h = out_gate * symbol.Activation(next_c, act_type="tanh")
        return next_h, [next_h, next_c]


class GRUCell(BaseRNNCell):
    """GRU cell, gate order [r,z,n] (ref: rnn_cell.py — GRUCell)."""

    def __init__(self, num_hidden, prefix="gru_", params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_hidden = num_hidden
        self._iW = self.params.get("i2h_weight")
        self._iB = self.params.get("i2h_bias")
        self._hW = self.params.get("h2h_weight")
        self._hB = self.params.get("h2h_bias")

    @property
    def state_info(self):
        return [{"shape": (0, self._num_hidden), "__layout__": "NC"}]

    @property
    def _gate_names(self):
        return ("_r", "_z", "_o")

    def __call__(self, inputs, states):
        self._counter += 1
        name = "%st%d_" % (self._prefix, self._counter)
        prev_h = states[0]
        i2h = symbol.FullyConnected(data=inputs, weight=self._iW,
                                    bias=self._iB,
                                    num_hidden=self._num_hidden * 3,
                                    name="%si2h" % name)
        h2h = symbol.FullyConnected(data=prev_h, weight=self._hW,
                                    bias=self._hB,
                                    num_hidden=self._num_hidden * 3,
                                    name="%sh2h" % name)
        i2h_r, i2h_z, i2h = symbol.SliceChannel(
            i2h, num_outputs=3, name="%si2h_slice" % name)
        h2h_r, h2h_z, h2h = symbol.SliceChannel(
            h2h, num_outputs=3, name="%sh2h_slice" % name)
        reset_gate = symbol.Activation(i2h_r + h2h_r, act_type="sigmoid",
                                       name="%sr_act" % name)
        update_gate = symbol.Activation(i2h_z + h2h_z, act_type="sigmoid",
                                        name="%sz_act" % name)
        next_h_tmp = symbol.Activation(i2h + reset_gate * h2h,
                                       act_type="tanh",
                                       name="%sh_act" % name)
        next_h = (1.0 - update_gate) * next_h_tmp + update_gate * prev_h
        return next_h, [next_h]


class FusedRNNCell(BaseRNNCell):
    """Whole-sequence fused cell over the RNN op (ref: rnn_cell.py —
    FusedRNNCell; cuDNN there, one fused XLA program here — same packed
    parameter layout as ops/rnn.py)."""

    def __init__(self, num_hidden, num_layers=1, mode="lstm",
                 bidirectional=False, dropout=0.0, get_next_state=False,
                 forget_bias=1.0, prefix=None, params=None):
        if prefix is None:
            prefix = "%s_" % mode
        super().__init__(prefix=prefix, params=params)
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._dropout = dropout
        self._get_next_state = get_next_state
        self._forget_bias = forget_bias
        self._parameter = self.params.get("parameters",
                                          init=init.Xavier(factor_type="in"))
        self._directions = 2 if bidirectional else 1

    @property
    def state_info(self):
        b = self._directions * self._num_layers
        n = 2 if self._mode == "lstm" else 1
        return [{"shape": (b, 0, self._num_hidden), "__layout__": "LNC"}
                for _ in range(n)]

    @property
    def _gate_names(self):
        return {"rnn_relu": ("",), "rnn_tanh": ("",),
                "lstm": ("_i", "_f", "_c", "_o"),
                "gru": ("_r", "_z", "_o")}[self._mode]

    @property
    def _num_gates(self):
        return len(self._gate_names)

    def __call__(self, inputs, states):
        raise NotImplementedError(
            "FusedRNNCell cannot be stepped — call unroll()")

    def _slice_weights(self, arr, li, lh):
        """Views over the packed vector in the ops/rnn.py layout (all
        weights, then all biases), named for the unfused cells
        ("%sl0_i2h_weight" = the full gate-stacked matrix)."""
        args = {}
        h, d, L = self._num_hidden, self._directions, self._num_layers
        g = self._num_gates
        p = 0
        for layer in range(L):
            in_sz = li if layer == 0 else lh * d
            for di in range(d):
                dname = ("l", "r")[di]
                args["%s%s%d_i2h_weight" % (self._prefix, dname, layer)] \
                    = arr[p:p + g * h * in_sz].reshape((g * h, in_sz))
                p += g * h * in_sz
                args["%s%s%d_h2h_weight" % (self._prefix, dname, layer)] \
                    = arr[p:p + g * h * h].reshape((g * h, h))
                p += g * h * h
        for layer in range(L):
            for di in range(d):
                dname = ("l", "r")[di]
                args["%s%s%d_i2h_bias" % (self._prefix, dname, layer)] \
                    = arr[p:p + g * h]
                p += g * h
                args["%s%s%d_h2h_bias" % (self._prefix, dname, layer)] \
                    = arr[p:p + g * h]
                p += g * h
        assert p == arr.shape[0], (p, arr.shape)
        return args

    def unpack_weights(self, args):
        from .. import ndarray as nd

        args = dict(args)
        pname = self._prefix + "parameters"
        if pname not in args:
            return args
        arr = args.pop(pname)
        h, d = self._num_hidden, self._directions
        g = self._num_gates
        total = arr.shape[0]
        # solve layer-0 input size from the packed length:
        # total = d*g*h*li + d*g*h*h + (L-1)*d*g*h*(h*d + h) + L*d*2*g*h
        deeper = sum(g * h * (h * d) + g * h * h
                     for _ in range(self._num_layers - 1)) * d
        biases = 2 * g * h * d * self._num_layers
        li = (total - biases - deeper - d * g * h * h) // (d * g * h)
        for name, view in self._slice_weights(arr, li, h).items():
            args[name] = view.copy() if hasattr(view, "copy") \
                else nd.array(view)
        return args

    def pack_weights(self, args):
        from .. import ndarray as nd
        import numpy as np

        args = dict(args)
        d, L = self._directions, self._num_layers
        chunks = []
        for layer in range(L):
            for di in range(d):
                dname = ("l", "r")[di]
                for kind in ("i2h", "h2h"):
                    chunks.append(args.pop(
                        "%s%s%d_%s_weight" % (
                            self._prefix, dname, layer, kind)))
        for layer in range(L):
            for di in range(d):
                dname = ("l", "r")[di]
                for kind in ("i2h", "h2h"):
                    chunks.append(args.pop(
                        "%s%s%d_%s_bias" % (
                            self._prefix, dname, layer, kind)))
        flat = np.concatenate(
            [c.asnumpy().reshape(-1) if hasattr(c, "asnumpy")
             else np.asarray(c).reshape(-1) for c in chunks])
        args[self._prefix + "parameters"] = nd.array(flat)
        return args

    def _fused_begin_state(self, data_tnc):
        # (L*D, B, H) zeros from the (T, B, I) data symbol, shape-free
        z = symbol.zeros_like(symbol.slice_axis(
            symbol.slice_axis(data_tnc, axis=0, begin=0, end=1),
            axis=2, begin=0, end=1))  # (1, B, 1)
        state = symbol.tile(
            z, reps=(self._directions * self._num_layers, 1,
                     self._num_hidden))
        n = 2 if self._mode == "lstm" else 1
        return [state] * n

    def _default_begin_state(self, first_step):
        # nested (Sequential/Bidirectional) composition hands a (B, I)
        # step symbol; lift it to the (L*D, B, H) LNC state the RNN op
        # needs
        z = symbol.expand_dims(symbol.zeros_like(symbol.slice_axis(
            first_step, axis=1, begin=0, end=1)), axis=0)  # (1, B, 1)
        state = symbol.tile(
            z, reps=(self._directions * self._num_layers, 1,
                     self._num_hidden))
        n = 2 if self._mode == "lstm" else 1
        return [state] * n

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None):
        self.reset()
        inputs, axis = _normalize_sequence(length, inputs, layout, True)
        if axis == 1:  # NTC -> the op's TNC
            inputs = symbol.swapaxes(inputs, dim1=0, dim2=1)
        if begin_state is None:
            begin_state = self._fused_begin_state(inputs)
        states = begin_state
        kwargs = {}
        if self._mode == "lstm":
            kwargs["state_cell"] = states[1]
        rnn = symbol.RNN(inputs, self._parameter, states[0],
                         state_size=self._num_hidden,
                         num_layers=self._num_layers,
                         bidirectional=self._bidirectional,
                         p=self._dropout, state_outputs=True,
                         mode=self._mode, name=self._prefix + "rnn",
                         **kwargs)
        outputs = rnn[0]
        if self._get_next_state:
            states = [rnn[1], rnn[2]] if self._mode == "lstm" else [rnn[1]]
        else:
            states = []
        if axis == 1:
            outputs = symbol.swapaxes(outputs, dim1=0, dim2=1)
        outputs, _ = _normalize_sequence(length, outputs, layout,
                                         merge_outputs)
        return outputs, states

    def unfuse(self):
        """Equivalent SequentialRNNCell of unfused cells
        (ref: rnn_cell.py — FusedRNNCell.unfuse)."""
        stack = SequentialRNNCell()
        get_cell = {
            "rnn_relu": lambda p: RNNCell(self._num_hidden,
                                          activation="relu", prefix=p),
            "rnn_tanh": lambda p: RNNCell(self._num_hidden,
                                          activation="tanh", prefix=p),
            "lstm": lambda p: LSTMCell(self._num_hidden, prefix=p,
                                       forget_bias=self._forget_bias),
            "gru": lambda p: GRUCell(self._num_hidden, prefix=p),
        }[self._mode]
        for i in range(self._num_layers):
            if self._bidirectional:
                stack.add(BidirectionalRNNCell(
                    get_cell("%sl%d_" % (self._prefix, i)),
                    get_cell("%sr%d_" % (self._prefix, i)),
                    output_prefix="%sbi_l%d_" % (self._prefix, i)))
            else:
                stack.add(get_cell("%sl%d_" % (self._prefix, i)))
            if self._dropout > 0 and i != self._num_layers - 1:
                stack.add(DropoutCell(
                    self._dropout, prefix="%s_dropout%d_" % (
                        self._prefix, i)))
        return stack


class SequentialRNNCell(BaseRNNCell):
    """Sequentially stacked cells (ref: rnn_cell.py)."""

    def __init__(self, params=None):
        super().__init__(prefix="", params=params)
        self._override_cell_params = params is not None
        self._cells = []

    def add(self, cell):
        self._cells.append(cell)
        if self._override_cell_params:
            assert cell._own_params, \
                "Either specify params for SequentialRNNCell or child " \
                "cells, not both."
            cell.params._params.update(self.params._params)
        self.params._params.update(cell.params._params)

    @property
    def state_info(self):
        return sum([c.state_info for c in self._cells], [])

    def begin_state(self, **kwargs):
        assert not self._modified
        return sum([c.begin_state(**kwargs) for c in self._cells], [])

    def _default_begin_state(self, first_step):
        return sum([c._default_begin_state(first_step)
                    for c in self._cells], [])

    def unpack_weights(self, args):
        for cell in self._cells:
            args = cell.unpack_weights(args)
        return args

    def pack_weights(self, args):
        for cell in self._cells:
            args = cell.pack_weights(args)
        return args

    def __call__(self, inputs, states):
        self._counter += 1
        next_states = []
        p = 0
        for cell in self._cells:
            assert not isinstance(cell, BidirectionalRNNCell), \
                "BidirectionalRNNCell must only be used with unroll"
            n = len(cell.state_info)
            state = states[p:p + n]
            p += n
            inputs, state = cell(inputs, state)
            next_states.extend(state)
        return inputs, next_states

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None):
        self.reset()
        num_cells = len(self._cells)
        if begin_state is None:
            # normalize once; the per-step list feeds both the state
            # probe and the first child's unroll (no duplicate slicing)
            inputs, _ = _normalize_sequence(length, inputs, layout, False)
            begin_state = self._default_begin_state(inputs[0])
        p = 0
        next_states = []
        for i, cell in enumerate(self._cells):
            n = len(cell.state_info)
            states = begin_state[p:p + n]
            p += n
            inputs, states = cell.unroll(
                length, inputs=inputs, begin_state=states, layout=layout,
                merge_outputs=None if i < num_cells - 1 else merge_outputs)
            next_states.extend(states)
        return inputs, next_states


class BidirectionalRNNCell(BaseRNNCell):
    """Runs two cells over the sequence in opposite directions
    (ref: rnn_cell.py — BidirectionalRNNCell)."""

    def __init__(self, l_cell, r_cell, params=None, output_prefix="bi_"):
        super().__init__(prefix="", params=params)
        self._output_prefix = output_prefix
        self._cells = [l_cell, r_cell]

    def unpack_weights(self, args):
        for cell in self._cells:
            args = cell.unpack_weights(args)
        return args

    def pack_weights(self, args):
        for cell in self._cells:
            args = cell.pack_weights(args)
        return args

    def __call__(self, inputs, states):
        raise NotImplementedError(
            "Bidirectional cannot be stepped. Please use unroll")

    @property
    def state_info(self):
        return sum([c.state_info for c in self._cells], [])

    def begin_state(self, **kwargs):
        assert not self._modified
        return sum([c.begin_state(**kwargs) for c in self._cells], [])

    def _default_begin_state(self, first_step):
        return sum([c._default_begin_state(first_step)
                    for c in self._cells], [])

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None):
        self.reset()
        inputs, axis = _normalize_sequence(length, inputs, layout, False)
        if begin_state is None:
            begin_state = self._default_begin_state(inputs[0])
        states = begin_state
        l_cell, r_cell = self._cells
        n_l = len(l_cell.state_info)
        l_outputs, l_states = l_cell.unroll(
            length, inputs=inputs, begin_state=states[:n_l], layout=layout,
            merge_outputs=False)
        r_outputs, r_states = r_cell.unroll(
            length, inputs=list(reversed(inputs)),
            begin_state=states[n_l:], layout=layout, merge_outputs=False)
        outputs = [symbol.Concat(l_o, r_o, dim=1,
                                 name="%st%d" % (self._output_prefix, i))
                   for i, (l_o, r_o) in enumerate(
                       zip(l_outputs, reversed(r_outputs)))]
        outputs, _ = _normalize_sequence(length, outputs, layout,
                                         merge_outputs)
        return outputs, l_states + r_states


class DropoutCell(BaseRNNCell):
    """Dropout on inputs (ref: rnn_cell.py — DropoutCell). train_mode is
    resolved at bind time by the executor's is_train flag."""

    def __init__(self, dropout, prefix="dropout_", params=None):
        super().__init__(prefix=prefix, params=params)
        assert isinstance(dropout, (int, float))
        self.dropout = dropout

    @property
    def state_info(self):
        return []

    def _default_begin_state(self, first_step):
        return []

    def __call__(self, inputs, states):
        if self.dropout > 0:
            inputs = symbol.Dropout(data=inputs, p=self.dropout)
        return inputs, states


class ModifierCell(BaseRNNCell):
    """Base for cells wrapping another cell (ref: rnn_cell.py)."""

    def __init__(self, base_cell):
        super().__init__()
        base_cell._modified = True
        self.base_cell = base_cell

    @property
    def params(self):
        self._own_params = False
        return self.base_cell.params

    @property
    def state_info(self):
        return self.base_cell.state_info

    def begin_state(self, **kwargs):
        assert not self._modified
        self.base_cell._modified = False
        begin = self.base_cell.begin_state(**kwargs)
        self.base_cell._modified = True
        return begin

    def _default_begin_state(self, first_step):
        return self.base_cell._default_begin_state(first_step)

    def unpack_weights(self, args):
        return self.base_cell.unpack_weights(args)

    def pack_weights(self, args):
        return self.base_cell.pack_weights(args)


class ZoneoutCell(ModifierCell):
    """Zoneout (ref: rnn_cell.py — ZoneoutCell; Krueger et al.
    1606.01305)."""

    def __init__(self, base_cell, zoneout_outputs=0.0, zoneout_states=0.0):
        assert not isinstance(base_cell, FusedRNNCell), \
            "FusedRNNCell does not support zoneout. Please unfuse first."
        assert not isinstance(base_cell, BidirectionalRNNCell), \
            "BidirectionalRNNCell does not support zoneout. " \
            "Please add ZoneoutCell to the cells underneath instead."
        super().__init__(base_cell)
        self.zoneout_outputs = zoneout_outputs
        self.zoneout_states = zoneout_states
        self.prev_output = None

    def reset(self):
        super().reset()
        self.prev_output = None

    def __call__(self, inputs, states):
        cell = self.base_cell
        p_outputs, p_states = self.zoneout_outputs, self.zoneout_states
        next_output, next_states = cell(inputs, states)

        def mask(p, like):
            return symbol.Dropout(symbol.ones_like(like), p=p)

        prev_output = self.prev_output if self.prev_output is not None \
            else symbol.zeros_like(next_output)
        output = symbol.where(mask(p_outputs, next_output), next_output,
                              prev_output) \
            if p_outputs != 0.0 else next_output
        states = [symbol.where(mask(p_states, new_s), new_s, old_s)
                  for new_s, old_s in zip(next_states, states)] \
            if p_states != 0.0 else next_states
        self.prev_output = output
        return output, states


class ResidualCell(ModifierCell):
    """Adds input to output (ref: rnn_cell.py — ResidualCell)."""

    def __call__(self, inputs, states):
        output, states = self.base_cell(inputs, states)
        output = symbol.elemwise_add(output, inputs,
                                     name="%s_plus_residual" % output.name)
        return output, states

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None):
        self.reset()
        self.base_cell._modified = False
        outputs, states = self.base_cell.unroll(
            length, inputs=inputs, begin_state=begin_state, layout=layout,
            merge_outputs=merge_outputs)
        self.base_cell._modified = True
        merge_outputs = isinstance(outputs, symbol.Symbol) \
            if merge_outputs is None else merge_outputs
        inputs, _ = _normalize_sequence(length, inputs, layout,
                                        merge_outputs)
        if merge_outputs:
            outputs = symbol.elemwise_add(outputs, inputs)
        else:
            outputs = [symbol.elemwise_add(o, i)
                       for o, i in zip(outputs, inputs)]
        return outputs, states
