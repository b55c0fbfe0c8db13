"""What the Gluon training adapters share: the object a runner drives.

``TrainProgram`` wraps ONE compiled step with its state. Set-up drives it
through its first steps and the window goes on driving the same object.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import train_reference
from harness.loader import BenchError

F32 = jnp.float32


class TrainProgram:
    """A step ``(x, y) -> loss`` of the program, its parameters and its
    optimizer state under the reference's leaf names."""

    def __init__(self, step, names, param_of, state_of, optimizer, samples_per_step,
                 describe, place=None):
        self._step = self.entry = step   # entry: the program's own step object
        self._names = names              # reference leaf -> program parameter name
        self._param_of = param_of        # program name -> jax array, now
        self._state_of = state_of        # program name -> tuple of state arrays, now
        self._optimizer = optimizer
        self.samples_per_step = samples_per_step
        self.describe = describe
        self._recorded = None
        self._place = place
        self._norms = jax.jit(train_reference.norms)

    def record_next_step(self):
        """Keep the shapes, types and placements of every argument that the
        step's next call hands to its jitted function (``_jit`` on both entry
        points; it exists once the step has run), then step out of the way:
        the call after that goes straight to the program's own function."""
        step, jitted = self._step, self._step._jit
        if jitted is None:
            raise BenchError("the entry point holds no jitted step after its first call "
                             "(fallen back to eager: %s)"
                             % getattr(step, "fallback_reason", None))

        def aval(a):
            if isinstance(a, jax.Array):
                # an array that no one placed lowers as one that is free to move
                return jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.aval.weak_type,
                                            sharding=a.sharding if a.committed else None)
            return a  # a host scalar goes to the lowering as it went to the call

        def recording(*args, **kwargs):
            self._recorded = (jitted, jax.tree.map(aval, (args, kwargs)))
            step._jit = jitted
            return jitted(*args, **kwargs)

        step._jit = recording

    def compiled_step(self):
        """The executable the window drives, for the compiler's account of its
        memory: lowered from the arguments of a real call, so it is the program
        that ran and compiles nothing (the runner checks that it did not)."""
        jitted, (args, kwargs) = self._recorded
        return jitted.lower(*args, **kwargs).compile()

    def batch(self, x, y):
        """A batch of the reference's pool as the program's own arrays."""
        from mxnet_tpu import nd

        if self._place is not None:  # a mesh: each chip gets its rows once, here
            x, y = self._place(x), self._place(y)
        return nd.NDArray(x), nd.NDArray(y)

    def step(self, batch):
        """Dispatch one step; the loss comes back as the program's array."""
        return self._step(batch[0], batch[1])

    @staticmethod
    def wait(loss):
        jax.block_until_ready(loss.data)

    @staticmethod
    def loss_value(loss):
        return float(jnp.mean(loss.data.astype(F32)))

    def parameters(self):
        return {leaf: self._param_of(name) for leaf, name in self._names.items()}

    def first_gradient(self):
        """After the first step: the gradient as the optimizer got it, worked
        out from its state (new buffers: the next step donates the state)."""
        state = {leaf: self._state_of(name) for leaf, name in self._names.items()}
        opt = self._optimizer
        return jax.jit(lambda st: {k: train_reference.gradient_from_state(opt, v)
                                   for k, v in st.items()})(state)

    @staticmethod
    def zero_counts():
        """{name: count} of what must not have happened in the run (token-slots
        a router dropped, say), read once after the window: each is compared
        with 0. An adapter that counts such things defines its own."""
        return {}

    @staticmethod
    def after_window():
        """{name: number} that the program published over the run, for the
        per-layer readers (``run["program"]``). An adapter defines its own."""
        return {}

    def norms(self, tree):
        return {k: float(v) for k, v in jax.device_get(self._norms(tree)).items()}

    def delta_norms(self, start):
        now = self.parameters()
        diff = jax.jit(lambda a, b: train_reference.norms(
            {k: a[k].astype(F32) - b[k].astype(F32) for k in a}))(now, start)
        return {k: float(v) for k, v in jax.device_get(diff).items()}


def set_parameters(net_params, names, values):
    """Give the program the benchmark's seeded weights, leaf by leaf (the
    program has no bulk entry: PERF.md lists it)."""
    for leaf, name in names.items():
        p = net_params[name]
        if tuple(p.shape) != tuple(values[leaf].shape):
            raise ValueError("%s is %s in the program, %s in the reference"
                             % (name, p.shape, values[leaf].shape))
        p.set_data(values[leaf])
