"""Sharded training step over a Gluon block — SPMD data/tensor parallel
with ZeRO weight-update sharding and elastic mesh rebinding.

This is the TPU-native core that replaces the reference's entire
DataParallelExecutorGroup + KVStore push/pull machinery
(ref: python/mxnet/module/executor_group.py, src/kvstore/*): the whole
train step (forward, backward, optimizer) is ONE jitted XLA program over a
Mesh; gradient reduction across the data axis and any tensor-parallel
collectives are inserted by GSPMD and ride ICI.

Params live as jax arrays placed with NamedSharding; PartitionSpec rules
(regex on parameter name) give tensor parallelism, default is replicated
(pure data parallel). Aux states (BatchNorm running stats, MoE router
accounting) are carried as non-differentiated inputs and returned
updated — the same rebind-capture protocol as CachedOp (gluon/block.py —
_build_cached).

The mesh is not limited to dp×tp: the same step runs a full
dp×tp×pp×ep mesh (``make_mesh((2, 1, 2, 2), ("dp", "tp", "pp",
"ep"))`` or the launch line's ``--mesh 2,1,2,2 --mesh-axes
dp,tp,pp,ep``) where pipeline stages and MoE experts are RULE-SHARDED
stacked parameters and the schedule/routing are ordinary ops inside
this one donated program — parallel/unified.py builds such a block;
the step only sees more named axes. ZeRO eligibility stays a per-axis
decision: dim 0 must divide dp AND no rule may already shard the param
on any axis (tp/pp/ep exclusion); optimizer state for rule-sharded
params follows the weight's own layout instead.

The sharding annotations are END-TO-END (the SNIPPETS "8 chips to
6000-chip superclusters without changing application code" pattern): the
batch is pinned to the data axis and the loss to replicated INSIDE the
program, params/states carry explicit NamedSharding placements, and the
mesh itself may span processes (parallel.init_distributed + a launch-line
``--mesh``) — the training script is identical at 1 host and at N.

ZeRO weight-update sharding (Xu et al., arXiv:2004.13336) is a stage
ladder over the data axis, ``zero_stage=``:

====== ===================================================================
stage  per-device effect (eligible params: dim 0 divides dp, not already
       tensor-parallel-sharded by a rule)
====== ===================================================================
0      pure data parallel — everything replicated (the baseline).
1      optimizer states shard dim-0 over the data axis (~dp× less state
       memory); gradients still all-reduce replicated.
2      + gradients are pinned to the update sharding, so GSPMD fuses the
       dp all-reduce into reduce-scatter and each replica updates only
       its slice (the paper's full weight-update sharding; the legacy
       ``shard_update=True`` flag maps here).
3      + the params THEMSELVES live dim-0-sharded (~dp× less param
       memory); GSPMD all-gathers at use in the forward, FSDP-style.
====== ===================================================================

Every stage is numerically exact vs. stage 0 — only layout and collective
choice change, never the math (tests assert <=1e-6 over 5 steps).
"""
from __future__ import annotations

import json
import contextlib
import re
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .. import autograd as ag
from .. import random as _random
from ..ndarray.ndarray import NDArray
from ..gluon.block import Block, _trace_depth
from ..gluon.parameter import param_trace_scope
from ..profiler import setup_scope
from .mesh import make_mesh

__all__ = ["ShardedTrainStep", "shard_params", "sharding_rule",
           "allreduce_across_processes"]


def sharding_rule(*pairs):
    """Build a rule list: (name_regex, PartitionSpec) applied first-match."""
    return [(re.compile(pat), spec) for pat, spec in pairs]


def _spec_for(name, rules):
    if rules:
        for pat, spec in rules:
            if pat.search(name):
                return spec
    return P()  # replicated


def shard_params(params, mesh, rules=None, shardings=None):
    """Place Parameter buffers on the mesh per the rules (replicated
    unless a rule names a tensor-parallel layout), or per an explicit
    ``shardings`` {name: NamedSharding} map.

    Placements are BATCHED into one ``jax.device_put`` call and arrays
    whose layout already matches are skipped entirely — a resume or
    reshard pass over a mostly-placed model moves only what changed
    instead of blocking on a fresh transfer of every buffer (the old
    one-device_put-per-param loop re-transferred everything).
    Returns the number of arrays actually moved."""
    names, vals, targets = [], [], []
    for name, p in params.items():
        if shardings is not None:
            target = shardings[name]
        else:
            target = NamedSharding(mesh, _spec_for(name, rules))
        d = p.data().data
        cur = getattr(d, "sharding", None)
        # only an array that already lives on A MESH is in place: on a
        # one-device mesh a fresh (uncommitted, single-device) buffer is
        # "equivalent" to the target too, but the step returns it
        # mesh-placed, and that change of type after step 1 makes the
        # whole step program trace and compile a second time
        if isinstance(cur, NamedSharding) \
                and cur.is_equivalent_to(target, d.ndim):
            continue
        names.append(name)
        vals.append(d)
        targets.append(target)
    if not names:
        return 0
    with setup_scope("place"):
        for name, v in zip(names, jax.device_put(vals, targets)):
            params[name].data()._set_data(v)
    return len(names)


def _make_opt_update(optimizer, optimizer_params):
    """Per-tensor pure update fn + state-init, from the fused optimizer ops
    (the same kernels the eager Updater uses)."""
    from ..ops.registry import get_op

    hp = dict(optimizer_params or {})
    lr = hp.pop("learning_rate", 0.01)
    wd = hp.pop("wd", 0.0)
    momentum = hp.pop("momentum", 0.0)
    rescale = hp.pop("rescale_grad", 1.0)
    clip = hp.pop("clip_gradient", None)

    if optimizer == "sgd":
        if momentum:
            fn = get_op("sgd_mom_update").fn

            def init(w):
                return (jnp.zeros_like(w),)

            def update(w, g, s, t):
                w2, m2 = fn(w, g, s[0], lr=lr, momentum=momentum, wd=wd,
                            rescale_grad=rescale, clip_gradient=clip)
                return w2, (m2,)
        else:
            fn = get_op("sgd_update").fn

            def init(w):
                return ()

            def update(w, g, s, t):
                return fn(w, g, lr=lr, wd=wd, rescale_grad=rescale,
                          clip_gradient=clip), ()
    elif optimizer == "adam":
        beta1 = hp.pop("beta1", 0.9)
        beta2 = hp.pop("beta2", 0.999)
        eps = hp.pop("epsilon", 1e-8)
        fn = get_op("adam_update").fn

        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, s, t):
            # bias correction folded into lr, as the eager Adam does
            coef1 = 1.0 - beta1 ** t
            coef2 = 1.0 - beta2 ** t
            lr_t = lr * jnp.sqrt(coef2) / coef1
            w2, m2, v2 = fn(w, g, s[0], s[1], lr=lr_t, beta1=beta1,
                            beta2=beta2, epsilon=eps, wd=wd,
                            rescale_grad=rescale, clip_gradient=clip)
            return w2, (m2, v2)
    else:
        raise MXNetError(
            "ShardedTrainStep supports 'sgd' and 'adam'; got %r (use the "
            "eager Trainer for other optimizers)" % (optimizer,))
    return init, update


class ShardedTrainStep:
    """One-program SPMD training step for a Gluon block.

    Usage::

        mesh = parallel.make_mesh((dp, tp), ("data", "model"))
        step = ShardedTrainStep(net, loss_fn, "sgd",
                                {"learning_rate": 0.1}, mesh=mesh,
                                zero_stage=2,
                                rules=sharding_rule((r"dense\\d+_weight",
                                                     P("model", None))))
        loss = step(x_batch, y_batch)   # params update in place

    The batch is sharded along the mesh's data axis; XLA emits the grad
    psum over that axis (data parallel) and whatever collectives the rules
    imply (tensor parallel). ``zero_stage`` (0-3, module docstring) shards
    the weight update itself; the legacy ``shard_update=True`` maps to
    stage 2.

    The step also slots into the resilience/elasticity stack: it speaks
    the CheckpointManager ``trainer`` protocol (:meth:`save_states` /
    :meth:`load_states` restore onto the step's CURRENT mesh, whatever
    its shape), registers with ``tuning`` for AOT warm-start
    (:meth:`aot_warmup`), and can be re-homed onto a survivor mesh in
    place via :meth:`rebind_mesh` (parallel/reshard.py drives this when
    the membership reaper fences a host).
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, rules=None, data_axis="data", remat=None,
                 shard_update=False, zero_stage=None):
        """remat: None (save all intermediates — XLA default), "full"
        (recompute the whole forward in backward; ~1/3 more FLOPs for far
        less saved-activation HBM traffic — the jax.checkpoint analog of
        the reference's mirror/memonger), or any name from
        jax.checkpoint_policies (e.g. "dots_saveable").

        zero_stage: cross-replica weight-update sharding stage (0-3, see
        the module docstring); defaults to ``MXT_ZERO_STAGE`` (0 when
        unset). Params whose dim 0 doesn't divide the data axis (or that
        rules already shard) stay replicated at every stage, per the
        paper's fallback. ``shard_update=True`` is the legacy spelling
        of stage 2."""
        self.block = block
        self.loss_fn = loss_fn
        if remat not in (None, "full") and \
                not hasattr(jax.checkpoint_policies, str(remat)):
            valid = [n for n in dir(jax.checkpoint_policies)
                     if not n.startswith("_")]
            raise MXNetError(
                "unknown remat %r — use None, 'full', or one of %s"
                % (remat, valid))
        self._remat = remat
        if zero_stage is None:
            if shard_update:
                zero_stage = 2
            else:
                from .. import config

                zero_stage = int(config.get("MXT_ZERO_STAGE") or 0)
        zero_stage = int(zero_stage)
        if not 0 <= zero_stage <= 3:
            raise MXNetError(
                "zero_stage must be 0..3 (got %r)" % (zero_stage,))
        self.zero_stage = zero_stage
        self.mesh = mesh or make_mesh(axis_names=(data_axis,))
        if data_axis not in self.mesh.axis_names:
            if data_axis == "data":
                # the default name against a mesh that spells its axes
                # differently (the 4D launch convention dp,tp,pp,ep):
                # the FIRST mesh axis is the data axis by construction
                # (slowest-varying — make_mesh keeps dp outermost)
                data_axis = self.mesh.axis_names[0]
            else:
                raise MXNetError(
                    "mesh has no %r axis (axes: %s)"
                    % (data_axis, self.mesh.axis_names))
        self.data_axis = data_axis
        self._rules = rules
        # blocks that pin internal layouts (parallel/unified.py) resolve
        # their sharding axes against the step's LIVE mesh
        rebind = getattr(block, "rebind_mesh", None)
        if callable(rebind):
            rebind(self.mesh)
        self._all_params = OrderedDict(
            sorted(block.collect_params().items()))
        for name, p in self._all_params.items():
            if p._data is None:
                raise MXNetError(
                    "parameter %s is not initialized (run net.initialize() "
                    "and one eager forward for deferred shapes)" % name)
        self._train_names = [n for n, p in self._all_params.items()
                             if p.grad_req != "null"]
        self._aux_names = [n for n, p in self._all_params.items()
                           if p.grad_req == "null"]
        self._init_s, self._update = _make_opt_update(
            optimizer, optimizer_params)
        # derive placement + ZeRO shardings BEFORE creating states, so
        # sharded states are materialized directly at 1/dp size (a
        # replicated-then-reshard init would peak at the full footprint
        # per device, exactly the memory ZeRO exists to avoid)
        self._compute_shardings()
        shard_params(self._all_params, self.mesh,
                     shardings=self._param_shardings)
        self._states = {}
        for n in self._train_names:
            d = self._all_params[n].data().data
            # states materialize directly AT their storage sharding:
            # ZeRO-eligible params at 1/dp, rule-sharded (tp/pp/ep)
            # params matching the weight's own placement — never a
            # replicated-then-reshard peak
            sshard = self._state_shardings[n]
            n_state = len(jax.eval_shape(self._init_s, d))
            self._states[n] = jax.jit(
                self._init_s, out_shardings=(sshard,) * n_state)(d) \
                if n_state else ()
        # base RNG key is drawn lazily on the first step so a
        # mx.random.seed() between construction and training still takes
        # effect; per-step keys are then fold_in(base, t) ON DEVICE (a
        # host-side split per step is a separate executable launch)
        self._base_key = None
        # device-resident step counter, carried/donated through the jit.
        # Placed mesh-replicated from birth: the jit RETURNS it that way,
        # so an uncommitted initial value would change the argument
        # sharding between call 0 and call 1 and force a full recompile
        # of the step program on the second step.
        self._t_dev = jax.device_put(
            jnp.zeros((), jnp.int32),
            NamedSharding(self.mesh, P()))
        self._batch_cache = {}
        self._aot_compiled = {}  # (x sig, y sig) -> compiled (see _compile)
        self._last_sig = None
        self._ncalls = 0         # host dispatch counter (chaos timing)
        self._stream = None      # engine.StepStream (health staging only)
        self._health = False     # stat row compiled into the program
        self._health_mon = None  # health.HealthMonitor (retirement consumer)
        self._spike = False      # grad_spike chaos rule compiled in
        self._jit = self._build()
        from .. import tuning

        tuning.register_step(self)  # tuning.warmup() AOT-compiles us
        self._publish_mesh_telemetry()

    # ------------------------------------------------------------------
    # sharding derivation
    # ------------------------------------------------------------------
    def _compute_shardings(self):
        """(Re)derive per-parameter storage + ZeRO update shardings for
        the CURRENT mesh and stage. Called at build and again by
        rebind_mesh: a survivor mesh changes dp, so eligibility (dim-0
        divisibility) must be re-decided, never copied."""
        dp = self.mesh.shape[self.data_axis]
        train = set(self._train_names)
        self._param_shardings = {}
        self._zero_shardings = {n: None for n in self._train_names}
        self._state_shardings = {}
        for n, p in self._all_params.items():
            d = p.data().data
            spec = _spec_for(n, self._rules)
            # rule validation (typed, at derivation time — not a cryptic
            # XLA error at trace time): every named axis must exist on
            # THIS mesh and the spec must fit the tensor's rank, else a
            # 4D rule on a 2D mesh would silently replicate (or crash)
            for ax in tuple(spec):
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    if a is not None and a not in self.mesh.axis_names:
                        raise MXNetError(
                            "sharding rule for %s names mesh axis %r, "
                            "but the mesh has axes %s"
                            % (n, a, self.mesh.axis_names))
            if len(tuple(spec)) > d.ndim:
                raise MXNetError(
                    "sharding rule for %s has %d dims but the parameter "
                    "is rank %d" % (n, len(tuple(spec)), d.ndim))
            padded = tuple(spec) + (None,) * (d.ndim - len(tuple(spec)))
            zspec = None
            if (self.zero_stage >= 1 and n in train and d.ndim >= 1
                    and d.shape[0] % dp == 0
                    and not any(s is not None for s in padded)):
                zspec = P(self.data_axis, *padded[1:])
                self._zero_shardings[n] = NamedSharding(self.mesh, zspec)
            # ZeRO-3: the param ITSELF lives dim-0-sharded; GSPMD
            # all-gathers at use (FSDP-style). Stages 0-2 store per the
            # tensor-parallel rule (replicated by default).
            pspec = zspec if (self.zero_stage >= 3 and zspec is not None) \
                else spec
            self._param_shardings[n] = NamedSharding(self.mesh, pspec)
            if n in train:
                # optimizer state follows the UPDATE sharding when ZeRO
                # owns the param, else the weight's own storage layout —
                # a momentum/adam slot for a pp/ep-rule-sharded expert
                # weight must live sharded like the weight, never
                # silently replicated (the non-dp-axis regression)
                self._state_shardings[n] = self._zero_shardings[n] \
                    or self._param_shardings[n]

    def _batch_sharding(self, ndim):
        return NamedSharding(
            self.mesh, P(self.data_axis, *([None] * (ndim - 1))))

    # ------------------------------------------------------------------
    def _pure_loss(self, train_vals, aux_vals, x, y, key):
        """Forward + loss as a pure function; aux rebinds captured."""
        wrappers = {}
        for n, v in zip(self._train_names, train_vals):
            wrappers[n] = NDArray(v)
        for n, v in zip(self._aux_names, aux_vals):
            wrappers[n] = NDArray(v)
        mapping = {self._all_params[n]: w for n, w in wrappers.items()}
        _trace_depth.depth += 1
        try:
            with ag.pause(train_mode=True), _random.key_scope(key), \
                    param_trace_scope(mapping), jax.named_scope("forward"):
                out = Block.__call__(self.block, NDArray(x))
                loss = self.loss_fn(out, NDArray(y))
                loss = loss.mean()
        finally:
            _trace_depth.depth -= 1
        new_aux = tuple(
            jax.lax.stop_gradient(wrappers[n].data) for n in self._aux_names)
        return loss.data, new_aux

    def _loss_for_grad(self):
        if self._remat is None:
            return self._pure_loss
        if self._remat == "full":
            return jax.checkpoint(self._pure_loss)
        policy = getattr(jax.checkpoint_policies, self._remat)
        return jax.checkpoint(self._pure_loss, policy=policy)

    def _build(self):
        loss_fn = self._loss_for_grad()
        zero = [self._zero_shardings[n] for n in self._train_names]
        sshard = [self._state_shardings[n] for n in self._train_names]
        wshard = [self._param_shardings[n] for n in self._train_names]
        ashard = [self._param_shardings[n] for n in self._aux_names]
        stage = self.zero_stage
        replicated = NamedSharding(self.mesh, P())
        # training-health plane: the stat row and the grad_spike chaos
        # rule compile INTO the program at build (like the guard in the
        # single-host step); re-read on rebind_mesh's rebuild
        from .. import health as _health
        from .. import resilience as _resilience
        self._health = _health.enabled()
        health = self._health
        self._spike = _resilience.fault_point().rule("grad_spike") \
            is not None
        spike = self._spike
        train_names = self._train_names

        def step(train_vals, states, aux_vals, x, y, base_key, t,
                 spike_scale=1.0):
            # explicit end-to-end annotations (the GSPMD scale-out
            # contract): batch pinned to the data axis, loss replicated,
            # INSIDE the program — the same step placed on a 1-host or
            # an N-host mesh lays out identically with no script change.
            x = jax.lax.with_sharding_constraint(
                x, self._batch_sharding(x.ndim))
            y = jax.lax.with_sharding_constraint(
                y, self._batch_sharding(y.ndim))
            # RNG key and step count are derived ON DEVICE from the carried
            # t — one launch per step, no per-step host->device transfers.
            t = t + 1
            key = jax.random.fold_in(base_key, t)
            (loss, new_aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(train_vals, aux_vals, x, y, key)
            if spike:
                # seeded chaos: ONE layer's gradient scaled on device
                # (scale is 1.0 on every non-firing step)
                with jax.named_scope("grad_post"):
                    grads = _health.apply_grad_spike(grads, train_names,
                                                     spike_scale)
            loss = jax.lax.with_sharding_constraint(loss, replicated)
            # aux (BN running stats) pinned to their STORAGE sharding:
            # without this, ZeRO's sharded states pressure the GSPMD
            # solver into dim-0-sharding the aux outputs too, and the
            # layout change after step 1 forces a silent recompile
            new_aux = tuple(
                jax.lax.with_sharding_constraint(a, sh)
                for a, sh in zip(new_aux, ashard))
            new_train = []
            new_states = []
            for w, g, s, z, ss, ws in zip(train_vals, grads, states,
                                          zero, sshard, wshard):
                if z is not None and stage >= 2:
                    # ZeRO-2/3: pin the grad to the update sharding —
                    # GSPMD fuses the dp all-reduce into reduce-scatter
                    # and each replica updates only its slice
                    g = jax.lax.with_sharding_constraint(g, z)
                with jax.named_scope("optimizer"):
                    w2, s2 = self._update(w, g, s, t)
                # optimizer state stays pinned to its STORAGE sharding
                # across the update (ZeRO slice, or the weight's own
                # tp/pp/ep layout); the weight returns to ITS storage
                # (all-gather under ZeRO-1/2, stays dim-0-sharded under
                # ZeRO-3 where ws == z)
                s2 = tuple(
                    jax.lax.with_sharding_constraint(si, ss)
                    for si in s2)
                if z is not None:
                    w2 = jax.lax.with_sharding_constraint(w2, ws)
                new_train.append(w2)
                new_states.append(s2)
            if health:
                # per-layer stats packed ON DEVICE, replicated like the
                # loss: every host stages the identical small row into
                # its window, so per-host publication needs no gather
                row = _health.stat_row(loss, grads, train_vals,
                                       tuple(new_train))
                row = jax.lax.with_sharding_constraint(row, replicated)
                return (loss, tuple(new_train), tuple(new_states),
                        new_aux, t, row)
            return loss, tuple(new_train), tuple(new_states), new_aux, t

        if health and self._stream is None:
            from .. import engine

            # health stats ride a StepStream value channel: K steps of
            # rows cost ONE deferred read at retirement (and zero when
            # health is off — the stream itself only exists when armed)
            self._health_mon = _health.HealthMonitor(
                self._train_names, stream="sharded_step")
            self._stream = engine.StepStream(
                name="sharded_step", on_values=self._health_mon.consume)
        # params/states keep their placement; donate them so XLA reuses the
        # buffers (the static_alloc analog); t is donated too so the step
        # counter lives on device across steps
        return jax.jit(step, donate_argnums=(0, 1, 2, 6))

    # ------------------------------------------------------------------
    def _shard_batch(self, arr):
        data = arr.data if isinstance(arr, NDArray) else jnp.asarray(arr)
        sharding = self._batch_sharding(data.ndim)
        if getattr(data, "sharding", None) == sharding:
            return data
        # memoize by source buffer: train loops pass the same batch array
        # for many steps (and bench reuses one batch for all of them) —
        # re-sharding it every step burns host time for an identical result.
        # Only the latest (x, y) pair is kept: a bigger cache pins dropped
        # batches in HBM until eviction (they hold strong refs).
        cached = self._batch_cache.get(id(data))
        if cached is not None and cached[0] is data:
            return cached[1]
        if jax.process_count() > 1:
            # multi-host: every process holds its LOCAL slice of the
            # global batch; assemble the global array with no cross-host
            # transfer (each host feeds its own devices)
            out = jax.make_array_from_process_local_data(
                sharding, np.asarray(data))  # sync-ok: local batch is host data
        else:
            out = jax.device_put(data, sharding)
        while len(self._batch_cache) >= 2:
            self._batch_cache.pop(next(iter(self._batch_cache)))
        self._batch_cache[id(data)] = (data, out)
        return out

    def dump_hlo(self, x, y, path, optimized=True):
        """Write the step's HLO to ``path`` for offline analysis (the
        round-4 ResNet backward work: finding dgrad/wgrad layout copies
        needs the post-optimization module). optimized=False dumps the
        pre-optimization lowering instead. The AOT compile (one per
        process, shared with flops_per_step's accounting) is separate
        from the traced-call executable."""
        if optimized:
            compiled = self._compile(x, y)
            try:
                modules = compiled.runtime_executable().hlo_modules()
                text = "\n\n".join(m.to_string() for m in modules)
            except Exception:  # noqa: BLE001 — backend-dependent surface
                text = compiled.as_text()
        else:
            text = self._lower(x, y).as_text()
        with open(path, "w") as f:
            f.write(text)
        return path

    def _gather(self):
        """The exact (train, states, aux) operands __call__ passes —
        lowering helpers must stay in lockstep with execution."""
        train_vals = tuple(self._all_params[n].data().data
                           for n in self._train_names)
        aux_vals = tuple(self._all_params[n].data().data
                         for n in self._aux_names)
        states = tuple(self._states[n] for n in self._train_names)
        return train_vals, states, aux_vals

    def _lower(self, x, y):
        train_vals, states, aux_vals = self._gather()
        return self._jit.lower(
            train_vals, states, aux_vals, self._shard_batch(x),
            self._shard_batch(y), self._ensure_key(), self._t_dev)

    @staticmethod
    def _sig(a):
        d = a.data if isinstance(a, NDArray) else a
        return tuple(d.shape), str(d.dtype)

    def _compile(self, x, y):
        """AOT-compiled step, memoized per input signature so
        flops_per_step + dump_hlo share ONE compile."""
        key = (self._sig(x), self._sig(y))
        if key not in self._aot_compiled:
            self._aot_compiled[key] = self._lower(x, y).compile()
        return self._aot_compiled[key]

    def aot_warmup(self):
        """AOT-lower-and-compile the donated step program from the live
        parameter shapes + the last seen batch signature (falling back to
        the tuning table's recorded ``sharded_step`` signatures), so a
        resumed — or freshly RESHARDED — step pays its XLA compile here
        instead of inside the next training step. With a persistent
        compile cache the traced call then replays as a cache hit.
        Returns False when no batch signature is known yet."""
        sig = self._last_sig
        if sig is None:
            from .. import tuning

            dp = self.mesh.shape[self.data_axis]
            # only signatures whose batch divides THIS mesh's data axis
            # (the table may carry shapes recorded on another mesh)
            recorded = [s for s in tuning.signatures("sharded_step")
                        if s.get("x_shape") and s["x_shape"][0] % dp == 0]
            if not recorded:
                return False
            spec = recorded[-1]
            sig = ((tuple(spec["x_shape"]), spec["x_dtype"]),
                   (tuple(spec["y_shape"]), spec["y_dtype"]))
        (xs, xd), (ys, yd) = sig

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        train_vals, states, aux_vals = self._gather()
        lowered = self._jit.lower(
            jax.tree.map(sds, train_vals), jax.tree.map(sds, states),
            jax.tree.map(sds, aux_vals),
            jax.ShapeDtypeStruct(xs, xd,
                                 sharding=self._batch_sharding(len(xs))),
            jax.ShapeDtypeStruct(ys, yd,
                                 sharding=self._batch_sharding(len(ys))),
            self._ensure_key(), self._t_dev)
        self._aot_compiled[sig] = lowered.compile()
        return True

    def flops_per_step(self, x, y):
        """Total FLOPs of one compiled step per XLA cost analysis, or None
        if the backend doesn't report it. Used by bench.py for MFU."""
        # the compiled analysis: the TPU client offers no analysis of a
        # merely lowered module, and _compile is shared with dump_hlo
        cost = self._compile(x, y).cost_analysis() or {}
        return float(cost.get("flops", 0.0)) or None  # sync-ok: host cost dict

    def _ensure_key(self):
        if self._base_key is None:
            self._base_key = _random.new_key()
        return self._base_key

    def __call__(self, x, y):
        sig = (self._sig(x), self._sig(y))
        if sig != self._last_sig:
            self._last_sig = sig
            from .. import tuning

            # recorded signature -> a NEW process (warm resume) can AOT-
            # compile this step before its first batch ever arrives
            tuning.record_signature("sharded_step", {
                "x_shape": list(sig[0][0]), "x_dtype": sig[0][1],
                "y_shape": list(sig[1][0]), "y_dtype": sig[1][1]})
        train_vals, states, aux_vals = self._gather()
        # seeded chaos: scale is 1.0 except on the one firing dispatch
        # (same weak-float aval either way — no retrace)
        self._ncalls += 1
        spike_scale = 1.0
        if self._spike:
            from .. import health as _health
            spike_scale = _health.grad_spike_scale(self._ncalls)
        # the first call traces and compiles the step (or reads the cache)
        build = setup_scope("step_build") if self._ncalls == 1 \
            else contextlib.nullcontext()
        with build, jax.profiler.TraceAnnotation("mxt.step.dispatch",
                                                 step=self._ncalls):
            out = self._jit(
                train_vals, states, aux_vals, self._shard_batch(x),
                self._shard_batch(y), self._ensure_key(), self._t_dev,
                spike_scale)
        if self._health:
            loss, new_train, new_states, new_aux, self._t_dev, row = out
            # stats stage into the window: the ONE deferred read per K
            # steps at retirement covers them, the hot path reads nothing
            self._stream.push(loss, value=row)
        else:
            loss, new_train, new_states, new_aux, self._t_dev = out
        from .. import profiler
        profiler.record_launch()
        for n, v in zip(self._train_names, new_train):
            self._all_params[n].data()._set_data(v)
        for n, s in zip(self._train_names, new_states):
            self._states[n] = s
        for n, v in zip(self._aux_names, new_aux):
            self._all_params[n].data()._set_data(v)
        return NDArray(loss)

    # ------------------------------------------------------------------
    # memory accounting + telemetry
    # ------------------------------------------------------------------
    @property
    def step_count(self):
        """Completed optimizer steps. A host read of the carried device
        counter — a control-plane cursor for checkpoints/reshards, never
        read in the hot loop."""
        return int(self._t_dev)  # sync-ok: rare control-plane cursor read

    def per_device_bytes(self):
        """Bytes ONE device holds: ``{'param_bytes', 'opt_state_bytes'}``.
        Replicated tensors count full size per device; ZeRO/tp-sharded
        tensors count only the local shard — the quantity the ZeRO
        ladder shrinks ~dp× (bench's zero_stage_ab row asserts it)."""
        def dev0(a):
            return a.addressable_shards[0].data.nbytes

        params = sum(dev0(self._all_params[n].data().data)
                     for n in self._all_params)
        opt = sum(dev0(s) for n in self._train_names
                  for s in self._states[n])
        return {"param_bytes": int(params), "opt_state_bytes": int(opt)}

    def _publish_mesh_telemetry(self):
        """Mesh-shape / ZeRO / per-device-bytes gauges. mxt_top's mesh
        section renders only when these exist; reshards re-publish."""
        from .. import telemetry

        telemetry.gauge(
            "mxt_mesh_devices",
            "Devices in the active training mesh.").set(
                int(self.mesh.devices.size))
        ax = telemetry.gauge("mxt_mesh_axis_size",
                             "Mesh extent per named axis.", ("axis",))
        for name, size in self.mesh.shape.items():
            ax.labels(str(name)).set(int(size))
        telemetry.gauge(
            "mxt_zero_stage",
            "Active ZeRO weight-update sharding stage (0-3)."
        ).set(self.zero_stage)
        b = self.per_device_bytes()
        telemetry.gauge(
            "mxt_per_device_param_bytes",
            "Model parameter bytes held by ONE device (shrinks ~dp× "
            "under ZeRO-3).").set(b["param_bytes"])
        telemetry.gauge(
            "mxt_per_device_opt_bytes",
            "Optimizer-state bytes held by ONE device (shrinks ~dp× "
            "under ZeRO-1/2/3).").set(b["opt_state_bytes"])
        from .. import diagnostics

        # the HBM ledger tracks ONE device's working set (that is what
        # an OOM post-mortem must explain); reshards re-publish
        diagnostics.hbm_set("params", "sharded_step", b["param_bytes"])
        diagnostics.hbm_set("optimizer", "sharded_step",
                            b["opt_state_bytes"])

    # ------------------------------------------------------------------
    # checkpoint protocol (CheckpointManager's `trainer` slot) + reshard
    # ------------------------------------------------------------------
    def save_states(self, fname):
        """Optimizer states + step cursor + PRNG base key, in the
        CheckpointManager writer protocol (one path argument): a
        ShardedTrainStep slots straight into ``CheckpointManager`` as
        its ``trainer``, so sharded runs checkpoint through the same
        CRC-manifested atomic machinery as eager ones. Shards are
        gathered to host numpy — the checkpoint IS the cross-mesh
        transfer format the elastic reshard path rides."""
        arrays = {}
        for n in self._train_names:
            for i, s in enumerate(self._states[n]):
                arrays["s:%d:%s" % (i, n)] = np.asarray(s)  # sync-ok: checkpoint spill
        if self._base_key is not None:
            arrays["base_key"] = np.asarray(  # sync-ok: control-plane key snapshot
                jax.random.key_data(self._base_key))
        meta = {"t": self.step_count, "zero_stage": self.zero_stage,
                "mesh": {str(k): int(v)
                         for k, v in self.mesh.shape.items()}}
        arrays["__meta__"] = np.frombuffer(
            json.dumps(meta).encode("utf-8"), dtype=np.uint8)
        # open file handle: np.savez(path) appends .npz, which would
        # break CheckpointManager's tmp -> os.replace publish
        with open(fname, "wb") as f:
            np.savez(f, **arrays)

    def load_states(self, fname):
        """Inverse of :meth:`save_states` onto the CURRENT mesh: every
        state shard is re-placed per THIS step's (possibly different)
        dp×tp layout — a checkpoint written on an 8-device mesh restores
        onto a 6-device survivor mesh with no renormalization. Params
        (which CheckpointManager reloads just before this, replicated on
        the default device) are re-placed too; already-correct buffers
        are skipped."""
        with open(fname, "rb") as f:
            data = np.load(f)
            blob = {k: data[k] for k in data.files}
        meta = json.loads(blob.pop("__meta__").tobytes().decode("utf-8"))
        key_data = blob.pop("base_key", None)
        per = {n: {} for n in self._train_names}
        for k, v in blob.items():
            _, i, n = k.split(":", 2)
            if n not in per:
                raise MXNetError(
                    "sharded state checkpoint names unknown parameter %r"
                    % n)
            per[n][int(i)] = v
        replicated = NamedSharding(self.mesh, P())
        for n in self._train_names:
            vals = [per[n][i] for i in sorted(per[n])]
            if not vals:
                self._states[n] = ()
                continue
            # state storage sharding, NOT `zero or replicated`: a state
            # for a pp/ep/tp-rule-sharded weight re-places onto the
            # weight's layout (the old fallback silently replicated it,
            # dp×-ing its per-device bytes on every restore)
            z = self._state_shardings[n]
            self._states[n] = tuple(jax.device_put(vals, [z] * len(vals)))
        if key_data is not None:
            self._base_key = jax.random.wrap_key_data(
                jnp.asarray(key_data))
        self._t_dev = jax.device_put(
            jnp.asarray(int(meta["t"]), jnp.int32), replicated)
        shard_params(self._all_params, self.mesh,
                     shardings=self._param_shardings)
        self._batch_cache.clear()
        self._publish_mesh_telemetry()

    def rebind_mesh(self, new_mesh, transfer=True):
        """Re-home this step on a different mesh in place (the elastic
        reshard primitive). Recomputes every sharding for the new dp×tp
        shape (ZeRO eligibility is re-decided for the new dp), rebuilds
        the donated step program, and — with ``transfer=True`` — moves
        live params/optimizer state device-to-device. ``transfer=False``
        leaves value movement to a CheckpointManager restore: the spill
        path reshard.reshard_step uses when the old mesh's hosts may be
        dead (their buffers unreachable)."""
        if new_mesh.axis_names != self.mesh.axis_names:
            raise MXNetError(
                "rebind_mesh must keep the axis names (%s -> %s)"
                % (self.mesh.axis_names, new_mesh.axis_names))
        self.mesh = new_mesh
        rebind = getattr(self.block, "rebind_mesh", None)
        if callable(rebind):
            # mesh-aware blocks (parallel/unified.py) re-resolve their
            # internal sharding constraints against the survivor mesh
            rebind(new_mesh)
        self._compute_shardings()
        replicated = NamedSharding(self.mesh, P())
        if transfer:
            shard_params(self._all_params, self.mesh,
                         shardings=self._param_shardings)
            for n in self._train_names:
                ss = list(self._states[n])
                if ss:
                    z = self._state_shardings[n]
                    self._states[n] = tuple(
                        jax.device_put(ss, [z] * len(ss)))
            self._t_dev = jax.device_put(self._t_dev, replicated)
            if self._base_key is not None:
                self._base_key = jax.device_put(self._base_key, replicated)
        self._batch_cache.clear()
        self._aot_compiled.clear()
        self._jit = self._build()
        self._publish_mesh_telemetry()
        return self


def allreduce_across_processes(value):
    """Sum an array across processes (used by the dist kvstore facade).
    Single-process: identity."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    sparse_stype = None
    if getattr(value, "stype", "default") != "default":
        # workers' index sets differ, so positional allgather of value
        # blocks would sum misaligned rows — reduce densely, re-sparsify
        sparse_stype = value.stype
        value = value.tostype("default")
    data = value.data if isinstance(value, NDArray) else value
    gathered = multihost_utils.process_allgather(data)
    # materialize on host: the allgather result is a GLOBAL (replicated)
    # array, and letting it flow into single-device NDArray ops trips
    # "Cannot reshard an input that is not fully addressable" — a host
    # copy re-enters as a plain process-local array
    out = jnp.asarray(np.asarray(gathered).sum(axis=0))  # sync-ok: host re-entry
    if sparse_stype is not None:
        from ..sparse import cast_storage
        return cast_storage(NDArray(out), sparse_stype)
    return NDArray(out) if isinstance(value, NDArray) else out
