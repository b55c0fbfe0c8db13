"""CachedTrainStep — the canonical Gluon train loop as ONE donated launch.

The reference's canonical loop

    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(batch_size)

pays one XLA launch for the hybridized forward, one per tape node for the
backward vjp walk (autograd.py — _run_backward), and one for the fused
optimizer update (gluon/trainer.py — _FusedUpdate). Each launch is a
host dispatch, and for small steps the backward walk's dispatches alone
dominate. ShardedTrainStep (parallel/sharded.py) already proves whole-step
fusion with buffer donation works here; CachedTrainStep brings the same
treatment to the single-device canonical path without asking the user to
leave the Gluon API: forward + loss + `jax.value_and_grad` over the
flattened parameter pytree + the per-parameter optimizer math
(`_FusedUpdate._param_update`, the exact kernels the eager Updater runs)
compile into ONE `jax.jit` program with weights, optimizer state, and aux
state donated. XLA's fuser then does the heavy lifting across the whole
step ("Operator Fusion in XLA", arXiv:2301.13062); donation gives the
in-place weight-update behavior of the weight-update treatment in
arXiv:2004.13336 on a single chip.

Aux states (BatchNorm running stats) ride the CachedOp rebind protocol
(gluon/block.py — _build_cached): the traced Parameter wrappers are
inspected after the forward and whatever they rebound to is returned as
extra (donated-in, written-back) outputs. The PRNG key is derived ON
DEVICE via fold_in(base_key, t), and all dynamic scalars (t, lr, wd,
rescale_grad) enter as traced 0-d arguments, so lr schedulers never
retrace.

Ineligible configurations (unsupported optimizer, sparse grads, dist
kvstore, multi-process, grad_req='add') fall back transparently to the
eager record/backward/step loop — same numerics, more launches. Gate:
``MXT_FUSED_STEP`` (default on, mirrors ``MXT_FUSED_TRAINER``).

With ``MXT_SKIP_NONFINITE=1`` the resilience non-finite guard compiles
INTO the program (resilience.py): a ``lax.cond`` makes the whole
weight/state/aux update the identity when any gradient is non-finite and
the step counter stays put. The flag is NOT read back per step: the step
count rides the program as a donated device scalar and the last 31 flags
as a device bitmask, so the host dispatches up to ``MXT_MAX_INFLIGHT``
steps ahead (engine.StepStream) and ONE deferred mask read retires a
whole window's bookkeeping — update counts, ``LossScaler.update_scale``,
the ``skipped_nonfinite_steps`` counter — without ever touching the
weights path (the skip is on-device, so numerics are bit-exact at any
window depth). An ``lr_scheduler`` makes the learning rate depend on the
data-dependent step count, so guard + scheduler forces the window to 1
(the pre-async per-step read).
"""
from __future__ import annotations

import time
from collections import OrderedDict

import jax

from ..base import MXNetError
from .. import autograd as ag
from .. import optimizer as opt
from .. import random as _random
from ..ndarray.ndarray import NDArray
from ..ndarray import ndarray as _nd
from .block import Block, _trace_depth
from .parameter import param_trace_scope
from .trainer import _FusedUpdate

__all__ = ["CachedTrainStep", "train_step", "FusedApply"]


def _config():
    from .. import config
    return config


def _count_launch():
    from .. import profiler
    profiler.record_launch()


class CachedTrainStep:
    """One donated XLA launch per training step for a Gluon block.

    Usage::

        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 1e-3})
        step = trainer.fuse_step(net, loss_fn)   # or gluon.train_step(...)
        for x, y in loader:
            loss = step(x, y)                    # params update in place

    ``step(x, y, batch_size=None)`` is numerically identical to the
    canonical record/backward/step loop with ``batch_size`` defaulting to
    ``x.shape[batch_axis]`` (the gradient seed is ones over the loss —
    exactly what ``loss.backward()`` does — and the optimizer rescales by
    ``trainer._scale / batch_size``, exactly what ``trainer.step`` does).
    The returned loss has the same shape ``loss_fn`` produces.

    With ``return_outputs=True`` each call returns ``(loss, outputs)`` so
    metrics can be fed without a second forward — the outputs are extra
    results of the same single program, not another launch.

    Eligibility is decided once, lazily, on the first call (the trainer's
    kvstore decision and deferred parameter shapes must be resolved
    first); an ineligible config records ``fallback_reason`` and every
    call runs the eager loop instead — no exception, no retrace loop.
    A step that cannot run fused for transient reasons (uneven optimizer
    update counts left by a prior eager/kvstore path) also falls back,
    per step, and re-enters the fused path once counts are even again.
    """

    def __init__(self, net, loss_fn, trainer, batch_axis=0,
                 return_outputs=False):
        self._net = net
        self._loss_fn = loss_fn
        self._trainer = trainer
        self._batch_axis = batch_axis
        self._return_outputs = return_outputs
        self._jit = None
        self._fallback_reason = None
        self._base_key = None
        self._all_params = None
        self._train_names = None
        self._aux_names = None
        self._indices = None
        self._guard = False
        self._built_opt = None
        self._stream = None      # engine.StepStream (async dispatch window)
        self._t_dev = None       # device-carried step count (guard mode)
        self._mask_dev = None    # device-carried flag bitmask (guard mode)
        self._health = False     # stat row compiled into the program
        self._health_mon = None  # health.HealthMonitor (retirement consumer)
        self._spike = False      # grad_spike chaos rule compiled in
        self._hyper_cache = None  # (lr, wd, float(lr), float(wd))
        self._sig_recorded = False  # (x, y) signature saved for warmup
        self._hbm_published = False  # params/opt bytes in the HBM ledger
        self._committed = False  # donated buffers pinned to their device

    # -- introspection ---------------------------------------------------
    @property
    def fused(self):
        """True once the fused program is built (first call succeeded)."""
        return self._jit is not None

    @property
    def fallback_reason(self):
        """Why the fused path is permanently unavailable (None if fused
        or not yet decided)."""
        return self._fallback_reason

    # -- eligibility -----------------------------------------------------
    @staticmethod
    def eligible(trainer, net):
        """Reason string if the whole-step fusion cannot be used, else
        None. Mirrors _FusedUpdate.eligible plus whole-step-specific
        constraints (grad_req='write' only; trainer params == net
        params). Call after the trainer's kvstore is initialized."""
        o = trainer._optimizer
        if not _config().get("MXT_FUSED_STEP"):
            return "MXT_FUSED_STEP=0"
        if type(o).__name__ not in _FusedUpdate._SUPPORTED or \
                type(o).__module__ != opt.Optimizer.__module__:
            return "optimizer %s has no fused whole-step builder" \
                % type(o).__name__
        if getattr(o, "multi_precision", False):
            return "multi_precision optimizer"
        if getattr(o, "aggregate_num", 0):
            return "aggregate_num optimizer"
        if trainer._update_on_kvstore:
            return "update_on_kvstore"
        kv = trainer._kvstore
        if kv is not None and (kv.type.startswith("dist") or
                               trainer._compression_params):
            return "distributed/compressed kvstore"
        if jax.process_count() > 1:
            return "multi-process"
        net_params = net.collect_params()
        trainable = {n for n, p in net_params.items()
                     if p.grad_req != "null"}
        for name, p in net_params.items():
            # mesh-sharded buffers (parallel.ShardedTrainStep placed them
            # with a multi-device NamedSharding) must not be DONATED into
            # this single-device program: XLA would silently gather them
            # back to one device and the next sharded step would pay a
            # full re-placement — the two step builders own disjoint nets
            d = p._data
            if d is not None:
                sh = getattr(d.data, "sharding", None)
                if sh is not None and len(getattr(sh, "device_set",
                                                  ())) > 1:
                    return "parameter %s is mesh-sharded (%d devices) — " \
                        "parallel.ShardedTrainStep owns sharded nets" \
                        % (name, len(sh.device_set))
        for name, p in net_params.items():
            if p.grad_req == "null":
                continue
            if p.grad_req != "write":
                return "grad_req=%r on %s (whole-step fusion computes " \
                    "fresh grads; accumulation needs the eager loop)" \
                    % (p.grad_req, name)
            if getattr(p, "_grad_stype", "default") != "default":
                return "sparse gradient on %s" % name
            if name not in trainer._param2idx:
                return "parameter %s not managed by this trainer" % name
        for p in trainer._params:
            if p.grad_req != "null" and p.name not in trainable:
                return "trainer manages parameter %s outside the net" \
                    % p.name
        return None

    # -- build -----------------------------------------------------------
    def _build(self, x):
        net, tr = self._net, self._trainer
        # resolve deferred shapes with one throwaway eager forward in
        # predict mode (the HybridBlock._ensure_initialized treatment,
        # generalized to plain Blocks)
        if any(p._deferred_init is not None
               for p in net.collect_params().values()):
            from ..profiler import setup_scope

            with setup_scope("infer_shapes"), ag.pause(train_mode=False):
                _trace_depth.depth += 1
                try:
                    net(x)
                finally:
                    _trace_depth.depth -= 1
        self._all_params = OrderedDict(sorted(net.collect_params().items()))
        for name, p in self._all_params.items():
            if p._data is None:
                raise MXNetError(
                    "parameter %s is not initialized (run net.initialize() "
                    "before the first step)" % name)
        self._train_names = [n for n, p in self._all_params.items()
                             if p.grad_req != "null"]
        self._aux_names = [n for n, p in self._all_params.items()
                           if p.grad_req == "null"]
        self._indices = [tr._param2idx[n] for n in self._train_names]

        o = tr._optimizer
        self._built_opt = o
        # the guard compiles INTO the program, so the flag is read once
        # at build time (toggling the env later needs a fresh step fn)
        self._guard = bool(_config().get("MXT_SKIP_NONFINITE"))
        guard = self._guard
        # the health stat row and the grad_spike chaos rule compile INTO
        # the program too (same read-at-build contract as the guard)
        from .. import health as _health
        from .. import resilience as _resilience

        self._health = _health.enabled()
        health = self._health
        self._spike = _resilience.fault_point().rule("grad_spike") \
            is not None
        spike = self._spike
        upds = [_FusedUpdate._param_update(o, i) for i in self._indices]
        all_params = self._all_params
        train_names, aux_names = self._train_names, self._aux_names
        loss_fn = self._loss_fn

        def pure_loss(train_vals, aux_vals, xv, yv, key):
            """Forward + loss as a pure function of the param pytree; aux
            rebinds (BatchNorm running stats) captured via the CachedOp
            protocol (block.py — _build_cached)."""
            wrappers = {}
            for n, v in zip(train_names, train_vals):
                wrappers[n] = NDArray(v)
            for n, v in zip(aux_names, aux_vals):
                wrappers[n] = NDArray(v)
            mapping = {all_params[n]: w for n, w in wrappers.items()}
            _trace_depth.depth += 1
            try:
                with ag.pause(train_mode=True), _random.key_scope(key), \
                        param_trace_scope(mapping), \
                        jax.named_scope("forward"):
                    out = Block.__call__(net, NDArray(xv))
                    outs = list(out) if isinstance(out, (list, tuple)) \
                        else [out]
                    loss = loss_fn(outs[0] if len(outs) == 1 else outs,
                                   NDArray(yv))
            finally:
                _trace_depth.depth -= 1
            new_aux = tuple(jax.lax.stop_gradient(wrappers[n].data)
                            for n in aux_names)
            out_datas = tuple(jax.lax.stop_gradient(o_.data)
                              for o_ in outs)
            # grad of the SUM == the implicit all-ones seed loss.backward()
            # uses; rescale_grad (1/batch) is applied inside the update
            return loss.data.sum(), (loss.data, new_aux, out_datas)

        if not guard:
            def step(train_vals, states, aux_vals, xv, yv, base_key, t, lr,
                     wd, rescale, spike_scale=1.0):
                # per-step key derived on device: no host-side split launch
                key = jax.random.fold_in(base_key, t)
                (_, (loss_vec, new_aux, outs)), grads = jax.value_and_grad(
                    pure_loss, has_aux=True)(train_vals, aux_vals, xv, yv,
                                             key)
                if spike:
                    # seeded chaos: ONE layer's gradient scaled on device
                    # (scale is 1.0 on every non-firing step)
                    with jax.named_scope("grad_post"):
                        grads = _health.apply_grad_spike(
                            grads, train_names, spike_scale)
                new_train, new_states = [], []
                with jax.named_scope("optimizer"):
                    for f, w, g, s in zip(upds, train_vals, grads, states):
                        w2, s2 = f(w, g, s, t, lr, wd, rescale)
                        new_train.append(w2)
                        new_states.append(s2)
                if health:
                    # per-layer stats packed INSIDE the program — staged
                    # into the window, never read per step
                    row = _health.stat_row(loss_vec, grads, train_vals,
                                           new_train)
                    return (loss_vec, tuple(new_train),
                            tuple(new_states), new_aux, outs, row)
                return (loss_vec, tuple(new_train), tuple(new_states),
                        new_aux, outs)
        else:
            # non-finite step guard (resilience.py): the all-finite check
            # and the identity-on-overflow update are part of THIS program
            # — zero extra launches. The step count t is CARRIED on device
            # (advances only when the step applied) and the flag lands in
            # a carried bitmask (newest step = bit 0) instead of being
            # read back per step: the engine's in-flight window reads the
            # mask once per K steps and replays the bits into host
            # bookkeeping. aux (BatchNorm stats) also roll back so a NaN
            # forward never pollutes the running statistics.
            def step(train_vals, states, aux_vals, xv, yv, base_key, t,
                     mask, lr, wd, rescale, spike_scale=1.0):
                import jax.numpy as jnp

                t_upd = t + 1  # the count this update applies at
                key = jax.random.fold_in(base_key, t_upd)
                (_, (loss_vec, new_aux, outs)), grads = jax.value_and_grad(
                    pure_loss, has_aux=True)(train_vals, aux_vals, xv, yv,
                                             key)
                if spike:
                    # seeded chaos: ONE layer's gradient scaled on device
                    # (scale is 1.0 on every non-firing step)
                    with jax.named_scope("grad_post"):
                        grads = _health.apply_grad_spike(
                            grads, train_names, spike_scale)

                def _apply(_):
                    new_train, new_states = [], []
                    with jax.named_scope("optimizer"):
                        for f, w, g, s in zip(upds, train_vals, grads,
                                              states):
                            w2, s2 = f(w, g, s, t_upd, lr, wd, rescale)
                            new_train.append(w2)
                            new_states.append(s2)
                    return tuple(new_train), tuple(new_states), new_aux

                def _skip(_):
                    return (tuple(train_vals), tuple(states),
                            tuple(aux_vals))

                with jax.named_scope("grad_post"):
                    finite = jnp.bool_(True)
                    for g in grads:
                        finite = jnp.logical_and(finite,
                                                 jnp.isfinite(g).all())
                new_train, new_states, kept_aux = jax.lax.cond(
                    finite, _apply, _skip, None)
                t_new = t + jnp.where(finite, 1, 0)
                mask_new = (mask << 1) | jnp.where(finite, 0, 1)
                if health:
                    # the guard bit rides the row's last column, so one
                    # stacked read retires flags AND stats together
                    row = _health.stat_row(loss_vec, grads, train_vals,
                                           new_train, mask=mask_new)
                    return (loss_vec, new_train, new_states, kept_aux,
                            outs, t_new, mask_new, row)
                return (loss_vec, new_train, new_states, kept_aux, outs,
                        t_new, mask_new)

        # weights + optimizer state + aux donated: buffers are reused
        # across steps (the static_alloc analog) and the Parameter
        # wrappers rebind to the outputs
        self._jit = jax.jit(step, donate_argnums=(0, 1, 2))
        from .. import engine, tuning
        if health:
            # stats ride the window's value channel: in guard mode the
            # row's last column carries the guard bit, so the SAME one
            # deferred read per K steps retires flags and stats together
            self._health_mon = _health.HealthMonitor(
                self._train_names, stream="fused_step",
                guard_hook=(lambda: self._consume_flag(False))
                if guard else None)
            on_values = self._consume_health_row
            on_flags = None
        else:
            on_values = None
            on_flags = self._consume_flag if guard else None
        self._stream = engine.StepStream(
            name="fused_step", on_flags=on_flags, on_values=on_values)
        tuning.register_step(self)  # bare tuning.warmup() AOT-compiles us

    # -- per-step host path ------------------------------------------------
    def _consume_flag(self, finite):
        """Land ONE step's deferred guard flag into host bookkeeping —
        called from the engine window's retirement (in dispatch order),
        possibly several steps after the launch."""
        o = self._built_opt
        if finite:
            for i in self._indices:
                o._update_count(i)
        else:
            from .. import resilience
            resilience.record_skipped_step()
        scaler = getattr(self._trainer, "_amp_scaler", None)
        if scaler is not None:
            # dynamic loss-scale backoff driven from the same flag,
            # consumed from the trailing window
            scaler.update_scale(not finite)

    def _consume_health_row(self, step_no, row):
        """Land ONE retired step's stat row (and, in guard mode, its
        guard bit — packed as the row's last column so the stacked
        window read covers both) into host bookkeeping."""
        if self._guard:
            # bit 0 of the step's mask rode the row as 0.0/1.0 exactly
            self._consume_flag(float(row[-1]) == 0.0)  # sync-ok: retired host row
        if self._health_mon is not None:
            self._health_mon.consume(step_no, row)

    def _reset_async(self):
        """Land every deferred flag and drop the device-carried step
        count; the next fused step re-derives it from host counts. Called
        before any path that advances host counts outside the stream."""
        if self._stream is not None and self._stream.pending:
            self._stream.flush()
        self._t_dev = None
        self._mask_dev = None

    def _host_hypers(self, o):
        """(lr, wd) as host floats, cached between steps — with no
        scheduler they only change when the user assigns them, so the
        per-step float() conversions stay off the dispatch hot path."""
        cache = self._hyper_cache
        if cache is None or cache[0] != o.lr or cache[1] != o.wd:
            cache = (o.lr, o.wd, float(o.lr), float(o.wd))  # sync-ok: host scalars, cached
            self._hyper_cache = cache
        return cache[2], cache[3]

    def _sig_entry(self):
        """Tuning-table signature key for this step's net (stable across
        processes: gluon name prefixes are deterministic)."""
        return "fused_step:%s" % self._net.name

    def _record_signature(self, x, y):
        """Remember the batch signature so tuning.warmup() in a resumed
        process can AOT-compile this exact program before the first real
        step."""
        if self._sig_recorded:
            return
        self._sig_recorded = True
        try:
            from .. import tuning

            tuning.record_signature(self._sig_entry(), {
                "x_shape": list(x.shape), "x_dtype": str(x.data.dtype),
                "y_shape": list(y.shape), "y_dtype": str(y.data.dtype),
                "guard": bool(self._guard)})
        except Exception:  # noqa: BLE001 — bookkeeping must not fail a step
            pass

    def aot_warmup(self, x=None, y=None):
        """AOT-lower-and-compile the fused step program without running
        a step (donation makes execute-to-warm destructive — weights are
        never touched). ``x``/``y`` give the batch signature explicitly;
        omitted, the signatures a previous process recorded in the
        tuning table are replayed. With ``MXT_COMPILE_CACHE_DIR`` set
        the compile lands in (warm: replays from) the persistent cache,
        so the first real step performs zero hot-path JIT. Returns the
        number of programs compiled, or False if the step cannot build
        (ineligible config / no recorded signature)."""
        from .. import tuning

        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        if x is not None:
            if not isinstance(x, NDArray):
                x = _nd.array(x)
            if not isinstance(y, NDArray):
                y = _nd.array(y)
            specs = [{"x_shape": list(x.shape),
                      "x_dtype": str(x.data.dtype),
                      "y_shape": list(y.shape),
                      "y_dtype": str(y.data.dtype)}]
            # persist the signature: a bare tuning.warmup() (this
            # process or the next one) can then replay this compile
            tuning.record_signature(self._sig_entry(), specs[0])
        else:
            specs = tuning.signatures(self._sig_entry())
        if not specs:
            return False
        if self._jit is None and self._fallback_reason is None:
            self._fallback_reason = self.eligible(tr, self._net)
            if self._fallback_reason is None:
                spec = specs[0]
                self._build(_nd.zeros(tuple(spec["x_shape"]),
                                      dtype=spec["x_dtype"]))
        if self._jit is None:
            return False
        o = tr._optimizer
        updater = tr._updaters[0]
        for n, i in zip(self._train_names, self._indices):
            if i not in updater.states:
                updater.states[i] = o.create_state_multi_precision(
                    i, self._all_params[n].data())
                updater.states_synced[i] = True

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        ws = tuple(sds(self._all_params[n].data().data)
                   for n in self._train_names)
        ss = tuple(tuple(sds(l.data)
                         for l in _FusedUpdate._leaves(updater.states[i]))
                   for i in self._indices)
        aux = tuple(sds(self._all_params[n].data().data)
                    for n in self._aux_names)
        if self._base_key is None:
            self._base_key = _random.new_key()
        import jax.numpy as jnp

        count = 0
        for spec in specs:
            xs = jax.ShapeDtypeStruct(tuple(spec["x_shape"]),
                                      spec["x_dtype"])
            ys = jax.ShapeDtypeStruct(tuple(spec["y_shape"]),
                                      spec["y_dtype"])
            # scalar args mirror the hot path's aval kinds (python
            # int/float = weak-typed; guard t/mask are strong i32/u32)
            # so the persistent-cache key matches the real dispatch
            if self._guard:
                self._jit.lower(ws, ss, aux, xs, ys, self._base_key,
                                jnp.int32(0), jnp.uint32(0), 0.0, 0.0,
                                1.0).compile()
            else:
                self._jit.lower(ws, ss, aux, xs, ys, self._base_key, 1,
                                0.0, 0.0, 1.0).compile()
            count += 1
        return count

    def _publish_hbm(self, updater):
        """Register this step's device working set in the diagnostics
        HBM ledger (once; host arithmetic on shape metadata only): the
        params pool (trainable + aux) and the optimizer-state pool."""
        if self._hbm_published:
            return
        self._hbm_published = True
        try:
            from .. import diagnostics

            params = sum(self._all_params[n].data().data.nbytes
                         for n in self._all_params)
            opt = sum(l.data.nbytes
                      for i in self._indices
                      for l in _FusedUpdate._leaves(updater.states[i]))
            key = self._sig_entry()
            diagnostics.hbm_set("params", key, params)
            diagnostics.hbm_set("optimizer", key, opt)
        except Exception:  # noqa: BLE001 — accounting must not fail a step
            pass

    def _commit_buffers(self, updater):
        """Pin every donated buffer (weights, optimizer state, aux) to
        the device it is on, once, before the first dispatch. Freshly
        initialized buffers are uncommitted; the program returns them
        committed, and a jit keys its executable on that — so without
        this the WHOLE step compiles a second time on step 2 (a
        ResNet-50 step is half a minute of compile on the chip)."""
        if self._committed:
            return
        self._committed = True
        nds = [p.data() for p in self._all_params.values()]
        nds += [l for i in self._indices
                for l in _FusedUpdate._leaves(updater.states[i])]
        loose = [a for a in nds if not a.data.committed]
        if loose:
            placed = jax.device_put(
                [a.data for a in loose],
                [a.data.sharding for a in loose])
            for a, v in zip(loose, placed):
                a._set_data(v)

    def _fused_step(self, x, y, batch_size):
        """One fused launch, dispatched asynchronously. Returns None if
        host-side invariants don't hold this step (caller falls back to
        the eager loop)."""
        _t0 = time.perf_counter()  # dispatch-phase span (host work only)
        self._record_signature(x, y)
        tr = self._trainer
        o = tr._optimizer
        updater = tr._updaters[0]
        for n, i in zip(self._train_names, self._indices):
            if i not in updater.states:
                updater.states[i] = o.create_state_multi_precision(
                    i, self._all_params[n].data())
                updater.states_synced[i] = True
        self._publish_hbm(updater)
        self._commit_buffers(updater)
        # the fused program uses ONE step count for every parameter; if a
        # prior eager/kvstore path left counts uneven, stay eager
        counts = {o._index_update_count.get(i, o.begin_num_update)
                  for i in self._indices}
        if len(counts) > 1:
            self._reset_async()
            return None
        rescale = tr._scale / batch_size
        tr._check_and_rescale_grad(rescale)
        sched = o.lr_scheduler
        if self._guard:
            if sched is not None:
                # scheduler lr depends on the data-dependent step count:
                # observe the flag per step (window forced to 1). t enters
                # as the last APPLIED count; the program bumps it itself.
                base = o._index_update_count.get(
                    self._indices[0], o.begin_num_update) \
                    if self._indices else 0
                num_update = max(o.num_update, base + 1)
                lr = float(sched(num_update))  # sync-ok: host scheduler scalar
                wd = float(o.wd)  # sync-ok: host scalar
                t_in, mask_in = base, 0
            else:
                lr, wd = self._host_hypers(o)
                if self._t_dev is None:
                    import jax.numpy as jnp

                    base = o._index_update_count.get(
                        self._indices[0], o.begin_num_update) \
                        if self._indices else 0
                    self._t_dev = jnp.int32(base)
                    self._mask_dev = jnp.uint32(0)
                t_in, mask_in = self._t_dev, self._mask_dev
        else:
            # host bookkeeping mirrors the eager order (_update_count then
            # _get_lr): the scheduler sees the post-bump num_update
            for i in self._indices:
                o._update_count(i)
            t_in = o._index_update_count[self._indices[0]] \
                if self._indices else 1
            if sched is not None:
                lr = float(sched(o.num_update))  # sync-ok: host scheduler scalar
                wd = float(o.wd)  # sync-ok: host scalar
            else:
                lr, wd = self._host_hypers(o)
        ws = tuple(self._all_params[n].data().data
                   for n in self._train_names)
        ss = tuple(tuple(l.data
                         for l in _FusedUpdate._leaves(updater.states[i]))
                   for i in self._indices)
        aux = tuple(self._all_params[n].data().data
                    for n in self._aux_names)
        if self._base_key is None:
            # drawn lazily so mx.random.seed() between construction and
            # the first step still takes effect
            self._base_key = _random.new_key()
        # seeded chaos: scale is 1.0 except on the one firing dispatch
        # (jit sees the same weak-float aval either way — no retrace)
        spike_scale = 1.0
        if self._spike:
            from .. import health as _health
            spike_scale = _health.grad_spike_scale(
                self._stream._dispatched + 1)
        row = None
        try:
            with jax.profiler.TraceAnnotation(
                    "mxt.step.dispatch", step=self._stream._dispatched + 1):
                if self._guard:
                    if self._health:
                        (loss_vec, new_w, new_s, new_aux, outs, t_new,
                         mask_new, row) = self._jit(
                            ws, ss, aux, x.data, y.data, self._base_key, t_in,
                            mask_in, lr, wd, rescale, spike_scale)
                    else:
                        (loss_vec, new_w, new_s, new_aux, outs, t_new,
                         mask_new) = self._jit(
                            ws, ss, aux, x.data, y.data, self._base_key, t_in,
                            mask_in, lr, wd, rescale, spike_scale)
                elif self._health:
                    loss_vec, new_w, new_s, new_aux, outs, row = self._jit(
                        ws, ss, aux, x.data, y.data, self._base_key, t_in, lr,
                        wd, rescale, spike_scale)
                else:
                    loss_vec, new_w, new_s, new_aux, outs = self._jit(
                        ws, ss, aux, x.data, y.data, self._base_key, t_in, lr,
                        wd, rescale, spike_scale)
        except Exception as e:  # noqa: BLE001 — OOM gets the HBM ledger
            from .. import diagnostics

            diagnostics.reraise_if_oom(e, "fused_step")
            raise
        _count_launch()
        # rebind unconditionally: donation consumed the input buffers, and
        # on a skipped step the outputs ARE the (identity) old values
        for n, i, w2, s2 in zip(self._train_names, self._indices, new_w,
                                new_s):
            self._all_params[n].data()._set_data(w2)
            for leaf, v in zip(_FusedUpdate._leaves(updater.states[i]), s2):
                leaf._set_data(v)
        for n, v in zip(self._aux_names, new_aux):
            self._all_params[n].data()._set_data(v)
        if self._guard:
            if sched is not None:
                from ..ndarray.pending import PendingValue

                if row is not None:
                    # same single read as the mask path: the row carries
                    # the guard bit in its last column plus the stats
                    r = PendingValue(row).get()  # sync-ok: scheduler forces per-step observe
                    self._consume_health_row(int(t_in) + 1, r)
                else:
                    ok = (int(PendingValue(mask_new).get()) & 1) == 0
                    self._consume_flag(ok)
            else:
                # deferred: the flag lands when the engine window retires
                # this step's token (<= 1 host read per K steps)
                self._t_dev, self._mask_dev = t_new, mask_new
                if row is not None:
                    self._stream.push(loss_vec, value=row)
                else:
                    self._stream.push(loss_vec, flags=mask_new)
        elif row is not None:
            # stats stage into the window; the retirement read the token
            # already costs covers them (bit-equal syncs/step vs off)
            self._stream.push(loss_vec, value=row)
        else:
            # no host-consumed outputs; the token still throttles dispatch
            self._stream.push(loss_vec)
        from .. import telemetry
        telemetry.record_phase("dispatch", time.perf_counter() - _t0,
                               stream="fused_step",
                               step=self._stream._dispatched)
        loss = NDArray(loss_vec)
        if self._return_outputs:
            out_nds = [NDArray(o_) for o_ in outs]
            return loss, out_nds[0] if len(out_nds) == 1 else out_nds
        return loss

    def _eager_step(self, x, y, batch_size):
        """The canonical loop, verbatim — identical numerics, more
        launches."""
        with ag.record():
            out = self._net(x)
            outs = out if not isinstance(out, (list, tuple)) else \
                (out[0] if len(out) == 1 else list(out))
            loss = self._loss_fn(outs, y)
        loss.backward()
        self._trainer.step(batch_size)
        if self._return_outputs:
            return loss, outs
        return loss

    def __call__(self, x, y, batch_size=None):
        if not isinstance(x, NDArray):
            x = _nd.array(x)
        if not isinstance(y, NDArray):
            y = _nd.array(y)
        if batch_size is None:
            batch_size = x.shape[self._batch_axis]
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        if self._jit is not None and tr._optimizer is not self._built_opt:
            # trainer.load_states swapped the optimizer object; the jit
            # closed over the old hyper-params — rebuild against the live
            # one so a resumed run stays fused with the right settings
            self._reset_async()
            self._jit = None
            self._fallback_reason = None
            self._hyper_cache = None
            self._committed = False  # load_states brought fresh buffers
        result = None
        if self._jit is None and self._fallback_reason is None:
            self._fallback_reason = self.eligible(tr, self._net)
            if self._fallback_reason is None:
                from ..profiler import setup_scope

                # the build, the step's trace and compile (or the cache's
                # read) and the return of its first dispatch
                with setup_scope("step_build"):
                    self._build(x)
                    result = self._fused_step(x, y, batch_size)
        elif self._jit is not None:
            result = self._fused_step(x, y, batch_size)
        if result is not None:
            return result
        return self._eager_step(x, y, batch_size)


def train_step(net, loss_fn, trainer, batch_axis=0, return_outputs=False):
    """Build a fused (one donated launch) training step for ``net``, with
    transparent fallback to the eager record/backward/step loop — the
    functional spelling of ``trainer.fuse_step(net, loss_fn)``."""
    return CachedTrainStep(net, loss_fn, trainer, batch_axis=batch_axis,
                           return_outputs=return_outputs)


class FusedApply:
    """Fuse a list of per-index optimizer updates into ONE donated launch.

    The _FusedUpdate jit brought to any (weights, grads) list keyed by
    updater indices — Module.update's per-parameter loop rides this so the
    symbolic path's optimizer phase is one launch too, sharing
    ``_FusedUpdate._param_update`` for numerics (identical to the eager
    ``Updater`` call, fewer launches). Falls back (returns False) when a
    per-step invariant doesn't hold; the caller then runs the eager loop.
    """

    def __init__(self, optimizer, indices):
        self._opt = optimizer
        self._indices = list(indices)
        self._hyper_cache = None  # (lr, wd, rescale) -> host floats
        upds = [_FusedUpdate._param_update(optimizer, i)
                for i in self._indices]

        @jax.named_scope("optimizer")
        def step(ws, gs, ss, t, lr, wd, rescale):
            out_w, out_s = [], []
            for f, w, g, s in zip(upds, ws, gs, ss):
                w2, s2 = f(w, g, s, t, lr, wd, rescale)
                out_w.append(w2)
                out_s.append(s2)
            return tuple(out_w), tuple(out_s)

        self._jit = jax.jit(step, donate_argnums=(0, 2))

    @staticmethod
    def supported(optimizer):
        """Static (per-optimizer) half of the eligibility check; dense
        grads are re-checked per call."""
        return (_config().get("MXT_FUSED_STEP")
                and type(optimizer).__name__ in _FusedUpdate._SUPPORTED
                and type(optimizer).__module__ == opt.Optimizer.__module__
                and not getattr(optimizer, "multi_precision", False)
                and not getattr(optimizer, "aggregate_num", 0))

    def __call__(self, updater, weights, grads):
        o = self._opt
        for i, w, g in zip(self._indices, weights, grads):
            if getattr(g, "stype", "default") != "default":
                return False
            if i not in updater.states:
                updater.states[i] = o.create_state_multi_precision(i, w)
                updater.states_synced[i] = True
        counts = {o._index_update_count.get(i, o.begin_num_update)
                  for i in self._indices}
        if len(counts) > 1:
            return False
        for i in self._indices:
            o._update_count(i)
        t = o._index_update_count[self._indices[0]] if self._indices else 1
        if o.lr_scheduler is not None:
            lr = float(o.lr_scheduler(o.num_update))  # sync-ok: host scheduler scalar
            wd = float(o.wd)  # sync-ok: host scalar
            rs = float(o.rescale_grad)  # sync-ok: host scalar
        else:
            # constant scheduler: hoist the per-step float() conversions
            # off the dispatch hot path (cached until the user changes
            # the hyper-params)
            cache = self._hyper_cache
            if cache is None or cache[0] != o.lr or cache[1] != o.wd or \
                    cache[2] != o.rescale_grad:
                cache = (o.lr, o.wd, o.rescale_grad,  # sync-ok: host scalars, cached
                         float(o.lr), float(o.wd),  # sync-ok: host scalars, cached
                         float(o.rescale_grad))  # sync-ok: host scalars, cached
                self._hyper_cache = cache
            lr, wd, rs = cache[3], cache[4], cache[5]
        ws = tuple(w.data for w in weights)
        gs = tuple(g.data for g in grads)
        ss = tuple(tuple(l.data
                         for l in _FusedUpdate._leaves(updater.states[i]))
                   for i in self._indices)
        new_w, new_s = self._jit(ws, gs, ss, t, lr, wd, rs)
        _count_launch()
        for w, i, w2, s2 in zip(weights, self._indices, new_w, new_s):
            w._set_data(w2)
            for leaf, v in zip(_FusedUpdate._leaves(updater.states[i]), s2):
                leaf._set_data(v)
        return True
