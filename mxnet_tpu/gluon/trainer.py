"""gluon.Trainer (ref: python/mxnet/gluon/trainer.py).

Applies an Optimizer to a set of Parameters. The reference's per-GPU grad
arrays + kvstore allreduce collapse here: each Parameter holds ONE buffer
(possibly sharded over the mesh, in which case the backward pass already
psum-reduced the gradient over ICI). The kvstore path is kept with the same
`update_on_kvstore` decision logic (ref: trainer.py — _init_kvstore,
model.py — _create_kvstore) so KVStore-driven training (including
dist types and server-side optimizers) behaves like the reference.
"""
from __future__ import annotations

import time

import jax

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt
from .. import kvstore as kvs
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class _FusedUpdate:
    """ONE donated XLA launch for Trainer.step's optimizer phase.

    The reference's canonical Gluon loop (record/backward/trainer.step,
    ref: gluon/trainer.py — step) issues one engine op per parameter; its
    async engine hides the launches. Here every launch is a host dispatch
    of its own, so a 200-parameter model would pay 200 of them in
    Trainer.step alone. This fuses every eligible parameter's update into
    one jitted program with weights and optimizer state DONATED — the
    static_alloc analog ShardedTrainStep already uses (parallel/sharded.py)
    brought to the canonical path.

    Eligible: optimizer class in _SUPPORTED (sgd/nag/adam/adamw/rmsprop/
    adagrad), dense gradients, no multi_precision, and no
    distributed/server-side kvstore. Anything else
    falls back to the eager per-parameter updater (same numerics, more
    launches). Dynamic scalars (scheduler lr, wd, rescale_grad, step t)
    enter as traced 0-d arguments so no step ever retraces; per-parameter
    lr_mult/wd_mult are folded in as static multipliers at build time.
    Optimizer state stays in Updater.states in the eager layout, so
    save_states/load_states round-trip unchanged.
    """

    _SUPPORTED = ("SGD", "NAG", "Adam", "AdamW", "RMSProp", "AdaGrad")

    @staticmethod
    def eligible(trainer):
        from .. import config as _config

        o = trainer._optimizer
        if not _config.get("MXT_FUSED_TRAINER"):
            return False
        if type(o).__name__ not in _FusedUpdate._SUPPORTED or \
                type(o).__module__ != opt.Optimizer.__module__:
            return False
        if getattr(o, "multi_precision", False):
            return False
        if getattr(o, "aggregate_num", 0):
            return False
        if trainer._update_on_kvstore:
            return False
        kv = trainer._kvstore
        embedding_kv = kv is not None and kv.type == "dist_embedding"
        if kv is not None and not embedding_kv and \
                (kv.type.startswith("dist") or
                 trainer._compression_params):
            return False
        if jax.process_count() > 1:
            return False
        for p in trainer._params:
            if p.grad_req == "null":
                continue
            if getattr(p, "_grad_stype", "default") != "default" \
                    and not embedding_kv:
                # sparse grads are only safe to exclude from the fused
                # program when the embedding kvstore owns them (the
                # trainer routes them through _embedding_step); any
                # other config must stay on the eager per-param path
                return False
        return True

    def __init__(self, trainer):
        self._trainer = trainer
        o = trainer._optimizer
        self._opt = o
        # sparse-grad params are kvstore-owned (dist_embedding routes
        # them via _embedding_step); the fused program covers the rest
        self._indices = [i for i, p in enumerate(trainer._params)
                         if p.grad_req != "null"
                         and getattr(p, "_grad_stype",
                                     "default") == "default"]
        self._upds = [self._param_update(o, i) for i in self._indices]
        self._hyper_cache = None  # host floats, cached between steps
        self._jit_guarded = None  # built on first guarded() call
        self._stream = None       # engine.StepStream for deferred flags
        self._t_dev = None        # device-carried step count (guard mode)
        self._mask_dev = None
        upds = self._upds

        @jax.named_scope("optimizer")
        def step(ws, gs, ss, t, lr, wd, rescale):
            out_w, out_s = [], []
            for f, w, g, s in zip(upds, ws, gs, ss):
                w2, s2 = f(w, g, s, t, lr, wd, rescale)
                out_w.append(w2)
                out_s.append(s2)
            return tuple(out_w), tuple(out_s)

        # weights + states donated: buffers are reused across steps and the
        # params' NDArray wrappers rebind to the outputs
        self._jit = jax.jit(step, donate_argnums=(0, 2))
        from .. import tuning
        tuning.register_step(self)  # bare tuning.warmup() AOT-compiles us

    @staticmethod
    def _param_update(o, index):
        """Per-parameter pure update (w, g, state_leaves, t, lr, wd,
        rescale) -> (w2, leaves2), numerics identical to the eager
        Optimizer.update path."""
        import jax.numpy as jnp

        from ..ops.registry import get_op

        lr_mult = o.param_dict[index].lr_mult if index in o.param_dict \
            else o.lr_mult.get(index, o.lr_mult.get(
                o.idx2name.get(index), 1.0))
        wd_mult = o.param_dict[index].wd_mult if index in o.param_dict \
            else o.wd_mult.get(index, o.wd_mult.get(
                o.idx2name.get(index), 1.0))
        clip = o.clip_gradient
        name = type(o).__name__
        if name in ("SGD", "NAG"):
            momentum = o.momentum
            if momentum:
                fn = get_op("sgd_mom_update" if name == "SGD"
                            else "nag_mom_update").fn

                def upd(w, g, s, t, lr, wd, rescale):
                    w2, m2 = fn(w, g, s[0], lr=lr * lr_mult,
                                momentum=momentum, wd=wd * wd_mult,
                                rescale_grad=rescale, clip_gradient=clip)
                    return w2, (m2,)
            else:
                fn = get_op("sgd_update").fn

                def upd(w, g, s, t, lr, wd, rescale):
                    return fn(w, g, lr=lr * lr_mult, wd=wd * wd_mult,
                              rescale_grad=rescale, clip_gradient=clip), ()
        elif name == "RMSProp":
            gamma1, gamma2, eps = o.gamma1, o.gamma2, o.epsilon
            clip_w = o.clip_weights
            if o.centered:
                fn = get_op("rmspropalex_update").fn

                def upd(w, g, s, t, lr, wd, rescale):
                    w2, n2, g2, d2 = fn(
                        w, g, s[0], s[1], s[2], lr=lr * lr_mult,
                        gamma1=gamma1, gamma2=gamma2, epsilon=eps,
                        wd=wd * wd_mult, rescale_grad=rescale,
                        clip_gradient=clip, clip_weights=clip_w)
                    return w2, (n2, g2, d2)
            else:
                fn = get_op("rmsprop_update").fn

                def upd(w, g, s, t, lr, wd, rescale):
                    w2, n2 = fn(w, g, s[0], lr=lr * lr_mult,
                                gamma1=gamma1, epsilon=eps,
                                wd=wd * wd_mult, rescale_grad=rescale,
                                clip_gradient=clip, clip_weights=clip_w)
                    return w2, (n2,)
        elif name == "AdaGrad":
            eps = o.float_stable_eps

            def upd(w, g, s, t, lr, wd, rescale):
                # mirror the eager python update exactly (optimizer.py —
                # AdaGrad.update dense branch)
                g = g * rescale
                if clip is not None:
                    g = jnp.clip(g, -clip, clip)
                g = g + (wd * wd_mult) * w
                s2 = s[0] + g * g
                w2 = w - (lr * lr_mult) * g / (jnp.sqrt(s2) + eps)
                return w2.astype(w.dtype), (s2,)
        else:  # Adam / AdamW — bias correction folded into lr, as eager
            beta1, beta2, eps = o.beta1, o.beta2, o.epsilon
            if name == "Adam":
                fn = get_op("adam_update").fn

                def apply(w, g, s, lr_t, wd, rescale):
                    return fn(w, g, s[0], s[1], lr=lr_t, wd=wd,
                              beta1=beta1, beta2=beta2, epsilon=eps,
                              rescale_grad=rescale, clip_gradient=clip)
            else:
                fn = get_op("adamw_update").fn

                def apply(w, g, s, lr_t, wd, rescale):
                    return fn(w, g, s[0], s[1], lr=lr_t, wd=wd, eta=1.0,
                              beta1=beta1, beta2=beta2, epsilon=eps,
                              rescale_grad=rescale, clip_gradient=clip)

            def upd(w, g, s, t, lr, wd, rescale):
                coef1 = 1.0 - jnp.power(beta1, t)
                coef2 = 1.0 - jnp.power(beta2, t)
                lr_t = lr * lr_mult * jnp.sqrt(coef2) / coef1
                w2, m2, v2 = apply(w, g, s, lr_t, wd * wd_mult, rescale)
                return w2, (m2, v2)
        return upd

    @staticmethod
    def _leaves(state):
        if state is None:
            return ()
        if isinstance(state, tuple):
            return state
        return (state,)

    def _prepare(self, updater):
        """Shared per-step invariants: grads/states present, counts even.
        Returns False if the caller must fall back to the eager path."""
        o = self._opt
        params = self._trainer._params
        for i in self._indices:
            p = params[i]
            if p._data is None or getattr(p._data, "_grad", None) is None:
                return False
            if i not in updater.states:
                updater.states[i] = o.create_state_multi_precision(
                    i, p.data())
                updater.states_synced[i] = True
        # the fused program uses ONE step count for every parameter; if a
        # prior eager/kvstore path left counts uneven, stay eager
        counts = {o._index_update_count.get(i, o.begin_num_update)
                  for i in self._indices}
        return len(counts) == 1

    def _host_hypers(self, o):
        """(lr, wd) host floats with the constant-scheduler conversions
        cached between steps (off the dispatch hot path)."""
        cache = self._hyper_cache
        if cache is None or cache[0] != o.lr or cache[1] != o.wd:
            cache = (o.lr, o.wd, float(o.lr), float(o.wd))  # sync-ok: host scalars, cached
            self._hyper_cache = cache
        return cache[2], cache[3]

    def __call__(self, rescale):
        """Run one fused update. Returns False (caller should fall back to
        the eager path) if host-side invariants don't hold this step."""
        tr = self._trainer
        o = self._opt
        updater = tr._updaters[0]
        params = tr._params
        if self._t_dev is not None:
            # a guarded (deferred-flag) run preceded this unguarded step:
            # land its bookkeeping before advancing counts on host
            self.flush_guarded()
        if not self._prepare(updater):
            return False

        # host-side bookkeeping first, mirroring eager order (_update_count
        # then _get_lr): scheduler sees the post-bump num_update
        for i in self._indices:
            o._update_count(i)
        t = o._index_update_count[self._indices[0]] if self._indices else 1
        if o.lr_scheduler is not None:
            lr = float(o.lr_scheduler(o.num_update))  # sync-ok: host scheduler scalar
            wd = float(o.wd)  # sync-ok: host scalar
        else:
            lr, wd = self._host_hypers(o)

        _t0 = time.perf_counter()
        ws = tuple(params[i].data().data for i in self._indices)
        gs = tuple(params[i].grad().data for i in self._indices)
        ss = tuple(tuple(l.data for l in self._leaves(updater.states[i]))
                   for i in self._indices)
        new_w, new_s = self._jit(ws, gs, ss, t, lr, wd, rescale)
        from .. import profiler
        profiler.record_launch()
        for i, w2, s2 in zip(self._indices, new_w, new_s):
            params[i].data()._set_data(w2)
            for leaf, v in zip(self._leaves(updater.states[i]), s2):
                leaf._set_data(v)
        from .. import telemetry
        telemetry.record_phase("dispatch", time.perf_counter() - _t0,
                               stream="trainer_step")
        return True

    # -- deferred non-finite guard (async dispatch) ------------------------
    def _build_guarded(self):
        """The same fused update with the resilience guard compiled IN:
        a lax.cond makes the whole update the identity when any gradient
        is non-finite, the step count rides the program as a device
        scalar, and the flag lands in a carried bitmask consumed by the
        engine's in-flight window — no per-step host read."""
        import jax.numpy as jnp

        upds = self._upds

        def step(ws, gs, ss, t, mask, lr, wd, rescale):
            finite = jnp.bool_(True)
            for g in gs:
                finite = jnp.logical_and(finite, jnp.isfinite(g).all())
            t_upd = t + 1

            def _apply(_):
                out_w, out_s = [], []
                for f, w, g, s in zip(upds, ws, gs, ss):
                    w2, s2 = f(w, g, s, t_upd, lr, wd, rescale)
                    out_w.append(w2)
                    out_s.append(s2)
                return tuple(out_w), tuple(out_s)

            def _skip(_):
                return tuple(ws), tuple(ss)

            new_w, new_s = jax.lax.cond(finite, _apply, _skip, None)
            t_new = t + jnp.where(finite, 1, 0)
            mask_new = (mask << 1) | jnp.where(finite, 0, 1)
            return new_w, new_s, t_new, mask_new

        self._jit_guarded = jax.jit(step, donate_argnums=(0, 2))
        from .. import engine
        self._stream = engine.StepStream(name="trainer_step",
                                         on_flags=self._on_flag)

    def _on_flag(self, finite):
        """Deferred bookkeeping for one retired step, in dispatch order
        (the loss-scale wrapper drives its own scaler — not here)."""
        if finite:
            for i in self._indices:
                self._opt._update_count(i)
        else:
            from .. import resilience
            resilience.record_skipped_step()

    def flush_guarded(self):
        """Land every deferred flag and drop the device step count (the
        next guarded step re-derives it from host counts)."""
        if self._stream is not None and self._stream.pending:
            self._stream.flush()
        self._t_dev = None
        self._mask_dev = None

    @property
    def pending(self):
        return self._stream.pending if self._stream is not None else 0

    def aot_warmup(self):
        """AOT-lower-and-compile the fused optimizer update (and the
        guarded variant when ``MXT_SKIP_NONFINITE`` is on) from the live
        parameter shapes — donation makes execute-to-warm destructive,
        so this never touches a weight. With ``MXT_COMPILE_CACHE_DIR``
        set the compiles land in (or replay from) the persistent cache;
        the first real ``trainer.step`` then performs no hot-path JIT.
        Returns the number of programs compiled, or False when the
        parameters aren't initialized yet."""
        import jax

        from .. import config as _cfg

        tr = self._trainer
        o = self._opt
        updater = tr._updaters[0]
        params = tr._params
        for i in self._indices:
            if params[i]._data is None:
                return False
            if i not in updater.states:
                updater.states[i] = o.create_state_multi_precision(
                    i, params[i].data())
                updater.states_synced[i] = True

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        ws = tuple(sds(params[i].data().data) for i in self._indices)
        gs = ws  # gradient avals match the weights
        ss = tuple(tuple(sds(l.data)
                         for l in self._leaves(updater.states[i]))
                   for i in self._indices)
        # scalar args mirror the hot path's aval kinds exactly (python
        # int/float here = weak-typed there) so the persistent-cache key
        # matches the real dispatch
        self._jit.lower(ws, gs, ss, 1, 0.0, 0.0, 1.0).compile()
        count = 1
        if _cfg.get("MXT_SKIP_NONFINITE"):
            import jax.numpy as jnp

            if self._jit_guarded is None:
                self._build_guarded()
            self._jit_guarded.lower(ws, gs, ss, jnp.int32(0),
                                    jnp.uint32(0), 0.0, 0.0, 1.0).compile()
            count += 1
        return count

    def guarded(self, rescale):
        """One fused update with the in-program non-finite guard,
        dispatched asynchronously. Returns False when this step can't run
        guarded-fused (caller falls back to the synchronous check)."""
        o = self._opt
        if o.lr_scheduler is not None:
            # scheduler lr depends on the data-dependent step count — the
            # synchronous guard path keeps exact lr semantics
            return False
        tr = self._trainer
        updater = tr._updaters[0]
        if not self._prepare(updater):
            self.flush_guarded()
            return False
        params = tr._params
        if self._jit_guarded is None:
            self._build_guarded()
        if self._t_dev is None:
            import jax.numpy as jnp

            base = o._index_update_count.get(
                self._indices[0], o.begin_num_update) if self._indices \
                else 0
            self._t_dev = jnp.int32(base)
            self._mask_dev = jnp.uint32(0)
        lr, wd = self._host_hypers(o)
        _t0 = time.perf_counter()
        ws = tuple(params[i].data().data for i in self._indices)
        gs = tuple(params[i].grad().data for i in self._indices)
        ss = tuple(tuple(l.data for l in self._leaves(updater.states[i]))
                   for i in self._indices)
        new_w, new_s, t_new, mask_new = self._jit_guarded(
            ws, gs, ss, self._t_dev, self._mask_dev, lr, wd, rescale)
        from .. import profiler
        profiler.record_launch()
        for i, w2, s2 in zip(self._indices, new_w, new_s):
            params[i].data()._set_data(w2)
            for leaf, v in zip(self._leaves(updater.states[i]), s2):
                leaf._set_data(v)
        self._t_dev, self._mask_dev = t_new, mask_new
        self._stream.push(mask_new, flags=mask_new)
        from .. import telemetry
        telemetry.record_phase("dispatch", time.perf_counter() - _t0,
                               stream="trainer_step")
        return True


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got %s." % (type(params),))
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    "got list of %s." % (type(param),))
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))  # sync-ok: construction-time host scalar
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {
            "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = []
        self._fused = None  # None = undecided, False = ineligible
        self._reset_kvstore()

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params and set(optimizer_params) != {"rescale_grad"}:
                raise ValueError(
                    "optimizer_params must be None if optimizer is an "
                    "instance of Optimizer instead of str")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _reset_kvstore(self):
        if self._kvstore and self._kvstore.type.startswith("dist"):
            raise RuntimeError(
                "Cannot reset distributed KVStore.")
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = [p for p in self._params]
        self._fused = None

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore_arg = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        has_sparse = any(getattr(p, "_grad_stype", "default") != "default"
                         for p in self._params)
        kvstore = None
        if kvstore_arg:
            if isinstance(kvstore_arg, kvs.KVStore):
                kvstore = kvstore_arg
            elif isinstance(kvstore_arg, str):
                kvstore = kvs.create(kvstore_arg)
            else:
                raise ValueError("kvstore must be a KVStore instance or name")
        elif has_sparse:
            # sparse grads are applied where the weight lives
            kvstore = kvs.create("local")
        if kvstore is not None and kvstore.type == "dist_embedding":
            # hybrid ownership: row_sparse tables update on the sharded
            # embedding fleet (server-side sparse optimizer), dense
            # parameters stay on the local — fused — update path
            if update_on_kvstore is False:
                raise ValueError(
                    "update_on_kvstore=False is not supported with "
                    "kvstore='dist_embedding': sparse tables update on "
                    "the embedding servers by design")
            kvstore.set_optimizer(self._optimizer)
            update_on_kvstore = False
        elif kvstore is not None:
            if has_sparse:
                # ref: trainer.py — sparse gradients force
                # update_on_kvstore=True (row_sparse rows are updated on
                # the store that holds the full weight)
                if update_on_kvstore is False:
                    raise ValueError(
                        "update_on_kvstore=False is not supported with "
                        "sparse gradients (matches reference)")
                update_on_kvstore = True
            if update_on_kvstore is None:
                # reference default: update on kvstore when distributed
                update_on_kvstore = kvstore.type.startswith("dist")
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
                # server-side optimizer owns the state; keep updater list
                # for save_states compatibility
                self._updaters = [kvstore._updater]
        else:
            update_on_kvstore = False
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = True

    @property
    def _embedding_kv(self):
        return self._kvstore is not None \
            and self._kvstore.type == "dist_embedding"

    def _init_params(self):
        """Lazily register params whose deferred init has completed."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            self._params_to_init = []
            return
        remaining = []
        emb = self._embedding_kv
        for param in self._params_to_init:
            if param._deferred_init is not None or param._data is None:
                remaining.append(param)
            else:
                if emb and getattr(param, "_grad_stype",
                                   "default") != "row_sparse":
                    # dist_embedding registers ONLY the sparse tables;
                    # dense params never ship to the fleet
                    continue
                idx = self._param2idx[param.name]
                self._kvstore.init(idx, param.data())
        self._params_to_init = remaining

    @property
    def learning_rate(self):
        return self._optimizer.lr if self._optimizer.lr_scheduler is None \
            else self._optimizer.lr_scheduler(self._optimizer.num_update)

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def fuse_step(self, net, loss_fn, batch_axis=0, return_outputs=False):
        """Whole-step fusion: forward + backward + optimizer update as ONE
        donated XLA launch (gluon/train_step.py — CachedTrainStep), with
        transparent fallback to the eager record/backward/step loop when
        this trainer's config is ineligible. Returns a callable
        ``step(x, y, batch_size=None) -> loss`` (or ``(loss, outputs)``
        with ``return_outputs=True``)."""
        from .train_step import CachedTrainStep

        return CachedTrainStep(net, loss_fn, self, batch_axis=batch_axis,
                               return_outputs=return_outputs)

    # ------------------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + optimizer update, scaled by 1/batch_size
        (ref: trainer.py — step). With ``MXT_SKIP_NONFINITE=1`` a batch
        whose gradients contain NaN/Inf is skipped wholesale — weights,
        optimizer state, and update counts untouched (resilience.py). On
        the fused path the guard compiles INTO the launch and its flag is
        observed deferred through the engine's in-flight window, so no
        per-step host read throttles dispatch; the eager path keeps the
        synchronous check (the skip decision gates the update itself)."""
        rescale_grad = self._scale / batch_size
        self._check_and_rescale_grad(rescale_grad)
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._fused is None:
            self._fused = _FusedUpdate(self) if _FusedUpdate.eligible(self) \
                else False
        from .. import resilience
        emb = self._embedding_kv
        if resilience.skip_nonfinite_enabled():
            # the embedding push is not gated by a deferred flag (rows
            # apply server-side the moment they arrive), so with an
            # embedding kvstore the guard must decide SYNCHRONOUSLY
            # before any row ships
            if not emb and self._fused and self._fused.guarded(
                    rescale_grad):
                return  # guard + update in one launch, flag deferred
            if self._fused:
                self._fused.flush_guarded()
            if self._grads_overflowed():
                resilience.record_skipped_step()
                return
        if emb:
            # sparse tables: gradient rows to the fleet (server-side
            # sparse optimizer), then a row pull of exactly the touched
            # rows back into the dense mirror — through the hot cache,
            # which the push's write-back just refreshed
            self._embedding_step()
        if self._fused and self._fused(rescale_grad):
            return  # one donated launch covered reduce (identity) + update
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def _embedding_step(self):
        """Route every row_sparse parameter through the sharded
        embedding fleet: push gradient rows, pull the updated rows back
        into the parameter's dense buffer (the device-resident working
        set — untouched rows keep their values, the lazy-update
        contract)."""
        kv = self._kvstore
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or \
                    getattr(param, "_grad_stype", "default") != "row_sparse":
                continue
            grad = param.grad()  # RowSparseNDArray at the boundary
            kv.push(i, grad)
            kv.row_sparse_pull(i, out=param.data(), row_ids=grad.indices)

    def _grads_overflowed(self):
        """True if any live gradient is non-finite — one fused device
        check + one host read for the whole set (the LossScaler
        machinery; resilience.all_finite)."""
        from .. import resilience

        grads = [p.grad() for p in self._params
                 if p.grad_req != "null" and p._data is not None
                 and getattr(p._data, "_grad", None) is not None]
        return bool(grads) and not resilience.all_finite(grads)

    def _check_and_rescale_grad(self, scale):
        if self._kv_initialized and \
                (self._update_on_kvstore or self._embedding_kv) and \
                self._optimizer.rescale_grad != scale:
            raise UserWarning(
                "Possible change in the `batch_size` from previous `step` "
                "detected. Optimizer gradient normalizing factor will not "
                "change w.r.t new batch_size when update_on_kvstore=True")
        self._optimizer.rescale_grad = scale

    def allreduce_grads(self):
        """Only reduce gradients, no update (for grad manipulation between
        allreduce and update; ref: trainer.py — allreduce_grads)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            raise AssertionError(
                "allreduce_grads() when parameters are updated on kvstore "
                "is not supported. Try setting `update_on_kvstore` to False "
                "when creating trainer.")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if self._embedding_kv:
            # sparse params already flowed through _embedding_step;
            # dense grads stay local (single-process data path — the
            # fleet holds tables, not a gradient-reduction plane)
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if self._update_on_kvstore:
                # push grad; server applies the update into the weight,
                # pull brings it back
                self._kvstore.push(i, param.list_grad()[0])
                self._kvstore.pull(i, param.data(), ignore_sparse=False)
            else:
                self._kvstore.push(i, param.list_grad()[0])
                self._kvstore.pull(i, param.list_grad()[0])

    def update(self, batch_size, ignore_stale_grad=False):
        """Only the optimizer update (call allreduce_grads first;
        ref: trainer.py — update)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore is not " \
            "supported. Try setting `update_on_kvstore` to False when " \
            "creating trainer."
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._update_on_kvstore:
            return  # weights already updated server-side in _allreduce_grads
        updater = self._updaters[0]
        emb = self._embedding_kv
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if emb and getattr(param, "_grad_stype",
                               "default") == "row_sparse":
                continue  # applied server-side by _embedding_step
            if param._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        "parameter %s has not been initialized" % param.name)
                continue
            updater(i, param.grad(), param.data())

    # -- state persistence (ref: trainer.py — save_states/load_states) -----
    def save_states(self, fname):
        """Serialize optimizer state + update counts. Valid at ANY point
        — including before the first ``step()`` (per-parameter state is
        created lazily, so an early save just records the optimizer and
        empty state dicts); failure modes raise a clear MXNetError
        rather than an IndexError/AssertionError."""
        if self._optimizer is None:
            raise MXNetError(
                "Trainer has no optimizer — cannot save states")
        from .. import engine
        engine.wait_all()  # land deferred update counts before serializing
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            if self._kvstore is None or self._kvstore._updater is None:
                raise MXNetError(
                    "update_on_kvstore trainer has no server-side "
                    "updater yet — cannot save states")
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            if not self._updaters:
                raise MXNetError(
                    "Trainer has no updater — cannot save states")
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        from .. import engine
        engine.wait_all()  # drain in-flight steps before swapping state
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if not self._update_on_kvstore and not self._updaters:
            raise MXNetError(
                "Trainer has no updater — cannot load states")
        # the fused step closes over the optimizer OBJECT (hyper-params,
        # update counts); loading swaps it — rebuild on next step
        self._fused = None
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        param_dict = {i: param for i, param in enumerate(self._params)}
        self._optimizer.param_dict = param_dict
