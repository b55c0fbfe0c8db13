"""Continuous-batching request scheduler — the admission/recomposition
brain over :class:`~mxnet_tpu.serving.engine.DecodeEngine`.

The reference framework's serving story was ``Module.forward`` on a
padded batch: compose a batch, run it to the longest member's end, eat
the padding. Continuous batching (the vLLM/Orca discipline) recomposes
the batch at every decode step instead: finished requests retire
immediately, queued requests join mid-flight through a prefill, and the
fixed-slot decode program never idles a slot that traffic could fill.

Host/device split: the scheduler is PURE host bookkeeping. It learns
sampled tokens only when the engine's in-flight window retires them
(K steps per deferred read), so its view lags the device by up to K
steps — by design:

- length-based completion (``max_new_tokens``) is host-arithmetic and
  retires a slot the step its quota is dispatched (no lag);
- EOS-based completion is observed at retirement, so up to K post-EOS
  tokens are generated and discarded — the classic deferred-sync
  trade, same as the training guard flags;
- attribution is exact regardless of lag: every dispatched step carries
  its (slot → request) composition as window metadata, so a token row
  retiring after the slot was recomposed still lands on the right
  request.

Deadlines: a request carries an optional SLO budget (seconds from
``submit``); the scheduler evicts blown requests — queued or running —
frees their pages, and counts them in
``mxt_serving_requests_total{outcome="evicted"}``.

:class:`StaticBatcher` is the A/B baseline of continuous batching:
same engine, same requests, but admission only at batch boundaries —
every slot waits for the batch's longest member, which is exactly the
waste continuous batching deletes.
"""
from __future__ import annotations

import collections
import itertools
import time

from ..base import MXNetError
from . import metrics as _m

__all__ = ["Request", "ContinuousBatcher", "StaticBatcher"]

_req_ids = itertools.count()


def _trace_span(req, name, t0, t1, now, **attrs):
    """Stamp one request-lifecycle span against the request's trace_id
    (a no-op for untraced requests). Host wall clocks only — the spans
    that depend on device results (prefill's first token, decode
    completion) are stamped from inside the engine window's EXISTING
    deferred retirement, so tracing adds zero device syncs."""
    if req.trace_id is None or t0 is None or t1 is None:
        return
    from .. import telemetry

    telemetry.record_trace_span(
        name, req.trace_id, t0, t1, clock_now=now,
        track=getattr(req, "_track", None), request=req.id, **attrs)


class Request:
    """One generation request: a prompt, a token budget, an optional
    deadline, and the output/latency record the scheduler fills in.
    ``trace_id`` (minted by the fleet router, or caller-supplied)
    threads the request through the distributed-tracing layer: the
    scheduler stamps queue/prefill/decode spans against it."""

    def __init__(self, prompt, max_new_tokens=16, deadline=None,
                 eos_id=None, request_id=None, trace_id=None,
                 tenant=None, priority=None):
        self.prompt = [int(t) for t in prompt]
        if not self.prompt:
            raise MXNetError("Request needs a non-empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        self.deadline = None if deadline is None \
            else float(deadline)  # sync-ok: host float, not a device read
        self.eos_id = None if eos_id is None else int(eos_id)
        self.id = request_id if request_id is not None \
            else "req-%d" % next(_req_ids)
        self.trace_id = None if trace_id is None else str(trace_id)
        # multi-tenant QoS (serving/qos.py): the tenant id rides for
        # accounting; the priority CLASS (lower = more important)
        # orders admission and selects preemption victims
        self.tenant = None if tenant is None else str(tenant)
        self.priority = 0 if priority is None else int(priority)
        self._track = None  # timeline row, stamped by the batcher
        # disaggregated handoff: a prefill replica already computed this
        # request's KV pages — (page payload, first token) to ADOPT at
        # admission instead of prefilling (serving/fleet.py ship/adopt)
        self._handoff = None
        self.output_tokens = []
        # queued|running|completed|evicted|rejected|preempted
        self.state = "created"
        self.t_submit = self.t_admit = self.t_first = self.t_finish = None
        self._dispatched = 0   # tokens generated-or-in-flight (incl. #1)
        self._first_pv = None  # deferred first token from prefill
        self._eos = False
        self._finalized = False
        # speculative (variable-advance) accounting: steps dispatched
        # but not yet retired, and the token-count UPPER bound they
        # imply (observed + inflight * k) — the dispatch gate that keeps
        # page usage within the admission reservation
        self._inflight = 0
        self._ub = 0

    @property
    def done(self):
        return self.state in ("completed", "evicted", "rejected",
                              "preempted")

    def _take_first(self, now):
        """Materialize the prefill's deferred first token (idempotent;
        one amortized host read per request). Stamps the prefill phase:
        submit-side wall clock to first-token availability."""
        pv, self._first_pv = self._first_pv, None
        if pv is None:
            return
        tok = int(pv.get().reshape(-1)[0])
        if self.t_first is None:
            self.t_first = now
            if self.t_admit is not None:
                _m.request_latency().labels("prefill").observe(
                    max(0.0, now - self.t_admit))
            # the prefill span closes here, inside the deferred read
            # that just materialized the first token — zero new syncs
            _trace_span(self, "prefill", self.t_admit, now, now)
        self._record(tok, now)

    def _record(self, tok, now):
        """One observed output token (post-EOS and over-budget tokens —
        dispatch lag artifacts — are discarded)."""
        if self.done and self.state != "completed":
            return
        if self._eos or len(self.output_tokens) >= self.max_new_tokens:
            return
        self.output_tokens.append(int(tok))
        if self.t_first is None:
            self.t_first = now
            _trace_span(self, "prefill", self.t_admit, now, now)
        if self.eos_id is not None and int(tok) == self.eos_id:
            self._eos = True
        if self._eos or len(self.output_tokens) >= self.max_new_tokens:
            self.state = "completed"
            self.t_finish = now
            # the decode-window span: first token -> last observed
            # token, closed inside the in-flight window's retirement
            _trace_span(self, "decode", self.t_first, now, now,
                        tokens=len(self.output_tokens))


class ContinuousBatcher:
    """Admission queue + per-step batch recomposition over one engine."""

    def __init__(self, engine, now_fn=time.monotonic, track=None):
        self.engine = engine
        # the timeline row traced requests' spans land on (a fleet
        # replica names this "replica-<i>"; standalone batchers group
        # under "batcher")
        self.track = str(track) if track is not None else "batcher"
        engine.on_tokens = self._on_tokens
        self._queue = collections.deque()
        self._slot_req = {}  # slot -> Request currently OWNING the slot
        self._now = now_fn
        self.steps = 0
        self.completed = []  # terminal requests, in finalization order
        # the hang watchdog observes decode progress: token retirements
        # bump the counter (in _on_tokens), outstanding work is queued +
        # running requests — a wedged decode (or a page leak starving
        # admission forever) shows as pending>0 with a frozen counter
        from .. import diagnostics

        diagnostics.register_source(
            "serving_decode",
            pending_fn=lambda: len(self._queue) + len(self._slot_req))
        self._diag = diagnostics

    # -- intake -----------------------------------------------------------
    def submit(self, request):
        """Queue a request (returns it). Requests that can NEVER fit —
        prompt+budget over the engine's context or the whole pool — are
        rejected immediately rather than deadlocking the queue."""
        request.t_submit = self._now()
        request._track = self.track
        total = len(request.prompt) + request.max_new_tokens
        # a speculative engine reserves extra overshoot pages per
        # sequence — impossibility is judged against the padded need
        padded = total + getattr(self.engine, "_reserve_slack", 0)
        cache = self.engine.cache
        if total > self.engine.max_context \
                or cache.pages_needed(padded) > cache.num_pages:
            request.state = "rejected"
            self._finalize(request, "rejected")
            return request
        request.state = "queued"
        self._queue.append(request)
        _m.queue_depth().set(len(self._queue))
        return request

    # -- the per-step recomposition loop ----------------------------------
    @property
    def _k(self):
        """Tokens one decode step may commit per slot (1 for the plain
        engine, draft_k for a speculative one)."""
        return int(getattr(self.engine, "tokens_per_step", 1) or 1)

    def _may_dispatch(self, req):
        """Whether a running request should ride the next decode step.
        Plain engines: stop once the whole budget is dispatched (each
        step is exactly one token). Speculative engines advance a slot
        by a device-side VARIABLE 1..k tokens the host only learns at
        retirement, so the gate is the upper bound: dispatch while even
        full acceptance of everything in flight could not finish the
        budget — this also caps context overshoot at one round past the
        budget, which is what the admission reservation slack covers."""
        if req.done:
            return False
        k = self._k
        if k <= 1:
            return req._dispatched < req.max_new_tokens
        return req._ub < req.max_new_tokens

    def step(self):
        """One scheduler tick: evict blown deadlines, retire finished
        slots, admit what fits, dispatch one decode step. Returns True
        while there is (or was) work."""
        now = self._now()
        self.steps += 1
        self._evict_deadlines(now)
        self._reap_finished(now)
        self._admit(now)
        meta = tuple((s, r) for s, r in sorted(self._slot_req.items())
                     if self._may_dispatch(r))
        k = self._k
        if k > 1:
            # a speculative engine advances EVERY device-active slot
            # each round, so the active mask must mirror the dispatch
            # set exactly: a gated slot left active would commit tokens
            # the host never attributes (silent stream corruption)
            dispatch = {s for s, _ in meta}
            for slot in self._slot_req:
                if slot in dispatch:
                    self.engine.activate(slot)
                else:
                    self.engine.deactivate(slot)
        if meta:
            self.engine.decode_step(meta=meta)
            for _, r in meta:
                r._dispatched += 1
                r._inflight += 1
                r._ub += k
        elif self._slot_req:
            # every occupied slot is gated on deferred results (budget
            # possibly complete): force the reads — the in-flight
            # window if rounds are pending, else the prefill-sampled
            # first token — so the host learns the true advances and
            # either finishes the requests or resumes dispatching
            if self.engine.window.pending:
                self.engine.flush()
            else:
                for req in list(self._slot_req.values()):
                    req._take_first(now)
                    req._ub = len(req.output_tokens) \
                        + req._inflight * self._k
                self._reap_finished(now)
        return bool(meta or self._queue or self._slot_req)

    def run(self, max_steps=100000):
        """Drive until the queue and every slot drain (or the step
        bound trips); flushes the window and returns ``completed``. An
        unhandled exception in the serve loop leaves a diagnostics
        post-mortem (when the layer is armed) before propagating."""
        try:
            while (self._queue or self._slot_req) \
                    and self.steps < int(max_steps):
                self.step()
            self.drain()
        except Exception as e:  # noqa: BLE001 — dump, then propagate
            self._diag.maybe_postmortem(
                "serve_loop:%s" % type(e).__name__)
            raise
        return self.completed

    def drain(self):
        """Barrier: retire every in-flight step, materialize pending
        first tokens, finalize what completed."""
        self.engine.flush()
        now = self._now()
        for r in list(self._slot_req.values()):
            r._take_first(now)
        self._reap_finished(now)

    def cancel(self, request):
        """Force-evict one request — queued or running — freeing its
        slot and pages: the fleet router's hedge-loser and
        drain-migration hook. Rides the deadline-eviction bookkeeping
        (same ``outcome="evicted"`` accounting, same mid-window safety:
        in-flight steps still attribute through their metadata and the
        late tokens are discarded). Idempotent; returns True when the
        request was live here."""
        if request.done:
            return False
        hit = False
        try:
            self._queue.remove(request)
            hit = True
        except ValueError:
            pass
        for slot, req in list(self._slot_req.items()):
            if req is request:
                self.engine.release(slot)
                del self._slot_req[slot]
                hit = True
        if not hit:
            return False
        request.state = "evicted"
        request.t_finish = self._now()
        self._finalize(request, "evicted")
        _m.queue_depth().set(len(self._queue))
        _m.active_requests().set(len(self._slot_req))
        return True

    # -- internals --------------------------------------------------------
    def _free_slots(self):
        return [s for s in range(self.engine.slots)
                if s not in self._slot_req]

    def _evict_deadlines(self, now):
        for slot, req in list(self._slot_req.items()):
            if req.deadline is not None and not req.done \
                    and now - req.t_submit > req.deadline:
                req.state = "evicted"
                req.t_finish = now
                self.engine.release(slot)
                del self._slot_req[slot]
                self._finalize(req, "evicted")
        kept = collections.deque()
        while self._queue:
            req = self._queue.popleft()
            if req.deadline is not None \
                    and now - req.t_submit > req.deadline:
                req.state = "evicted"
                req.t_finish = now
                self._finalize(req, "evicted")
            else:
                kept.append(req)
        self._queue = kept
        _m.queue_depth().set(len(self._queue))
        _m.active_requests().set(len(self._slot_req))

    def _quota_done(self, req):
        """Slot-release test. Plain engines may release the slot the
        step the budget is DISPATCHED (1 token/step — the tail rows
        attribute through metadata). A speculative slot's advance is
        variable, so only observed completion releases it."""
        if req.done:
            return True
        return self._k <= 1 and req._dispatched >= req.max_new_tokens

    def _reap_finished(self, now):
        """Release slots whose request finished — by observed completion
        (EOS) or by dispatch quota (every budgeted token is at least in
        flight; the remaining rows attribute through step metadata)."""
        for slot, req in list(self._slot_req.items()):
            if self._quota_done(req):
                req._take_first(now)  # covers max_new_tokens == 1
                self.engine.release(slot)
                del self._slot_req[slot]
                if req.done:
                    self._finalize(req, req.state)
                # else: quota dispatched, tail tokens still in flight —
                # completion lands via step metadata at retirement
        _m.active_requests().set(len(self._slot_req))

    def _pick_admit_index(self):
        """Index of the next queued request to admit: the best (lowest)
        priority class, FIFO within a class — so an interactive arrival
        overtakes queued bulk, but never an older interactive one. With
        uniform priorities (the no-QoS deployment) this is index 0,
        identical to the historical pure-FIFO admit."""
        best_i = 0
        best_p = self._queue[0].priority
        for i, req in enumerate(self._queue):
            if req.priority < best_p:
                best_i, best_p = i, req.priority
        return best_i

    def _preempt_for(self, req, now):
        """Free capacity for ``req`` by force-evicting one RUNNING
        victim of a strictly worse (higher-numbered) priority class —
        most-bulk first, latest-submitted within a class (least sunk
        work). The victim leaves through the deadline-eviction
        machinery but in its own ``preempted`` state, which the fleet
        router treats as non-terminal: the copy re-enqueues through the
        PR 11 idempotent-failover path and replays token-exact later —
        late, never lost. Returns True when a victim was evicted."""
        victims = [(s, r) for s, r in self._slot_req.items()
                   if not r.done and r.priority > req.priority]
        if not victims:
            return False
        victims.sort(key=lambda sr: (sr[1].priority,
                                     sr[1].t_submit or 0.0))
        slot, victim = victims[-1]
        self.engine.release(slot)
        del self._slot_req[slot]
        victim.state = "preempted"
        victim.t_finish = now
        self._finalize(victim, "preempted")
        _m.tenant_preempted_total().labels(
            victim.tenant or "default").inc()
        _m.active_requests().set(len(self._slot_req))
        return True

    def _admit(self, now):
        while self._queue:
            i = self._pick_admit_index()
            req = self._queue[i]
            if not self._free_slots():
                # slot pressure: a top-class arrival may preempt a
                # strictly lower class out of its slot; equal-priority
                # traffic waits exactly as before
                if not self._preempt_for(req, now):
                    break
                continue  # re-evaluate with the freed slot/pages
            total = len(req.prompt) + req.max_new_tokens
            # a handoff request adopts shipped pages — no prefix
            # discount applies, so gate on the plain reservation
            prompt = None if req._handoff is not None else req.prompt
            if not self.engine.can_admit(total, prompt=prompt):
                # page pressure: same preemption rule as slot pressure
                if not self._preempt_for(req, now):
                    break  # pages busy; retiring traffic will free them
                continue
            del self._queue[i]
            slot = self._free_slots()[0]
            req.t_admit = now
            _m.request_latency().labels("queue").observe(
                max(0.0, now - req.t_submit))
            _trace_span(req, "queue", req.t_submit, now, now)
            if req._handoff is not None:
                # disaggregated path: install the prefill replica's
                # shipped pages; the first token rode the wire as a
                # host int — zero prefill work, nothing deferred
                payload, tok0 = req._handoff
                self.engine.adopt(slot, req.id, len(req.prompt),
                                  req.max_new_tokens, payload, tok0)
                req._handoff = None
                req._first_pv = None
                req.state = "running"
                req._record(int(tok0), now)  # may complete a 1-budget
            else:
                req._first_pv = self.engine.admit(
                    slot, req.id, req.prompt, req.max_new_tokens)
                req.state = "running"
            req._dispatched = 1  # the prefill-sampled token
            req._inflight = 0
            req._ub = 1
            self._slot_req[slot] = req
        _m.queue_depth().set(len(self._queue))
        _m.active_requests().set(len(self._slot_req))

    def _on_tokens(self, step_no, row, meta):
        """Engine retirement callback: one host token row + the step's
        composition metadata. Runs inside the window's deferred read —
        records only; slot recomposition stays in step(). The engine
        decodes the row (a speculative round carries a variable-length
        accepted prefix per slot; the plain engine exactly one token)."""
        del step_no
        self._diag.progress("serving_decode")
        now = self._now()
        k = self._k
        for slot, req in (meta or ()):
            req._take_first(now)
            was_done = req.done
            for tok in self.engine.decode_row(row, slot):
                req._record(int(tok), now)
            req._inflight = max(0, req._inflight - 1)
            req._ub = len(req.output_tokens) + req._inflight * k
            if req.state == "completed" and not was_done:
                self._finalize(req, "completed")

    def _finalize(self, req, outcome):
        if req._finalized:
            return
        req._finalized = True
        _m.requests_total().labels(outcome).inc()
        if outcome in ("evicted", "rejected", "preempted"):
            now = self._now()
            _trace_span(req, outcome, req.t_submit,
                        req.t_finish if req.t_finish is not None
                        else now, now)
            # SLO misses ride the flight recorder: a post-mortem shows
            # WHICH requests were shed in the run-up to an incident
            self._diag.record_event(
                "request_" + outcome, request_id=req.id,
                prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens,
                deadline=req.deadline)
        if outcome == "completed" and req.t_first is not None \
                and req.t_finish is not None:
            _m.request_latency().labels("decode").observe(
                max(0.0, req.t_finish - req.t_first))
        self.completed.append(req)


class StaticBatcher(ContinuousBatcher):
    """The padded-batch baseline: admission happens ONLY at batch
    boundaries. A batch of mixed-length requests runs until its longest
    member finishes; short members' slots sit deactivated (no useful
    work, pages still held) — the cost continuous batching removes.
    Same engine, same requests, same metrics: the A/B's other leg."""

    def _admit(self, now):
        if self._slot_req:
            return  # batch in flight: the door is closed
        super()._admit(now)

    def _reap_finished(self, now):
        items = list(self._slot_req.items())
        if not items:
            return
        finished = []
        for slot, req in items:
            if self._quota_done(req):
                self.engine.deactivate(slot)  # idle, not released
                finished.append((slot, req))
        if len(finished) == len(items):  # batch boundary: release all
            super()._reap_finished(now)
        else:
            _m.active_requests().set(len(self._slot_req))
