"""Runner for training cells: one compiled step with its state, driven from
the seed through its first steps (which the plain reference follows), then
through the measured window, then compared.

Order of a run:

1. set-up: seeded weights and a pool of seeded batches on the device (the
   reference's own generators), the program built around them, the first
   steps through the window's own call with a host read of each loss, the
   first gradient read back from the optimizer's state after step one and
   taken to the host once its norms are read, the parameters' change after the
   last; two more steps that must compile nothing;
2. the window: steps dispatched as the entry point dispatches them, at most
   ``max_inflight`` ahead of the last one waited for, closed by one wait on
   the last step; with ``--trace 1`` the last ``trace_seconds`` of it are a
   window of their own under the profiler;
3. after the window: the program's own counts are read, the program is
   freed, the reference follows the same first batches in float32, and every
   number compared is printed beside its limit.
"""
from __future__ import annotations

import collections
import gc
import math
import time

from harness import compare, device, program, stats, trace_reduce, train_reference
from harness.loader import load_module
from harness.spans import span


def _drive(prog, batches, seconds, start_index, max_inflight):
    """Dispatch steps for ``seconds``, then wait for the last. Returns the
    steps, the seconds from the first dispatch to the last step's end, the
    losses (device arrays), the program's counters over that time, and the
    host's clock at the end of every step that was waited for."""
    inflight = collections.deque()
    losses, ends = [], []
    c0 = program.counters()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = start_index
    while time.perf_counter() < deadline:
        with span("dispatch_step"):
            loss = prog.step(batches[i % len(batches)])
        losses.append(loss.data)
        inflight.append(loss)
        i += 1
        if len(inflight) > max_inflight:
            with span("wait"):
                prog.wait(inflight.popleft())
            ends.append(time.perf_counter() - t0)
    with span("wait"):
        prog.wait(inflight[-1])
    elapsed = time.perf_counter() - t0
    return {"steps": i - start_index, "seconds": elapsed, "losses": losses, "ends": ends,
            "counters": program.delta(program.counters(), c0), "next_index": i}


def step_time_tail(ends, ahead):
    """Where a window's time went: the host's clock between the ends of steps
    ``ahead`` apart (the steps in flight; an entry point that retires its steps
    in groups makes single gaps uneven, spans of that many even), in
    milliseconds a step. A stall of the device, or one of the host longer than
    the queue ahead, shows as one long span and when it ended."""
    spans = [(b - a) / ahead for a, b in zip(ends, ends[ahead:])]
    if not spans:
        return {}
    longest = max(range(len(spans)), key=spans.__getitem__)
    median = stats.percentile(spans, 50)
    return {"span_steps": ahead,
            "span_step_ms_median": 1e3 * median,
            "span_step_ms_p99": 1e3 * stats.percentile(spans, 99),
            "span_step_ms_longest": 1e3 * spans[longest],
            "longest_span_ends_at_s": ends[longest + ahead],
            "spans_over_1.05_medians": sum(v > 1.05 * median for v in spans)}


def first_steps(prog, batches, params, traffic):
    """The program's first steps through the window's own call, with what the
    comparison reads of them, and the compiles inside the step's own call from
    its second call on (there must be none)."""
    import jax

    first = {"losses": []}
    n_first = traffic["first_steps"]
    later_compiles = 0
    for i in range(n_first + traffic["warm_steps"]):
        c0 = program.counters()
        if i == 1:  # the step has run once: its next call's arguments are kept
            prog.record_next_step()
        loss = prog.step(batches[i % len(batches)])
        prog.wait(loss)
        if i > 0:
            later_compiles += program.delta(program.counters(), c0)["compiles"]
        if i < n_first:
            first["losses"].append(prog.loss_value(loss))
        if i == 0:
            gradient = prog.first_gradient()
            first["grad_norms"] = prog.norms(gradient)
            # to the host until the reference wants it: the window does not
            # share the chip with the yardstick's float32 copy
            t0 = time.perf_counter()
            first["first_gradient"] = jax.device_get(gradient)
            first["gradient_to_host_s"] = time.perf_counter() - t0
            del gradient
        if i == n_first - 1:
            first["delta_norms"] = prog.delta_norms(params)
    return first, later_compiles


def run(ctx):
    import jax
    import jax.numpy as jnp

    cell, config, traffic = ctx.cell, ctx.config, ctx.traffic
    devs = ctx.devices
    ref = load_module("references", config["family"])
    model = load_module("models", config["family"])
    flops = load_module("flops", config["family"])
    opt = train_reference.effective_optimizer(config, traffic)

    # -- 1. set-up ---------------------------------------------------------
    with span("build"):
        params = ref.init(config, ctx.seed)
        pool = ref.batches(config, traffic, ctx.seed)
        prog = model.build(config, traffic, params, devs, opt)
        batches = [prog.batch(x, y) for x, y in pool]
    ctx.say(phase="built", seconds=ctx.since_start(), **prog.describe)
    first, later_compiles = first_steps(prog, batches, params, traffic)
    index = traffic["first_steps"] + traffic["warm_steps"]
    n_first = traffic["first_steps"]
    fused = getattr(prog.entry, "fused", True)
    setup_counters = program.counters()  # the process's totals up to the window
    ctx.say(phase="first_steps", losses=first["losses"], fused=bool(fused),
            gradient_to_host_s=first.pop("gradient_to_host_s"),
            fallback_reason=getattr(prog.entry, "fallback_reason", None),
            setup_compiles=setup_counters["compiles"],
            setup_compile_seconds=setup_counters["compile_seconds"],
            cache_hits=setup_counters["cache_hits"],
            cache_misses=setup_counters["cache_misses"])

    # -- 2. the window -----------------------------------------------------
    setup_s = ctx.since_start()
    trace_s = float(traffic.get("trace_seconds", 4)) if ctx.trace else 0.0
    trace_s = min(trace_s, ctx.seconds / 2.0)
    win = _drive(prog, batches, ctx.seconds - trace_s, index, traffic["max_inflight"])
    traced = trace_dir = None
    if ctx.trace:
        trace_dir = ctx.trace_dir()
        jax.profiler.start_trace(trace_dir)
        try:
            with span("trace_window"):
                twin = _drive(prog, batches, trace_s, win["next_index"],
                              traffic["max_inflight"])
        finally:
            jax.profiler.stop_trace()
        traced = trace_reduce.reduce_trace(trace_dir)
        if traced is not None:
            traced["steps"] = twin["steps"]
        win["losses"] += twin["losses"]
        ctx.say(phase="traced", steps=twin["steps"], seconds=twin["seconds"],
                compiles=twin["counters"]["compiles"])
        win["counters"]["compiles"] += twin["counters"]["compiles"]
    losses = jax.device_get(jnp.stack([jnp.mean(l.astype(jnp.float32))
                                       for l in win["losses"]]))
    not_finite = int(sum(1 for v in losses if not math.isfinite(float(v))))
    chips = len(devs)
    rate = win["steps"] * prog.samples_per_step / win["seconds"] / chips
    with span("memory_analysis"):
        c0 = program.counters()
        share = device.program_share(prog.compiled_step())
        device.must_compile_nothing(program.delta(program.counters(), c0))
    peak_bytes = device.memory_peak_bytes(devs, share["beside_arguments"])
    ctx.say(phase="memory", allocator=device.memory_stats(devs), program=share,
            memory_peak_bytes=peak_bytes)
    step_ms = 1e3 * win["seconds"] / max(win["steps"], 1)
    ctx.say(phase="window", steps=win["steps"], seconds=win["seconds"],
            step_ms_mean=step_ms, samples_per_s_per_chip=rate,
            last_loss=float(losses[-1]), not_finite=not_finite, **win["counters"],
            **step_time_tail(win["ends"], traffic["max_inflight"]))

    # -- 3. after the window: free the program, follow with the reference ---
    zero_counts, published = prog.zero_counts(), prog.after_window()
    del prog, batches, model
    gc.collect()
    t_ref = time.perf_counter()
    with span("reference"):
        ref_first = train_reference.first_steps(
            ref, config, opt, params, pool, steps=n_first,
            program_gradient=first.pop("first_gradient"), devices=devs)
    rows = compare.judge(compare.training_numbers(first, ref_first), cell["limits"])
    rows.append({"compared": "compiles_after_first_step", "value": later_compiles,
                 "limit": 0, "ok": later_compiles == 0, "detail": ""})
    rows.append({"compared": "compiles_in_window", "value": win["counters"]["compiles"],
                 "limit": 0, "ok": win["counters"]["compiles"] == 0, "detail": ""})
    rows.append({"compared": "not_finite_losses", "value": not_finite, "limit": 0,
                 "ok": not_finite == 0, "detail": ""})
    rows.append({"compared": "fused_entry", "value": int(not fused), "limit": 0,
                 "ok": bool(fused), "detail": "the entry point fell back to eager"
                 if not fused else ""})
    for name, count in zero_counts.items():
        rows.append({"compared": name, "value": count, "limit": 0, "ok": count == 0,
                     "detail": "the program's own count over the run"})
    for row in rows:
        ctx.say(phase="compare", **row)
    ctx.say(phase="reference", seconds=time.perf_counter() - t_ref)

    run_facts = {
        "cell": cell, "config": config, "traffic": traffic, "chips": chips,
        "device_kind": devs[0].device_kind,
        "window": {"steps": win["steps"], "seconds": win["seconds"],
                   "samples_per_s_per_chip": rate, **win["counters"]},
        "setup": setup_counters,
        "train_flops_per_sample": flops.train_flops_per_sample(config, traffic),
        "trace": traced, "trace_dir": trace_dir, "program": published,
    }
    return {
        "correct": all(r["ok"] for r in rows),
        "compared": rows,
        "attempted": win["steps"],
        "failed": not_finite,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "facts": run_facts,
        "memory_peak_bytes": peak_bytes,
        "trace": traced,
    }
