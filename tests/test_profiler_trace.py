"""``profiler_trace.aggregate``: the xplane decoder against the two traces
recorded on the chip, the attribution rules on a hand-built trace, and what
``mx.profiler`` and ``tuning.compile_stats`` grew around it."""
import os
import struct
import sys

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, profiler_trace as pt, tuning

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLD_FIXTURE = os.path.join(REPO, "benchmark", "tests", "data", "small.xplane.pb")
NEW_FIXTURE = os.path.join(os.path.dirname(__file__), "data", "scoped_step.xplane.pb")


# -- a tiny encoder, the decoder's inverse, for hand-built traces -----------
def _v(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field, value):
    if isinstance(value, int):
        return _v(field << 3) + _v(value)
    if isinstance(value, float):
        return _v(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _v(field << 3 | 2) + _v(len(value)) + value


def _plane(name, lines, metadata, stat_names=("tf_op", "hlo_category", "run_id",
                                                "device_ordinal", "flops")):
    """lines: {line name: [(metadata id, start_ps, dur_ps, {stat: value})]};
    metadata: {id: (name, display_name, {stat: value})}."""
    sid = {n: i + 1 for i, n in enumerate(stat_names)}

    def stat(k, v):
        body = _f(1, sid[k])
        body += _f(5, v) if isinstance(v, str) else _f(2, v) if isinstance(v, float) \
            else _f(4, v)
        return body

    buf = _f(2, name)
    for lname, events in lines.items():
        line = _f(2, lname) + _f(3, 1)  # timestamp_ns = 1: a base of 1000 ps
        for mid, start, dur, st in events:
            ev = _f(1, mid) + _f(2, start) + _f(3, dur)
            for k, v in st.items():
                ev += _f(4, stat(k, v))
            line += _f(4, ev)
        buf += _f(3, line)
    for mid, (mname, display, st) in metadata.items():
        m = _f(1, mid) + _f(2, mname) + _f(4, display)
        for k, v in st.items():
            m += _f(5, stat(k, v))
        buf += _f(4, _f(1, mid) + _f(2, m))
    for n, i in sid.items():
        buf += _f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n)))
    return _f(1, buf)


US = 1_000_000  # picoseconds


def _benchmark_reduction():
    """``benchmark/harness/trace_reduce.py``, whose ``busy_s`` the result line
    carries: ``aggregate`` is held to it."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from harness import trace_reduce
    finally:
        sys.path.pop(0)
    return trace_reduce


@pytest.fixture
def built(tmp_path):
    """Two devices running the same 100 us 'step': forward 30, a loop of 40
    whose body (two of 15) is backward under attention_bwd, a kernel of 10
    inside the forward, an all-reduce of 5, an update of 10, a copy of 5;
    launched at host 1000 us where the device clocks read 200 us less."""
    meta = {
        1: ("%fusion.1 = f32[8] fusion(...)", "fusion.1",
            {"tf_op": "jit(step)/jvp(forward)/net/dense0/dot_general:",
             "hlo_category": "convolution fusion", "flops": 1000}),
        2: ("%while.2 = (...) while(...)", "while.2",
            {"tf_op": "jit(step)/transpose(jvp(forward))/net/attention_bwd/while:",
             "hlo_category": "while"}),
        3: ("%fusion.3 = f32[8] fusion(...)", "fusion.3",
            {"tf_op": "jit(step)/transpose(jvp(forward))/net/attention_bwd/while/body/"
                      "dot_general:", "hlo_category": "convolution fusion"}),
        4: ('%flash_attention_fwd.7 = f32[8] custom-call(...), '
            'custom_call_target="tpu_custom_call"', "flash_attention_fwd.7",
            {"tf_op": "jit(step)/jvp(forward)/net/attention/flash_attention_fwd/"
                      "pallas_call:", "hlo_category": "custom-call"}),
        5: ("%all-reduce-start.1 = f32[8] all-reduce-start(...)", "all-reduce-start.1",
            {"tf_op": "jit(step)/transpose(jvp(forward))/net/dense0/dot_general:",
             "hlo_category": "all-reduce"}),
        6: ("%fusion.6 = f32[8] fusion(...)", "fusion.6",
            {"tf_op": "jit(step)/optimizer/sub:", "hlo_category": "loop fusion"}),
        7: ("%copy.7 = f32[8] copy(...)", "copy.7", {"hlo_category": "data formatting"}),
        8: ("jit_step(123)", "", {}),
    }
    ops = [(1, 800 * US, 30 * US, {}), (4, 830 * US, 10 * US, {}),
           (2, 840 * US, 40 * US, {}), (3, 842 * US, 15 * US, {}),
           (3, 860 * US, 15 * US, {}), (5, 880 * US, 5 * US, {}),
           (6, 885 * US, 10 * US, {}), (7, 895 * US, 5 * US, {}),
           # a second step after 300 us of idle, the same shape
           (1, 1200 * US, 30 * US, {}), (6, 1230 * US, 10 * US, {})]
    modules = [(8, 800 * US, 100 * US, {"run_id": 41}), (8, 1200 * US, 40 * US, {"run_id": 42})]
    host_meta = {1: ("DoEnqueueProgram", "", {}), 2: ("mxt.step.dispatch", "", {}),
                 3: ("mxt.window.retire", "", {}), 4: ("$python_call", "", {})}
    host = {"main": [(2, 990 * US, 20 * US, {}), (1, 1000 * US, 2 * US,
                                                 {"run_id": 41, "device_ordinal": 0}),
                     (3, 1010 * US, 380 * US, {}), (4, 1011 * US, 1 * US, {}),
                     (2, 1390 * US, 15 * US, {}), (1, 1395 * US, 2 * US,
                                                  {"run_id": 42, "device_ordinal": 0})]}
    data = b"".join([
        _plane("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}, meta),
        _plane("/device:TPU:1", {"XLA Modules": modules, "XLA Ops": ops}, meta),
        _plane("/host:CPU", host, host_meta)])
    path = tmp_path / "built.xplane.pb"
    path.write_bytes(data)
    return str(path)


def test_every_moment_falls_in_one_phase(built):
    agg = pt.aggregate(built)
    assert agg["devices"] == 2
    us = {k: round(v * 1e6, 6) for k, v in agg["phase_s"].items()}
    # the loop's 40 us are its body's 30 (backward) and 10 of its own, also backward
    assert us == {"forward": 70.0, "backward": 40.0, "optimizer": 20.0, "grad_post": 0.0,
                  "collective": 5.0, "other": 5.0}
    assert sum(agg["phase_s"].values()) == pytest.approx(agg["busy_s"], abs=1e-12)
    assert agg["busy_s"] == pytest.approx(140e-6)
    assert agg["named_s"]["attention_bwd"] == pytest.approx(40e-6)
    assert agg["named_s"]["attention"] == pytest.approx(10e-6)
    assert agg["kernel_s"] == {"flash_attention_fwd": pytest.approx(10e-6)}
    assert agg["kernel_calls"] == {"flash_attention_fwd": 1}
    assert agg["scope_s"]["forward/net"] == pytest.approx(115e-6)  # depth 2
    assert pt.aggregate(built, depth=3)["scope_s"]["forward/net/dense0"] == \
        pytest.approx(65e-6)
    assert agg["category_s"]["while"] == pytest.approx(10e-6)
    assert agg["flops"] == 2000


def test_clock_offset_and_named_gaps(built):
    agg = pt.aggregate(built)
    # device start 800 (+1000 ps base) against the launch at 1000: least of two pairs
    assert agg["clock_offset_us"] == pytest.approx(-200.0)
    assert agg["launch_pairs"] == 2
    # the 300 us gap starts at device 900 = host 1100: inside mxt.window.retire
    assert agg["idle_gaps"] == [["mxt.window.retire", pytest.approx(300e-6)]]
    table = pt.format_table(agg)
    assert "clock_offset_us -200.000" in table and "under mxt.window.retire" in table
    assert "flash_attention_fwd" in table and "forward/net" in table


def test_idle_time_summed_by_span_and_host_seconds_by_name(tmp_path):
    """One device, busy 0-100, 400-500, 530-600, 900-1000 and 1300-1400 us on
    a clock 200 us behind the host's: two gaps start under the dispatching
    thread's ``mxt.data.wait`` (300 and 300 us), one under no span of that
    thread (300 us: a worker's ``mxt.data.put`` covers its start and a
    worker's ``mxt.data.decode`` the first gap's, and neither names a gap
    while the trace says which thread dispatches), and one of 30 us is
    shorter than ``GAP_US``."""
    meta = {1: ("%fusion.1 = f32[8] fusion(...)", "fusion.1",
                {"tf_op": "jit(step)/jvp(forward)/net/dot_general:",
                 "hlo_category": "convolution fusion"}),
            8: ("jit_step(123)", "", {})}
    ops = [(1, 0, 100 * US, {}), (1, 400 * US, 100 * US, {}), (1, 530 * US, 70 * US, {}),
           (1, 900 * US, 100 * US, {}), (1, 1300 * US, 100 * US, {})]
    modules = [(8, 0, 100 * US, {"run_id": 7})]
    names = ("tf_op", "hlo_category", "run_id", "device_ordinal", "n", "batch")
    host_meta = {1: ("DoEnqueueProgram", "", {}), 2: ("mxt.data.wait", "", {}),
                 3: ("mxt.data.decode", "", {}), 4: ("mxt.data.got", "", {}),
                 5: ("mxt.data.put", "", {}), 6: ("mxt.step.dispatch", "", {})}
    main = [(1, 200 * US, 2 * US, {"run_id": 7, "device_ordinal": 0}),
            (2, 250 * US, 340 * US, {"n": 1}),        # device 50-390
            (4, 590 * US, 0, {"n": 1, "batch": "0:3:0"}),
            (2, 790 * US, 100 * US, {"n": 2})]        # device 590-690
    worker = [(3, 100 * US, 1050 * US, {"batch": "0:3:0"}),   # device -100-950
              (5, 1190 * US, 20 * US, {"batch": "0:3:0"}),    # device 990-1010
              (3, 1700 * US, 100 * US, {"batch": "0:4:0"})]   # past the window

    def aggregate(dispatch):
        host = {"main": main + [(6, 195 * US, 10 * US, {})] * dispatch, "worker": worker}
        path = tmp_path / ("idle%d.xplane.pb" % dispatch)
        path.write_bytes(
            _plane("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}, meta, names)
            + _plane("/host:CPU", host, host_meta, names))
        return pt.aggregate(str(path)), str(path)

    agg, path = aggregate(1)
    assert agg["clock_offset_us"] == pytest.approx(-200.0)
    us = {k: round(v * 1e6, 6) for k, v in agg["idle_by_span_s"].items()}
    assert us == {"mxt.data.wait": 600.0, "(no span)": 300.0, "(short)": 30.0}
    assert list(us) == ["mxt.data.wait", "(no span)", "(short)"]  # largest first
    assert sum(agg["idle_by_span_s"].values()) == \
        pytest.approx(agg["window_s"] - agg["busy_s"], abs=1e-12)
    assert agg["idle_gaps"] == [[n, pytest.approx(300e-6)] for n in
                                ("mxt.data.wait", "mxt.data.wait", "(no span)")]
    # the window is device 0-1400 us, host 200-1600: the worker's first span is
    # clipped to it, its last lies outside, and one of no length is a call
    assert agg["host_span_s"] == {
        "mxt.data.decode": pytest.approx(950e-6), "mxt.data.got": 0.0,
        "mxt.data.put": pytest.approx(20e-6), "mxt.data.wait": pytest.approx(440e-6),
        "mxt.step.dispatch": pytest.approx(5e-6)}
    assert agg["host_span_calls"] == {"mxt.data.decode": 1, "mxt.data.got": 1,
                                      "mxt.data.put": 1, "mxt.data.wait": 2,
                                      "mxt.step.dispatch": 1}
    spans = pt.host_spans(path)
    assert agg["spans"] == spans  # read once: the aggregate carries them
    assert [(sp[0], sp[3]) for sp in spans if sp[0] == "mxt.data.got"] == \
        [("mxt.data.got", {"n": 1, "batch": "0:3:0"})]
    threads = {sp[0]: sp[4] for sp in spans}
    assert threads["mxt.data.wait"] == threads["mxt.step.dispatch"] != threads["mxt.data.put"]
    assert pt.span_totals(spans)[1]["mxt.data.decode"] == 2  # no window: all of them
    table = pt.format_table(agg)
    assert "idle in all   0.600 ms under mxt.data.wait" in table
    assert "host span     0.950 ms in      1 of mxt.data.decode" in table
    # a trace that does not say which thread dispatches: every thread's spans name
    us = {k: round(v * 1e6, 6) for k, v in aggregate(0)[0]["idle_by_span_s"].items()}
    assert us == {"mxt.data.wait": 600.0, "mxt.data.put": 300.0, "(short)": 30.0}


@pytest.mark.parametrize("fixture, window", [
    (OLD_FIXTURE, "bench.trace_window"), (OLD_FIXTURE, None),
    (NEW_FIXTURE, "bench.trace_window"), (NEW_FIXTURE, None)])
def test_idle_by_span_adds_up_on_the_chip_traces(fixture, window):
    agg = pt.aggregate(fixture, window=window)
    idle = agg["idle_by_span_s"]
    assert sum(idle.values()) == pytest.approx(agg["window_s"] - agg["busy_s"], abs=1e-9)
    # the ten named gaps are part of the sum under their names
    for name in {n for n, _ in agg["idle_gaps"]}:
        assert idle[name] >= sum(d for n, d in agg["idle_gaps"] if n == name) - 1e-12
    assert set(agg["host_span_s"]) == set(agg["host_span_calls"])
    assert all(n.startswith("mxt.") for n in agg["host_span_s"])


def test_window_clips_like_the_benchmark(built):
    agg = pt.aggregate(built, window="mxt.window.retire")  # host 1010-1390, unshifted
    assert agg["busy_s"] == pytest.approx(40e-6)  # the second step alone
    assert agg["window_s"] == pytest.approx(380e-6)


@pytest.mark.parametrize("tf_op, scopes, phase", [
    ("jit(work)/dot_general:", [], "other"),
    ("jit(step)/jvp(forward)/resnetv10/stage1/batchnorm0/batchnorm/mul:",
     ["forward", "resnetv10", "stage1", "batchnorm0", "batchnorm"], "forward"),
    ("jit(step)/transpose(jvp(forward))/net/stem/batchnorm0/batchnorm_bwd/mul",
     ["forward", "net", "stem", "batchnorm0", "batchnorm_bwd"], "backward"),
    ("jit(step)/jvp(forward)/loss0/jit(log_softmax)/reduce_max", ["forward", "loss0"],
     "forward"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/forward/while/body/"
     "closed_call/cell/cos", ["forward", "cell"], "backward"),
    ("jit(step)/optimizer/mul", ["optimizer"], "optimizer"),
    ("jit(step)/grad_post/is_finite", ["grad_post"], "grad_post"),
    ("jit(step)/jvp(forward/net)/conv0/conv_general_dilated", ["forward", "net", "conv0"],
     "forward"),
    ("", [], "other"),
])
def test_scopes_and_phase_of_a_name_stack(tf_op, scopes, phase):
    assert pt.scopes_of(tf_op) == scopes
    assert pt.phase_of("fusion.1", tf_op) == phase
    assert pt.phase_of("%all-reduce-done.3", tf_op) == "collective"


def test_old_chip_trace_metadata_and_busy_time():
    """The trace PR 24 recorded: ``tf_op`` on the four fusions, the sum equal to
    the benchmark's own reduction, the clocks paired by ``run_id``."""
    trace_reduce = _benchmark_reduction()
    (dev,) = [p for p in pt.read_planes(OLD_FIXTURE) if p.name == "/device:TPU:0"]
    fusions = [m for m in dev.metadata.values() if m["display_name"].startswith("fusion")]
    assert len(fusions) == 4
    assert {m["tf_op"] for m in fusions} == {"jit(work)/dot_general:"}
    assert {m["hlo_category"] for m in fusions} == {"convolution fusion"}
    agg = pt.aggregate(OLD_FIXTURE, window="bench.trace_window")
    ref = trace_reduce.reduce_planes(trace_reduce.read_planes(OLD_FIXTURE))
    assert agg["busy_s"] == pytest.approx(ref["busy_s"], abs=1e-6)
    assert sum(agg["phase_s"].values()) == pytest.approx(ref["busy_s"], abs=1e-6)
    assert agg["window_s"] == pytest.approx(ref["window_s"], abs=1e-9)
    assert agg["phase_s"]["other"] == pytest.approx(agg["busy_s"])  # no scope in it
    assert agg["category_s"]["convolution fusion"] == pytest.approx(0.003971154, abs=1e-8)
    assert agg["launch_pairs"] == 12
    assert agg["clock_offset_us"] == pytest.approx(-1395.169, abs=1e-3)
    assert len(agg["idle_gaps"]) == 10 and agg["idle_gaps"][0][0] == "bench.wait"


def test_scoped_step_recorded_on_the_chip():
    """``tests/data/scoped_step.xplane.pb`` (my chip run, PR 25, call 2;
    ``tools/record_scoped_trace.py``): three launches on a TPU v5e of a tiny
    step with ``value_and_grad``, a phase, blocks, a ``custom_vjp`` op with
    its ``_bwd`` half, a loop of four and the Pallas kernel ``scale_rows``."""
    assert os.path.getsize(NEW_FIXTURE) < 100_000
    agg = pt.aggregate(NEW_FIXTURE, depth=3)
    assert agg["devices"] == 1
    assert agg["busy_s"] == pytest.approx(0.000315207342, abs=1e-12)
    us = {k: round(v * 1e6, 3) for k, v in agg["phase_s"].items()}
    assert us == {"forward": 112.04, "backward": 181.006, "optimizer": 4.152,
                  "grad_post": 0.0, "collective": 0.0, "other": 18.009}
    assert sum(agg["phase_s"].values()) == pytest.approx(agg["busy_s"], abs=1e-12)
    # the kernel under its own name, once a launch
    assert agg["kernel_s"] == {"scale_rows": pytest.approx(1.76125e-06)}
    assert agg["kernel_calls"] == {"scale_rows": 3.0}
    # the loop's body nests in the loop's event: twelve calls in three launches, and
    # the ``while`` itself keeps 0.3 % of the time
    loop_ops = [o for o in agg["ops"] if o["scope"] == "forward/net/loop0"]
    assert {o["calls"] for o in loop_ops} == {12.0}
    assert agg["category_s"]["while"] == pytest.approx(9.2e-07, abs=1e-7)
    # the custom_vjp's backward half is backward by its scope's name
    assert agg["named_s"]["layernorm_bwd"] == pytest.approx(7.651484e-06)
    assert agg["named_s"]["layernorm"] == pytest.approx(3.57375e-06)
    bwd = [o for o in agg["ops"] if o["scope"].endswith("layernorm_bwd")]
    assert bwd and all(o["phase"] == "backward" for o in bwd)
    assert agg["scope_s"]["forward/net/loop0"] == pytest.approx(0.000228111172)
    assert agg["kind_s"]["dense"] == pytest.approx(4.250375e-05)
    # what the compiler adds for itself has no name: zero broadcasts, prefetches
    assert {o["name"] for o in agg["ops"] if o["phase"] == "other"} >= {"broadcast.4"}
    assert agg["flops"] == pytest.approx(55232699901.0)
    # the clocks, and the gaps under the program's own span
    assert agg["launch_pairs"] == 3
    assert agg["clock_offset_us"] == pytest.approx(-1150.148, abs=1e-3)
    assert [g[0] for g in agg["idle_gaps"]] == ["mxt.window.retire"] * 2
    assert agg["idle_gaps"][0][1] == pytest.approx(0.003845093594)
    # clipped to the window's span it is the benchmark's own busy time
    trace_reduce = _benchmark_reduction()
    ref = trace_reduce.reduce_planes(trace_reduce.read_planes(NEW_FIXTURE))
    clipped = pt.aggregate(NEW_FIXTURE, window="bench.trace_window")
    assert clipped["busy_s"] == pytest.approx(ref["busy_s"], abs=1e-6)
    assert "scale_rows" in pt.format_table(agg)


def test_no_trace_no_table(tmp_path):
    assert pt.aggregate(str(tmp_path)) is None
    assert "no device operations" in pt.format_table(None)


def test_dumps_prints_the_device_table_after_a_trace(tmp_path):
    """``set_state('run')`` / ``'stop'`` / ``dumps()``: the MXNet behaviour. On
    the CPU the trace holds no device plane and the table says so."""
    profiler.set_config(filename=str(tmp_path / "prof"))
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    before = profiler.dumps()
    assert "Device Statistics" not in before or "prof" not in before
    profiler.set_state("run")
    try:
        f(jnp.ones(8)).block_until_ready()
    finally:
        profiler.set_state("stop")
    out = profiler.dumps()
    assert "Profile Statistics:" in out and "setup.import" in out
    assert "Device Statistics" in out
    assert profiler.aggregate() is None or "phase_s" in profiler.aggregate()
    profiler.set_config(filename="profile_output")


def test_setup_scopes_are_exclusive_and_add_up():
    import time

    before = profiler.setup_seconds()
    t0 = time.perf_counter()
    with profiler.setup_scope("infer_shapes"):
        time.sleep(0.02)
        with profiler.setup_scope("initialize"):
            time.sleep(0.03)
    total = time.perf_counter() - t0
    after = profiler.setup_seconds()
    d = {k: after[k] - before.get(k, 0.0) for k in ("infer_shapes", "initialize")}
    assert d["initialize"] >= 0.03 and 0.02 <= d["infer_shapes"] < 0.03 + 0.015
    assert d["initialize"] + d["infer_shapes"] == pytest.approx(total, abs=5e-3)


def test_program_names_its_setup_phases():
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn

    before = profiler.setup_seconds()
    assert before["import"] > 0
    net = nn.HybridSequential(prefix="setup_")
    with net.name_scope():
        net.add(nn.Dense(8), nn.Dense(2))  # in_units deferred
    net.initialize()
    net.cast("float16")
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = tr.fuse_step(net, gluon.loss.L2Loss())
    x, y = nd.ones((4, 6), dtype="float16"), nd.ones((4, 2), dtype="float16")
    step(x, y, batch_size=4).wait_to_read()
    for p in net.collect_params().values():
        p.set_data(p.data())
    after = profiler.setup_seconds()
    for phase in ("initialize", "place", "infer_shapes", "step_build"):
        assert after.get(phase, 0.0) > before.get(phase, 0.0), phase
    again = profiler.setup_seconds()
    step(x, y, batch_size=4).wait_to_read()
    assert profiler.setup_seconds()["step_build"] == again["step_build"]  # once


def test_compile_stats_keeps_its_keys_and_names_functions():
    def a_function_to_find(x):
        return jnp.tanh(x) * 3

    x = jnp.ones(5)  # its own small program, before the snapshot
    before = tuning.compile_stats()
    jax.jit(a_function_to_find)(x).block_until_ready()
    after = tuning.compile_stats()
    for key in ("compiles", "compile_seconds", "trace_seconds", "cache_hits",
                "cache_misses"):
        assert key in after
    assert after["compiles"] == before["compiles"] + 1
    assert after["small_compiles"] == before["small_compiles"] + 1
    assert after["lower_seconds"] > before["lower_seconds"]
    assert after["trace_seconds"] > before["trace_seconds"]
    assert len(after["by_function"]) <= 10
    assert all(set(row) == {"name", "compiles", "seconds"} for row in after["by_function"])
    from mxnet_tpu.tuning import compile_cache

    with compile_cache._lock:
        compiles, seconds = compile_cache._by_function["a_function_to_find"]
    assert compiles == 1 and seconds > 0  # trace, lower and compile under one name
