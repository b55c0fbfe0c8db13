"""Whole runs on the CPU at the cells' tiny stand-ins: the last line's keys,
files added by a later PR found by name, the timed path broken underneath, and
the control."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from harness import loader

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
RUN = os.path.join(loader.BENCH_DIR, "run.py")


def rehearse(cell, trace, seed=7, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600, cwd=loader.REPO_DIR)
    assert p.returncode == 3, p.stderr[-2000:]  # a rehearsal is never a result
    line = json.loads(p.stdout.strip().splitlines()[-1])
    # every number compared beside its limit: the line's last key, and the last
    # lines of standard error
    assert list(line)[-1] == "compared" and "loss_gap.step1" in line["compared"]
    assert p.stderr.strip().splitlines()[-len(line["compared"]):] == [
        "compared %s %s limit %s" % (k, v["value"], v["limit"])
        for k, v in line["compared"].items()]
    return line, p.stdout


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in loader.bench_spec()["workloads"]])
def test_every_cells_rehearsal_ends_in_the_contracts_line(cell, trace):
    spec = loader.bench_spec()
    line, _ = rehearse(cell, trace)
    assert set(line) == KEYS  # no device trace on the CPU, so no breakdown
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    group = spec["per_layer"] if trace else spec["end_to_end"]
    want = {m["name"] for m in group if cell in m.get("workloads", [cell])}
    if trace:
        want &= set(loader.load_json("workloads", cell)["layer_metrics"])
    # the CPU gives no device trace: a reader of one finds nothing and is left out
    from_trace = {m["name"] for m in spec["per_layer"] if m["source"] == "device_trace"}
    assert set(line["metrics"]) <= want and want - set(line["metrics"]) <= from_trace
    for m in line["metrics"].values():
        assert m["value"] is None  # a CPU number never stands under a device metric


def test_no_chip_is_an_error_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload",
                        loader.bench_spec()["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=loader.REPO_DIR)
    assert p.returncode not in (0, 3) and "correct" not in p.stdout


DUMMY = {
    "configs/zz_dummy_config.json": {
        "name": "zz_dummy_config", "family": "zz_dummy", "source": "none: a test's file",
        "published": {"layers": [1, 1], "channels": [8, 16, 32], "classes": 5, "image": 16},
        "reduced": [], "assumed": {}, "dtype": "bfloat16", "layout": "NHWC",
        "optimizer": {"name": "sgd", "learning_rate": 0.1, "rate_per_batch": 256,
                      "momentum": 0.9}},
    "traffic/zz_dummy_traffic.json": {
        "name": "zz_dummy_traffic", "entry": "fuse_step", "batch": 16, "pool": 3,
        "first_steps": 3, "warm_steps": 1, "max_inflight": 4, "trace_seconds": 1},
    # what BENCHMARK.json's entry would hold stands in the rehearsal's group
    "workloads/zz_dummy_cell.json": {
        "runner": "train_steps",
        "layer_metrics": ["zz_dummy_metric", "zz_dummy_published", "launches_per_step.train"],
        "limits": {"loss_gap": 1.0, "grad_norm_gap": 100.0, "grad_leaf_diff": 100.0,
                   "delta_norm_gap": 100.0},
        "rehearse": {"config": "zz_dummy_config", "traffic": "zz_dummy_traffic",
                     "chips": 1}},
    "layer_metrics/zz_dummy_metric.py": (
        "NAME, UNIT, LAYER, MOVES, SOURCE = 'zz_dummy_metric', 'steps', 'a test', "
        "'train_samples_per_s', 'program_counter'\n\n\n"
        "def read(run):\n    return (run.get('window') or {}).get('steps')\n"),
    # a reader of what the program published after the window, which finds the
    # traced window's directory without a search
    "layer_metrics/zz_dummy_published.py": (
        "import os\n\n"
        "NAME, UNIT, LAYER, MOVES, SOURCE = 'zz_dummy_published', 'tokens', 'a test', "
        "'train_samples_per_s', 'program_counter'\n\n\n"
        "def read(run):\n"
        "    assert os.path.isdir(run['trace_dir']), run['trace_dir']\n"
        "    return run['program']['tokens_on_the_fullest_expert']\n"),
    # a family of its own: the ResNet adapter with counts of its own, as a sparse
    # model's adapter has them
    "references/zz_dummy.py": (
        "from harness.loader import load_module\n\n"
        "_resnet = load_module('references', 'resnet')\n"
        "init, batches, value_and_grad = _resnet.init, _resnet.batches, "
        "_resnet.value_and_grad\n"),
    "flops/zz_dummy.py": (
        "from harness.loader import load_module\n\n"
        "train_flops_per_sample = load_module('flops', 'resnet').train_flops_per_sample\n"),
    "models/zz_dummy.py": (
        "from harness.loader import load_module\n\n\n"
        "def build(config, traffic, params, devices, opt):\n"
        "    prog = load_module('models', 'resnet').build(config, traffic, params, devices, "
        "opt)\n"
        "    prog.zero_counts = lambda: {'dropped_token_slots': "
        "config['assumed'].get('dropped', 0)}\n"
        "    prog.after_window = lambda: {'tokens_on_the_fullest_expert': 96.0}\n"
        "    return prog\n"),
}


@pytest.mark.parametrize("dropped", [0, 2])
def test_files_a_later_pr_adds_are_found_and_run_without_an_edit(dropped):
    paths = []
    try:
        for rel, body in DUMMY.items():
            path = os.path.join(loader.BENCH_DIR, rel)
            assert not os.path.exists(path)
            paths.append(path)
            if rel.startswith("configs/"):
                body = dict(body, assumed={"dropped": dropped})
            with open(path, "w") as f:
                f.write(body if isinstance(body, str) else json.dumps(body))
        line, out = rehearse("zz_dummy_cell", 1)
        assert set(line) == KEYS
        # a count of the program's own that must read 0 is a number compared
        assert line["compared"]["dropped_token_slots"] == {"value": dropped, "limit": 0}
        assert line["correct"] is (dropped == 0)
        assert set(line["metrics"]) == {"zz_dummy_metric", "zz_dummy_published",
                                        "launches_per_step.train"}
        assert line["metrics"]["zz_dummy_metric"]["unit"] == "steps"
    finally:
        for path in paths:
            if os.path.exists(path):
                os.remove(path)


def _context(cell_name, seconds=1.0, trace=0):
    spec = importlib.util.spec_from_file_location("bench_run", RUN)
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import device, program

    _, cell, config, traffic = run.resolve(cell_name, rehearse=True)
    args = type("Args", (), {"seed": 11, "seconds": seconds, "trace": trace,
                             "rehearse": True})()
    devices = device.find_devices(cell["chips"], rehearse=True)  # the CPU: no look for a chip
    program.setup(rehearse=True)
    return run.Context(args, cell, config, traffic, devices)


@pytest.mark.parametrize("cell", [w["name"] for w in loader.bench_spec()["workloads"]])
def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell, monkeypatch):
    ctx = _context(cell)
    rows = []
    ctx.say = lambda **row: rows.append(row)
    runner = loader.load_module("runners", ctx.cell["runner"])
    model = loader.load_module("models", ctx.config["family"])
    sound = runner.run(ctx)
    assert sound["correct"] is True
    build = model.build

    def frozen(config, traffic, params, devices, opt):
        # the optimizer under the timed path moves nothing: rate 0 leaves the
        # weights, and with them the state that is read back, as they were
        prog = build(config, traffic, params, devices, dict(opt, learning_rate=0.0))
        prog._optimizer = opt  # the comparison reads the state as the cell states it
        return prog

    monkeypatch.setattr(model, "build", frozen)
    rows.clear()
    broken = runner.run(ctx)
    assert broken["correct"] is False
    failed = {r["compared"] for r in rows if r.get("phase") == "compare" and not r["ok"]}
    assert failed & {"grad_norm_gap", "grad_rel_diff", "delta_norm_gap"}


def _first_steps_at_the_tiny_size(cell, faults):
    """The program's first steps, the plain reference's, and the reference's
    own under each fault, all on one seed: [(numbers of the program)], then
    {fault: numbers of the reference with the fault, in the program's place}."""
    from harness import compare, train_reference

    ctx = _context(cell)
    config, traffic = ctx.config, ctx.traffic
    ref = loader.load_module("references", config["family"])
    model = loader.load_module("models", config["family"])
    runner = loader.load_module("runners", ctx.cell["runner"])
    opt = train_reference.effective_optimizer(config, traffic)
    params, pool = ref.init(config, 5), ref.batches(config, traffic, 5)
    prog = model.build(config, traffic, params, ctx.devices, opt)
    first, _ = runner.first_steps(prog, [prog.batch(x, y) for x, y in pool], params, traffic)
    plain = train_reference.first_steps(ref, config, opt, params, pool,
                                        program_gradient=first["first_gradient"],
                                        keep_gradient=True)
    want = plain.pop("first_gradient")
    sound = compare.training_numbers(first, plain)
    faulty = {}
    for fault in faults:
        low = train_reference.first_steps(ref, config, opt, params, pool, quant=fault,
                                          keep_gradient=True)
        got = low.pop("first_gradient")
        rel_diff, diff_norms = train_reference.gradient_distance(got, want)
        against = dict(plain, grad_rel_diff=rel_diff, grad_diff_norms=diff_norms)
        faulty[fault] = compare.training_numbers(low, against)
    return ctx, sound, faulty


def _value(numbers, name):
    return next(v for n, v, _ in numbers if n == name)


@pytest.mark.parametrize("cell", [w["name"] for w in loader.bench_spec()["workloads"]])
def test_the_control_in_fp8_fails_a_limit_that_the_program_meets(cell):
    """At the tiny size, with the limit on the first gradient's distance set as
    the contract sets it (above the sound reading, under the control's): the
    reference in fp8, put in the program's place, is not correct."""
    from harness import compare

    ctx, sound_numbers, faulty = _first_steps_at_the_tiny_size(cell, ["fp8"])
    limits = dict(ctx.cell["limits"])
    for name in sorted(set(compare.DISTANCES) & set(loader.resolve_cell(cell)["limits"])):
        sound, control = _value(sound_numbers, name), _value(faulty["fp8"], name)
        assert control > 3 * sound, (name, sound, control)
        limits[name] = (sound * control) ** 0.5
        ok = compare.judge(sound_numbers, limits)
        assert all(r["ok"] for r in ok), ok
        bad = compare.judge(faulty["fp8"], limits)
        assert not all(r["ok"] for r in bad)


RESNET_CELLS = [w["name"] for w in loader.bench_spec()["workloads"]
                if loader.load_json("configs", w["config"])["family"] == "resnet"]


@pytest.mark.parametrize("cell", RESNET_CELLS[:1])
def test_a_fault_in_the_branch_convolutions_backward_alone(cell):
    """Faults planted in the backward pass of the residual branches'
    convolutions, leaves that hold 4e-7 of the gradient's squared norm.

    Their weights' gradient with its sign turned leaves every loss and every
    norm as it was and moves the distance over all leaves together by a
    thousandth: the comparison as it was before review passes a step that
    climbs in 48 convolutions. Leaf by leaf it reads 2 on each of them.

    fp8 in that backward pass alone: on the chip at the cell's own size its
    worst leaf reads 0.61-0.66 against a sound run's 0.29 at most, and the limit
    on the worst leaf stands between them (PERF.md section 4). Its mean over the
    leaves, there and here, lies UNDER the sound program's own: rounding flips
    ReLU masks all along the trunk, so a bf16 gradient lies a sixth away from
    float32 on every leaf, and no limit on the mean can tell this fault from a
    sound run. At the tiny size the worst leaf does not part them either; the
    last line pins the reading on the mean."""
    from harness import compare

    ctx, sound_numbers, faulty = _first_steps_at_the_tiny_size(
        cell, ["fp8", "negated_branch_backward", "fp8_branch_backward"])
    sound = _value(sound_numbers, "grad_leaf_diff")
    control = _value(faulty["fp8"], "grad_leaf_diff")
    limits = dict(ctx.cell["limits"], grad_leaf_diff=(sound * control) ** 0.5,
                  grad_leaf_diff_worst=3 * _value(sound_numbers, "grad_leaf_diff_worst"))
    assert all(r["ok"] for r in compare.judge(sound_numbers, limits))
    turned = faulty["negated_branch_backward"]
    before_review = {k: v for k, v in limits.items() if not k.startswith("grad_leaf_diff")}
    before_review["grad_rel_diff"] = 3 * _value(sound_numbers, "grad_rel_diff")
    assert all(r["ok"] for r in compare.judge(turned, before_review))
    failed = {r["compared"] for r in compare.judge(turned, limits) if not r["ok"]}
    assert failed == {"grad_leaf_diff", "grad_leaf_diff_worst"}
    assert _value(turned, "grad_leaf_diff_worst") == pytest.approx(2.0, rel=1e-3)
    assert _value(faulty["fp8_branch_backward"], "grad_leaf_diff") < sound
