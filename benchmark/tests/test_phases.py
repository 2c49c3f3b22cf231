"""``phases.py`` and the readers that lean on it: on the small recorded TPU
profile (``fixture.xplane.pb``) with a hand-made map and synthetic spans, on
hand-made events, and end to end on the tiny cells (``tiny.py``), where the
train step's map is found through the ``compile`` span the program wrote and
the serve spans are the engine's own."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402

from benchmark import phases, trace  # noqa: E402

FIXTURE = os.path.join(HERE, "fixture.xplane.pb")


def fixture_run(tmp_path, mapped=None, spans=()):
    """What ``run.py`` hands a reader, for the recorded profile: the reduced
    trace, a ``compile`` span naming a map file, the journal's spans."""
    run = {"trace": trace.reduce(FIXTURE), "trace_span": [100.0, None], "spans": list(spans)}
    if mapped is not None:
        path = tmp_path / "phases-fixture-1.json"
        path.write_text(json.dumps({"program": "bench_fixture_step", "phases": mapped}))
        run["spans"].append({"kind": "compile", "start": 0.0, "end": 1.0, "label": "Stage.bench_fixture_step",
                             "phases": str(path)})
    return run


def test_device_time_by_phase_on_the_recorded_profile(tmp_path):
    run = fixture_run(tmp_path, {"convolution_tanh_fusion": ["mlp", "-"], "copy-done": [None, "-"]})
    table = phases.by_phase(run, "bench_fixture_step")
    assert table["steps"] == 4
    assert table["phases"][("mlp", "-")] == pytest.approx(62978, abs=1)  # the four fusions, as test_trace.py reads them
    assert table["busy_ns"] == pytest.approx(72465, abs=2)  # and every operation of the four programs
    assert set(table["phases"]) == {("mlp", "-"), ("unattributed", "-")}  # mapped to no phase, or not in the map
    share = phases.phase_share(run, "bench_fixture_step", ("mlp",))
    assert share == pytest.approx(100 * 62978 / 72465, rel=1e-4)
    assert share + phases.phase_share(run, "bench_fixture_step", ("unattributed",)) == pytest.approx(100.0)
    assert phases.kernels(run, "bench_fixture_step")["kernels"] == {}  # no custom call in this profile
    assert phases.kernel_ms_per_step(run, "bench_fixture_step", "flash_fwd") is None


def test_nothing_to_read_gives_none_and_never_raises(tmp_path):
    no_map = fixture_run(tmp_path)
    assert phases.by_phase(no_map, "bench_fixture_step") is None  # an older program: no compile span names a map
    assert phases.phase_share(no_map, "bench_fixture_step", ("mlp",)) is None
    gone = fixture_run(tmp_path, {"x": ["mlp", "-"]})
    os.remove(gone["spans"][-1]["phases"])
    assert phases.by_phase(gone, "bench_fixture_step") is None
    mapped = fixture_run(tmp_path, {"convolution_tanh_fusion": ["mlp", "-"]})
    assert phases.by_phase(mapped, "train_step") is None  # no such program ran
    for empty in ({}, {"trace": None}, {"trace": trace.reduce(None), "spans": [], "trace_span": None}):
        assert phases.by_phase(empty, "train_step") is None and phases.kernels(empty, "train_step") is None
        assert phases.idle_by_span(empty) is None and phases.span_ms_p50(empty, "call_fetch") is None
        assert phases.step_bookkeeping_ms_p50(empty) is None
    for name in ("train_optimizer_share", "train_loss_head_share", "train_attn_kernel_share", "train_unattributed_share",
                 "flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step", "serve_call_build_ms_p50",
                 "serve_call_upload_ms_p50", "serve_call_launch_ms_p50", "serve_call_fetch_ms_p50",
                 "serve_step_bookkeeping_ms_p50"):
        reader = tiny_module(name)
        assert reader.read(no_map | {"window": (0.0, 1.0)}) is None, name


def tiny_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(f"metric_{name}", os.path.join(os.path.dirname(HERE), "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_idle_time_goes_to_the_innermost_program_span(tmp_path):
    """The profile's idle time (the host slept 2 ms after each of four short
    programs) under synthetic journal spans laid over it on the host's clock."""
    reduced = trace.reduce(FIXTURE)
    lo, hi = reduced["window_ns"]
    window = (hi - lo) * 1e-9
    at = 100.0  # perf_counter when the profile's window opened
    first = reduced["modules"][0][0]
    fetch = (at + (first[0] - lo) * 1e-9, at + (first[1] - lo) * 1e-9 + 0.001)  # the first program and 1 ms after it
    spans = [
        {"kind": "engine_step", "start": at, "end": at + window / 2},
        {"kind": "call_fetch", "start": fetch[0], "end": fetch[1]},
        {"kind": "queue_wait", "start": at - 5.0, "end": at + 0.0001},  # a request's state, not the host's work
        {"kind": "admission", "start": at + 0.001, "end": at + 0.001},  # no length: nothing can lie under it
        {"kind": "decode_batch", "start": at - 50.0, "end": at - 49.0},  # long before the profile
    ]
    run = fixture_run(tmp_path, spans=spans)
    idle = phases.idle_by_span(run)
    assert sum(idle.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert set(idle) == {"engine_step", "call_fetch", "other"}
    assert idle["call_fetch"] == pytest.approx(0.001, rel=0.02)  # the millisecond after the program, and its own small gaps
    assert idle["other"] == pytest.approx(window / 2, rel=0.02)  # nothing of the program's was open in the second half
    assert idle["engine_step"] == pytest.approx(sum(idle.values()) - idle["call_fetch"] - idle["other"])


def hand_made_run():
    """Two runs of a train step with the three named kernels, one fusion that
    spans a kernel's time (a ``while`` over it would), and another program."""
    call = lambda name: f"%{name} = bf16[8,128]{{1,0}} custom-call(%p), custom_call_target=\"tpu_custom_call\""
    ops, modules = [], []
    for base in (0, 1000):
        modules.append((base, base + 900, "jit_train_step(123)"))
        ops += [(base + 0, base + 100, "%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop"),
                (base + 100, base + 400, "%while.1 = (f32[8]) while(%t)"),
                (base + 150, base + 350, call("flash_fwd.2")),
                (base + 400, base + 500, call("flash_bwd_dq.2")),
                (base + 500, base + 800, call("flash_bwd_dkv.2")),
                (base + 800, base + 900, "%copy.3 = f32[8]{0} copy(%x)")]
    modules.append((2000, 2100, "jit__threefry_fold_in(9)"))
    ops.append((2000, 2100, call("other_kernel.1")))
    return {"trace": {"ops": [ops], "modules": [modules], "window_ns": (0, 2200)}, "spans": []}


def test_kernels_are_told_apart_by_name_and_nested_time_is_counted_once():
    run = hand_made_run()
    table = phases.kernels(run, "train_step")
    assert table["steps"] == 2 and table["busy_ns"] == 1800  # the while's 300 ns hold the kernel's 200: counted once
    assert table["kernels"] == {"flash_fwd": 400, "flash_bwd_dq": 200, "flash_bwd_dkv": 600}  # not the other program's
    assert tiny_module("flash_fwd_ms_per_step").read(run) == pytest.approx(200e-6)
    assert tiny_module("flash_dq_ms_per_step").read(run) == pytest.approx(100e-6)
    assert tiny_module("flash_dkv_ms_per_step").read(run) == pytest.approx(300e-6)
    assert tiny_module("train_attn_kernel_share").read(run) == pytest.approx(100 * 1200 / 1800)
    # what flash_attn_roofline's reader sums (every custom call of the steps) is these three
    from benchmark import readers

    every = sum(b - a for a, b, n in run["trace"]["ops"][0] if readers.is_kernel(n) and b <= 2000)
    assert every == sum(table["kernels"].values())


def test_serve_call_spans_and_bookkeeping():
    span = lambda kind, a, b: {"kind": kind, "start": a, "end": b}
    spans = []
    for i, (build, up, launch, fetch, rest) in enumerate([(1, 2, 3, 4, 5), (2, 2, 2, 2, 2), (3, 1, 1, 1, 9)]):
        t = 10.0 + i
        ms = 1e-3
        spans += [span("engine_step", t, t + (build + up + launch + fetch + rest) * ms),
                  span("call_build", t, t + build * ms),
                  span("decode_batch", t + build * ms, t + (build + up + launch + fetch) * ms),
                  span("call_upload", t + build * ms, t + (build + up) * ms),
                  span("call_launch", t + (build + up) * ms, t + (build + up + launch) * ms),
                  span("call_fetch", t + (build + up + launch) * ms, t + (build + up + launch + fetch) * ms)]
    spans.append(span("engine_step", 50.0, 50.5))  # outside the window
    run = {"spans": spans, "window": (9.0, 20.0)}
    read = lambda name: tiny_module(name).read(run)
    assert read("serve_call_build_ms_p50") == pytest.approx(2.0)
    assert read("serve_call_upload_ms_p50") == pytest.approx(2.0)
    assert read("serve_call_launch_ms_p50") == pytest.approx(2.0)
    assert read("serve_call_fetch_ms_p50") == pytest.approx(2.0)
    assert read("serve_step_bookkeeping_ms_p50") == pytest.approx(5.0)  # 5, 2 and 9 ms of the steps are neither build nor call


# ------------------------------------------------------------- the tiny cells


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``tiny.py``'s tree with, as a later PR would add them, the serve readers
    entered for the tiny serve cells and a probe that reads the train readers
    over a profile made up from the program's own map (the CPU writes no
    device plane)."""
    tree = tiny.make_tree(str(tmp_path_factory.mktemp("phases") / "tree"))
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    for name in ("serve_call_build_ms_p50", "serve_call_upload_ms_p50", "serve_call_launch_ms_p50", "serve_call_fetch_ms_p50",
                 "serve_step_bookkeeping_ms_p50"):
        bench["per_layer"].append(dict(name=name, unit="ms", better="lower", source="program_counter", layer="serve loop, host",
                                       moves="serve_tpot_p90_ms", workloads=["tiny-chat", "tiny-long"]))
    bench["per_layer"].append(dict(name="probe_train_phase_shares", unit="%", better="lower", source="device_trace",
                                   layer="compiled train step", moves="train_tokens_per_s", workloads=["tiny-train"]))
    json.dump(bench, open(os.path.join(tree, "BENCHMARK.json"), "w"))
    with open(os.path.join(tree, "benchmark", "metrics", "probe_train_phase_shares.py"), "w") as f:
        f.write(PROBE)
    return tree


PROBE = '''
import json

from benchmark import phases, run as bench_run


def read(run):
    """The train readers over one made-up step: every instruction of the map the
    program wrote is one microsecond of device time. Their sum, which has to be 100."""
    mapped = phases.load_map(run, "train_step")
    assert mapped is not None, [s for s in run["spans"] if s["kind"] == "compile"]
    ops = [(1000 * i, 1000 * (i + 1), f"%{name} = f32[8]{{0}} fusion(%p)") for i, name in enumerate(sorted(mapped))]
    made_up = dict(run, trace={"ops": [ops], "modules": [[(0, 1000 * len(ops), "jit_train_step(1)")]],
                               "window_ns": (0, 1000 * len(ops))})
    table = phases.by_phase(made_up, "train_step")
    assert table["steps"] == 1 and table["busy_ns"] == 1000 * len(ops)
    names = {p for p, _ in table["phases"]}
    assert names >= {"embed", "attn_proj", "attn_kernel", "mlp", "norm", "loss_head", "grad_clip", "optimizer"}, names
    reader = lambda name: bench_run.load_module("metrics", name).read(made_up)
    shares = {n: reader(n) for n in ("train_optimizer_share", "train_loss_head_share", "train_unattributed_share")}
    assert all(v is not None and 0 < v < 100 for v in shares.values()), shares
    others = sum(ns for (p, _), ns in table["phases"].items()
                 if p not in ("grad_clip", "optimizer", "loss_head", "unattributed")) * 100.0 / table["busy_ns"]
    return sum(shares.values()) + others
'''


def test_train_readers_find_the_map_through_the_compile_span(tree):
    code, line, err = tiny.run_cell(tree, "--workload", "tiny-train", "--seed", "5", "--seconds", "2", "--trace", "1")
    assert code == 0 and line["correct"], err[-3000:]
    assert line["metrics"]["probe_train_phase_shares"]["value"] == pytest.approx(100.0)
    # on the CPU the profile has no device plane: the device readers find nothing and stay out of the line
    assert not {"train_optimizer_share", "train_unattributed_share", "flash_fwd_ms_per_step"} & set(line["metrics"])


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-long"])
def test_serve_call_readers_on_the_tiny_cells(tree, cell):
    code, line, err = tiny.run_cell(tree, "--workload", cell, "--seed", "6", "--seconds", "3", "--trace", "1")
    assert code == 0 and line["correct"], err[-3000:]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    parts = [got[f"serve_call_{k}_ms_p50"] for k in ("upload", "launch", "fetch")]
    assert all(v > 0 for v in parts) and got["serve_call_build_ms_p50"] > 0 and got["serve_step_bookkeeping_ms_p50"] > 0
    call = got["serve_decode_call_ms_p50" if cell == "tiny-chat" else "serve_prefill_call_ms_p50"]
    assert max(parts) < call  # the three tile a call: none is longer than one
    code, line, err = tiny.run_cell(tree, "--workload", cell, "--seed", "6", "--seconds", "2", "--trace", "0")
    assert code == 0 and not any(k.startswith("serve_call_") for k in line["metrics"])  # per-layer: only in a traced run
