"""The cell ``sarvam105_serve_reason``: its driver draws the leaves
``leaves.make`` draws, its control fails where the program passes (the
stated dtype against float8 products), a token altered in the sampler
comes out not correct, and its readers read a stretch recorded on the
chip.

Its entries in ``BENCHMARK.json`` are found by name (``check_entries``);
``test_spec.py`` checks that cells are appended in order, runs
``check_entries`` on the benchmark with a cell appended after this one,
and, with ``test_harness.py::test_every_cell_rehearses``, picks the cell
up like any other."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from test_harness import ROOT as REPO, _check_line, run_harness

CELL = "sarvam105_serve_reason"
CONTROL = os.path.join("benchmarks", "tools", "control.py")
FAULTY = os.path.join("tests", "benchmark", "faulty_run.py")
MARKS = ["bench.token.first", "bench.token.next"]
OWN = ["decode.step_mfu.mla_moe", "decode.step_roofline.mla_moe",
       "moe.experts_hit_share", "mla.keys_resident_share",
       "kernel.expert_matmul_roofline"]


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny_cell():
    from benchmarks.lib import spec
    return spec.Cell(REPO, CELL, rehearse=True)


def test_the_cell_and_its_configuration_are_the_issues():
    check_entries(_bench())


def check_entries(bench):
    """The cell and its configuration, found by name: the traffic and
    ``reduced`` it was added with, the five per-layer metrics that are the
    cell's own in the order it brought them, and ``tpot_p90_ms`` among what
    it reports. Where the entries stand is ``test_spec.py``'s to check."""
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["name"] == "sarvam-105b-l9-ep8"
    assert cell["chips"] == 1 and cell["traffic"] == "serve_reason_steady"
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_held",
        "max_position_embeddings"]
    reports = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [])]
    assert [m["name"] for m in bench["per_layer"]
            if m["workloads"] == [CELL]] == OWN
    assert "tpot_p90_ms" in reports


@pytest.mark.parametrize("group", ["prompt", "answer", "rate", "server"])
def test_the_traffic_is_the_issues(group):
    """ISSUE 28's traffic, as the cell runs it: the lengths, four fifths
    of the knee the sweep found, and the server's shape."""
    from benchmarks.lib import spec
    traffic = spec.Cell(REPO, CELL).traffic
    if group == "prompt":
        assert traffic["prompt"] == {"median": 512, "sigma": 0.8,
                                     "min": 128, "max": 2048}
    elif group == "answer":
        assert traffic["answer"] == {"median": 896, "sigma": 0.5,
                                     "min": 384, "max": 2048}
    elif group == "rate":
        knee = traffic["knee"]
        assert traffic["rate_rps"] == pytest.approx(
            knee["share"] * knee["rps"])
        assert 0.7 <= knee["share"] <= 0.8
        assert round(traffic["rate_rps"] * _bench()["run_seconds"]) >= 50
    else:
        assert traffic["max_sequences"] == 48 and traffic["greedy"]
        assert traffic["seq_buckets"] == [512, 1024, 2048, 3072, 4096]
        assert traffic["prefill_chunk"] == 1024
        assert traffic["lead_in"]["requests"] == 38


def test_the_driver_draws_the_leaves_that_leaves_make_draws():
    """Leaf by leaf, with the key and the index ``leaves._draw`` gives
    each: the same values as the one call that draws them all, in the
    served dtype; and the reference's copy is those values, rounded to
    the stated dtype and held in float32."""
    import jax.numpy as jnp
    from benchmarks.drivers import serve_arch
    from benchmarks.lib import leaves, spec
    cfg = _tiny_cell().config
    specs = spec.load_module("builders", cfg["builder"]).leaf_specs(cfg)
    whole = leaves.make(specs, 2 ** 31 + 9)
    leaf = serve_arch._leaf_drawer(specs, 2 ** 31 + 9, "float32")
    served = serve_arch._leaf_drawer(specs, 2 ** 31 + 9, "bfloat16")
    held = serve_arch._leaf_drawer(specs, 2 ** 31 + 9, "bfloat16",
                                   back_to="float32")
    assert len(specs) == 3 + 11 + 2 * 16
    for name in sorted(specs):
        assert np.array_equal(np.asarray(leaf(name)),
                              np.asarray(whole[name])), name
        rounded = whole[name].astype(jnp.bfloat16)
        assert served(name).dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(served(name), np.float32),
                              np.asarray(rounded, np.float32)), name
        assert held(name).dtype == jnp.float32
        assert np.array_equal(np.asarray(held(name)),
                              np.asarray(rounded, np.float32)), name


def test_ids_are_drawn_from_the_slice_of_the_vocabulary_held():
    from benchmarks.drivers import serve_arch
    from benchmarks.lib import spec
    cell = _tiny_cell()
    cfg = serve_arch.held_vocabulary(cell.config)
    assert cell.config["vocab_size"] == 2048 and cfg["vocab_size"] == 256
    generator = spec.load_module("generators", cell.traffic["kind"])
    plan = generator.plan(cell.traffic, cfg, 5, 4.0)
    ids = np.concatenate([r["prompt"] for r in plan["window"]]
                         + [r["prompt"] for r in plan["lead_in"]])
    assert 0 <= ids.min() and ids.max() < 256 and ids.max() > 200


def test_the_control_fails_and_the_program_passes_on_every_seed():
    """Served tokens judged by the reference with every product's
    operands rounded to float8_e4m3fn, at the rehearsal's size and
    against the rehearsal's limit; the reference rounded to the stated
    bfloat16 passes as the program does. (Off the TPU
    ``rtc.product_operands`` widens the program's bfloat16 products to
    float32: the program's own bfloat16 products run on the chip only.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, CONTROL, "--workload", CELL, "--seeds", "21,22",
         "--seconds", "3", "--rehearse"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert [r["seed"] for r in rows] == [21, 22]
    for row in rows:
        program, control = row["cases"]["program"], row["cases"]["control"]
        assert list(row["cases"]) == ["program", "stated", "control"]
        assert program["passes"] is True, row
        assert row["cases"]["stated"]["passes"] is True, row
        assert control["passes"] is False, row
        # the mean gap alone is held; the widest is read beside it, and
        # the program's case carries the window's own tail
        assert list(control["numbers"]) == ["mean_gap"]
        assert control["numbers"]["mean_gap"]["value"] \
            > control["numbers"]["mean_gap"]["limit"] \
            >= program["numbers"]["mean_gap"]["value"], row
        assert control["read_not_compared"]["logit_gap"] \
            > program["read_not_compared"]["logit_gap"] >= 0, row
        assert program["read_not_compared"]["tpot_p90_ms"] > 0
        assert program["read_not_compared"]["failed"] == 0


def test_a_token_altered_in_the_sampler_comes_out_not_correct():
    proc, line = run_harness(
        ["token_altered", "--workload", CELL, "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "0"], cwd=REPO, script=FAULTY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert line["compared"]["mean_gap"]["value"] \
        > line["compared"]["mean_gap"]["limit"]


def test_a_traced_rehearsal_reads_the_counters_and_no_share_of_a_peak():
    bench = _bench()
    layer = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    proc, line = run_harness(["--workload", CELL, "--seed", "5",
                              "--seconds", "2", "--trace", "1",
                              "--rehearse"], cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["metrics"] == {}
    got = line["rehearsal"]
    assert set(got) <= set(layer)
    assert 0 < got["moe.experts_hit_share"]["value"] <= 100
    assert 0 < got["mla.keys_resident_share"]["value"] < 100
    assert not [n for n in got if "mfu" in n or "roofline" in n]


def _recorded():
    """The stretch, cut with ``tools/cut_trace.py`` (0.3 s, one second
    into the traced stretch) and then without its ``mx.*`` annotations:
    ``test_program_spans.py`` holds every recorded stretch to be like a
    profile older than the program's spans."""
    from benchmarks.lib import trace
    path = os.path.join(REPO, "benchmarks", "data",
                        CELL + ".trace.json.gz")
    return trace.Reduced.marked(trace.load_json(path))


def test_the_readers_read_the_recorded_stretch():
    """A stretch of the cell recorded on the v5e (my chip run, PR 28):
    decode steps told from prefill chunks, and the step's shares of the
    peak and of the roofline from hand-made counters."""
    from benchmarks.lib import device, spec
    from benchmarks.lib import flops_mla_moe as fl
    from benchmarks.readers import decode_step_share_mla_moe as share
    from benchmarks.readers import serve_program_ms
    red = _recorded()
    run = types.SimpleNamespace(reduced=red)
    decode = {"program": "jit_fn", "followed_by": "bench.token.next",
              "marks": MARKS}
    steps = serve_program_ms.seconds(run, decode)
    assert len(steps) >= 3
    mean = sum(steps) / len(steps)
    assert all(abs(s - mean) < 0.25 * mean for s in steps)
    cell = spec.Cell(REPO, CELL)
    n = len(steps)
    prompts = [300 + 40 * i for i in range(38)]
    run = types.SimpleNamespace(
        reduced=red, cell=cell,
        peaks=device.peaks_table(REPO)["TPU v5 lite"],
        result={"traced": {"decode_steps": n, "t_start": 0.0, "t_stop": 1.0},
                "server_name": "s",
                "counters": {"s_decode_steps": 10 * n,
                             "s_moe_experts_hit": 10 * n * 8 * 14,
                             "s_moe_assignments": 10 * n * 8 * 38},
                # each of 38 sequences gave its first token before the
                # stretch and n tokens in it
                "window": {"all_requests": [
                    {"prompt_len": p, "times": [-1.0] + [0.5] * n}
                    for p in prompts]}})
    mfu = share.read(run, dict(decode, of="mfu"))
    roof = share.read(run, dict(decode, of="roofline"))
    assert 0 < mfu < roof < 100
    # the roofline's least time by hand: the step is bound by memory
    cfg = cell.config
    per_step = 2 * (fl.outside_experts_params(cfg)
                    + 8 * 14 * fl.expert_params(cfg)) \
        + sum(2 * 9 * 576 * (p + 1 + (n + 1) / 2.0) for p in prompts)
    assert roof == pytest.approx(100.0 * per_step / 819e9 / mean, rel=0.02)
    # a program without the counters reads nothing and does not raise
    run.result["counters"] = {}
    assert share.read(run, dict(decode, of="mfu")) is None
    assert share.read(run, dict(decode, of="roofline")) is None


def test_the_grouped_products_roofline_on_the_recorded_stretch():
    """``kernel.expert_matmul_roofline``: the experts hit, by the
    program's counters, against the ``ragged-dot`` operations inside the
    recorded decode steps (a prefill's grouped products lie outside)."""
    from benchmarks.lib import device, spec
    from benchmarks.lib import flops_mla_moe as fl
    from benchmarks.readers import expert_matmul_roofline as reader
    red = _recorded()
    cell = spec.Cell(REPO, CELL)
    params = cell.metric_file("kernel.expert_matmul_roofline")["params"]
    spans = reader.decode_executions(red, params)
    n = len(spans)
    assert n >= 3
    inside = [e for e in red.ops() if e.name.lstrip("%").startswith(
        "ragged-dot") and any(a <= e.start and e.end <= b for a, b in spans)]
    # three grouped products and their groups' metadata on each of the
    # eight sparse layers a step
    assert len(inside) == 4 * 8 * n
    spent = sum(e.dur for e in inside)
    run = types.SimpleNamespace(
        reduced=red, cell=cell,
        peaks=device.peaks_table(REPO)["TPU v5 lite"],
        result={"traced": {"decode_steps": n, "t_start": 0.0, "t_stop": 1.0},
                "server_name": "s",
                "counters": {"s_decode_steps": 10 * n,
                             "s_moe_experts_hit": 10 * n * 117,
                             "s_moe_assignments": 10 * n * 38 * 8}})
    got = reader.read(run, params)
    by_hand = 100.0 * (n * 117 * fl.expert_params(cell.config) * 2 / 819e9) \
        / spent
    assert got == pytest.approx(by_hand, rel=1e-6) and 30 < got < 100
    run.result["counters"] = {"s_decode_steps": 10 * n}
    assert reader.read(run, params) is None


def test_the_schedulers_replay_on_the_cells_own_plans():
    """``tools/replay_scheduler.py``: with a step of constant cost and
    prefill at none every turn's gap is that cost; with the costs read on
    the chip the tail lies above the mean, is the same for every seed on
    the mix's own schedule, and follows the seed where the seed draws the
    schedule."""
    from benchmarks.drivers import serve_arch
    from benchmarks.lib import spec
    from benchmarks.tools import replay_scheduler as tool
    cell = spec.Cell(REPO, CELL)
    generator = spec.load_module("generators", cell.traffic["kind"])
    cfg = serve_arch.held_vocabulary(cell.config)

    def read(seed, traffic=cell.traffic, **cost):
        plan = generator.plan(traffic, cfg, seed, 51.0)
        return tool.replay(traffic, plan, 51.0, cost)
    flat = dict(step_ms=20.0, per_sequence_ms=0.0, bucket_ms=0.0,
                chunk_ms=0.0, chunk_per_1024_ms=0.0)
    assert read(2 ** 31 + 3, **flat) == pytest.approx((20.0, 20.0))
    chip = dict(step_ms=12.6, per_sequence_ms=0.184, bucket_ms=1.46,
                chunk_ms=15.0, chunk_per_1024_ms=66.0)
    tail, mean = read(2 ** 31 + 3, **chip)
    assert 20.0 < mean < tail < 35.0
    assert read(2 ** 31 + 3, **chip) == (tail, mean)
    assert "schedule_seed" in cell.traffic
    assert read(2 ** 31 + 4, **chip) == (tail, mean)
    free = {k: v for k, v in cell.traffic.items() if k != "schedule_seed"}
    assert read(2 ** 31 + 3, free, **chip) != read(2 ** 31 + 4, free, **chip)
    assert tool.spread_less_farthest([25.0, 25.1, 25.2, 25.3, 25.4, 31.0]) \
        < tool.spread_less_farthest([25.0, 25.1, 25.2, 25.3, 30.0, 31.0])
