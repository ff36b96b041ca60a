"""The cell ``mimo_serve_longctx_chat``: its entries in ``BENCHMARK.json``,
found by name (``check_entries``), its traffic and its configuration are
those it was added with, its lead-in holds the long sessions first and
every program the mix needs is warmed, ``correct`` follows the long
sessions, the float8 control fails where the program passes, a part of
the mathematics changed in the program comes out not correct, and its
readers read nothing without the family's counters.

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

from test_harness import ROOT as REPO, run_harness

CELL = "mimo_serve_longctx_chat"
CONTROL = os.path.join("benchmarks", "tools", "control.py")
FAULTY = os.path.join("tests", "benchmark", "faulty_mimo.py")
OWN = ["decode.step_mfu.window_moe", "decode.step_roofline.window_moe",
       "kernel.gqa_decode_roofline", "attn.window_rows_share",
       "moe.experts_hit_share.window_moe",
       "kernel.expert_matmul_roofline.window_moe"]


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell(rehearse=False):
    from benchmarks.lib import spec
    return spec.Cell(REPO, CELL, rehearse=rehearse)


def test_the_cell_and_its_configuration_are_as_added():
    check_entries(_bench())


def check_entries(bench):
    """The cell and its configuration, found by name: one chip, the
    traffic and ``reduced`` it was added with, the six per-layer metrics
    that are the cell's own in the order it brought them, each moving
    ``tpot_p90_ms``, which it reports, and the generic serving metrics
    that the dense and the second family's cells report. Where the
    entries stand is ``test_spec.py``'s to check."""
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["name"] == "mimo-v2-flash-l7-ep16"
    assert config["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json"
    assert cell["chips"] == 1
    assert cell["traffic"] == "serve_longctx_chat_steady"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_held", "max_position_embeddings"]
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert [m["name"] for m in bench["per_layer"]
            if m["workloads"] == [CELL]] == OWN
    assert all(m["moves"] == "tpot_p90_ms" for m in bench["per_layer"]
               if m["name"] in OWN)
    assert "tpot_p90_ms" in reports
    other = {m["name"] for m in bench["per_layer"]
             if "sarvam105_serve_reason" in m["workloads"]
             and "opt13_serve_chat" in m["workloads"]}
    assert other <= reports


def test_the_configuration_is_the_catalogs_with_its_cut():
    """Every published key as published, but the four the cut names (each
    with its published value and its reason); the deployment of 16 chips a
    layer and 8 vocabulary slices; every assumed item; 3.43 B parameters
    held."""
    from benchmarks.builders import mimo_window_moe as builder
    cfg = _cell().config
    published = cfg["published"]
    cut = {"num_hidden_layers": (48, 7), "n_routed_experts": (256, 16),
           "vocab_held": (152576, 19072),
           "max_position_embeddings": (262144, 36864)}
    for key, (was, now) in cut.items():
        assert (published[key], cfg[key]) == (was, now), key
    assert set(cfg["reduced_why"]) == set(cut)
    published_config = {
        "model_type": "mimo_v2_flash", "hidden_size": 4096,
        "intermediate_size": 16384, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_key_value_heads": 4,
        "head_dim": 192, "v_head_dim": 128, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "sliding_window": 128,
        "attention_value_scale": 0.707, "partial_rotary_factor": 0.334,
        "rope_theta": 5000000, "swa_rope_theta": 10000,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "num_experts_per_tok": 8,
        "routed_scaling_factor": None, "n_shared_experts": None,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "layernorm_epsilon": 1e-05, "vocab_size": 152576,
        "tie_word_embeddings": False}
    for key, value in published_config.items():
        assert cfg[key] == value, key
    pattern = cfg["hybrid_layer_pattern"]
    assert len(pattern) == 48 and pattern.count(0) == 9
    assert pattern[:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == [0] + [1] * 47
    assert cfg["deployment"]["chips_per_layer"] == 16
    assert cfg["deployment"]["vocab_slices"] == 8
    assert cfg["deployment"]["expert_first"] == 0
    assert set(cfg["assumed"]) >= {
        "norm", "hybrid_layer_pattern", "rotary", "window",
        "attention_chunk_size", "sink", "attention_value_scale",
        "departures", "stated_precision", "control_precision"}
    assert round(builder.param_count(cfg) / 1e9, 2) == 3.43


@pytest.mark.parametrize("group", ["prompt", "answer", "long", "rate",
                                   "server"])
def test_the_traffic_is_as_added(group):
    traffic = _cell().traffic
    if group == "prompt":
        assert traffic["prompt"] == {"median": 256, "sigma": 0.8, "min": 64,
                                     "max": 1024}
        assert traffic["prompt"]["max"] <= traffic["prefill_chunk"]
    elif group == "answer":
        assert traffic["answer"] == {"median": 448, "sigma": 0.35,
                                     "min": 256, "max": 768}
    elif group == "long":
        long = traffic["long"]
        assert (long["sessions"], long["min"], long["max"]) \
            == (20, 16384, 28672)
        assert long["answer"] >= 6144
        assert long["max"] + long["answer"] <= traffic["seq_buckets"][-1]
        assert (long["check_sessions"], long["check_tokens"]) == (2, 128)
    elif group == "rate":
        knee = traffic["knee"]
        assert traffic["rate_rps"] == pytest.approx(
            knee["share"] * knee["rps"], rel=0.02)
        assert knee["share"] == 0.8 and len(knee["sweep"]) >= 3
    else:
        assert traffic["max_sequences"] == 36 and traffic["greedy"]
        assert traffic["seq_buckets"][-1] == 36864
        assert traffic["prefill_chunk"] == traffic["prefill_tokens"] == 1024
        assert traffic["driver"] == "serve_longctx"
        assert traffic["kind"] == "longdoc_chat"


def _programs(traffic, length):
    """The (kind, chunk, context) programs a prompt of ``length`` needs:
    its chunks' prefills and the decode at the bucket past it."""
    from mxnet_tpu.serve.decode import chunk_buckets
    buckets, chunk = traffic["seq_buckets"], traffic["prefill_chunk"]

    def bucket(n):
        return next(b for b in buckets if n <= b)
    out = set()
    for start in range(0, length, chunk):
        c = next(c for c in chunk_buckets(chunk)
                 if min(chunk, length - start) <= c)
        out.add(("prefill", c, bucket(start + c)))
    out.add(("decode", bucket(length + 1)))
    return out


@pytest.mark.parametrize("rehearse", [False, True])
def test_every_program_the_mix_needs_is_warmed(rehearse):
    """No program compiles inside the window: the warm prompts build every
    prefill a chat or long prompt of the mix needs and every decode its
    sessions reach."""
    from benchmarks.generators import longdoc_chat
    traffic = _cell(rehearse).traffic
    warmed = set()
    for n in traffic["warm_prompts"]:
        warmed |= _programs(traffic, n)
    need = set()
    for n in range(traffic["prompt"]["min"], traffic["prompt"]["max"] + 1):
        need |= _programs(traffic, n)
    for n in longdoc_chat.long_lengths(traffic):
        need |= _programs(traffic, n)
        need |= _programs(traffic, n + traffic["long"]["answer"] - 1)
    assert need <= warmed, sorted(need - warmed)


def test_the_plan_holds_the_long_sessions_first_and_repeats():
    """The twenty lengths, evenly spaced, the same for every seed, before
    the chat lead-in; the window's lengths are the same multisets for
    every seed and no prompt of it is longer than one chunk."""
    from benchmarks.generators import longdoc_chat
    cell = _cell()
    cfg = dict(cell.config, vocab_size=cell.config["vocab_held"])
    a, b = (longdoc_chat.plan(cell.traffic, cfg, seed, 51.0)
            for seed in (2 ** 31 + 1, 2 ** 31 + 2))
    lengths = longdoc_chat.long_lengths(cell.traffic)
    assert lengths[0] == 16384 and lengths[-1] == 28672 and len(lengths) == 20
    for plan in (a, b):
        lead = plan["lead_in"]
        assert [len(r["prompt"]) for r in lead[:20]] == lengths
        assert len(lead) == 20 + cell.traffic["lead_in"]["requests"]
        assert all(len(r["prompt"]) <= 1024 for r in lead[20:])
        assert len(plan["window"]) == round(cell.traffic["rate_rps"] * 51.0)
        assert all(r["prompt"].max() < cell.config["vocab_held"]
                   for r in plan["window"])
    assert sorted(len(r["prompt"]) for r in a["window"]) \
        == sorted(len(r["prompt"]) for r in b["window"])
    assert not np.array_equal(a["lead_in"][0]["prompt"],
                              b["lead_in"][0]["prompt"])


def test_correct_follows_the_long_sessions_on_logits():
    """``drivers/serve_longctx.py``: the rows are the window's finished
    turns and the long sessions' prompts with their first tokens, each
    group padded to its own length; ``mean_gap`` over all of them, and a
    long session not followed is not half a comparison."""
    from benchmarks.drivers import serve_longctx
    cell = _cell(rehearse=True)
    rng = np.random.default_rng(0)

    def rec(n, m, ok=True):
        return {"ok": ok, "prompt": rng.integers(0, 9, n),
                "tokens": list(range(m))}
    records = [rec(5, 9), rec(30, 20), rec(8, 3)]
    longs = [dict(rec(100, 24), served=300), dict(rec(140, 24), served=300)]
    rows, n_window, n_long = serve_longctx._rows(cell, 3, records, longs)
    assert (len(rows), n_window, n_long) == (4, 2, 2)
    assert sorted(len(r[0]) for r in rows[2:]) == [100, 140]
    zs = [np.eye(len(r[1]), 4, dtype=np.float32) for r in rows]
    judged = [[min(j, 3) for j in range(len(r[1]))] for r in rows]
    whole = serve_longctx._compared(cell, rows, zs, judged, n_window,
                                    n_long)
    assert whole["numbers"][0][0] == "mean_gap"
    assert whole["numbers"][0][1] >= 0
    assert whole["notes"]["long_mean_gap"] is not None
    half = serve_longctx._compared(cell, rows[:3], zs[:3], judged[:3],
                                   n_window, n_long)
    assert half["numbers"][0][1] == float("inf")


def test_the_control_fails_and_the_program_passes():
    """Served tokens judged by the reference with every product's operands
    rounded to float8_e4m3fn, at the rehearsal's size and limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, CONTROL, "--workload", CELL, "--seeds", "31",
         "--seconds", "3", "--rehearse"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row, = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert list(row["cases"]) == ["program", "stated", "control"]
    program, control = row["cases"]["program"], row["cases"]["control"]
    assert program["passes"] is True, row
    assert control["passes"] is False, row
    assert control["numbers"]["mean_gap"]["value"] \
        > 10 * control["numbers"]["mean_gap"]["limit"], row
    notes = program["read_not_compared"]
    assert notes["requests_compared"] == 4 and notes["window_rows"] == 2
    assert notes["tpot_p90_ms"] > 0 and notes["failed"] == 0


@pytest.mark.parametrize("fault", ["no_sink", "window_off_by_one",
                                   "full_windowed", "rotary_all_lanes"])
def test_a_part_of_the_mathematics_changed_comes_out_not_correct(fault):
    """The sink left out, the window one short, the full layers read
    through the window's mask, or the rotary on every lane
    (``faulty_mimo.py``), rehearsed through the whole harness."""
    proc, line = run_harness(
        [fault, "--workload", CELL, "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "0"], cwd=REPO, script=FAULTY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["failed"] == 0 and line["correct"] is False
    got = line["compared"]["mean_gap"]
    assert got["value"] > 3 * got["limit"]


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
    # the long sessions hold 100 to 140 keys and read 8 of them a window
    # layer: the window layers' share of all rows read is small
    assert 0 < got["attn.window_rows_share"]["value"] < 50
    assert not [n for n in got if "mfu" in n or "roofline" in n]


def test_the_readers_read_nothing_without_the_counters():
    """A program older than the family has none of its counters: the new
    readers return None and do not raise."""
    from benchmarks.readers import decode_step_share_window_moe as share
    from benchmarks.readers import experts_hit_share_window_moe as hit
    from benchmarks.readers import expert_matmul_roofline as experts
    from benchmarks.readers import gqa_decode_roofline as roof
    from benchmarks.readers import window_rows_share as rows
    cell = _cell()
    run = types.SimpleNamespace(
        reduced=None, cell=cell, peaks=None,
        result={"traced": None, "server_name": "s", "counters": {},
                "window": {"all_requests": []}})
    for name, reader in (("decode.step_roofline.window_moe", share),
                         ("decode.step_mfu.window_moe", share),
                         ("kernel.gqa_decode_roofline", roof),
                         ("attn.window_rows_share", rows),
                         ("moe.experts_hit_share.window_moe", hit),
                         ("kernel.expert_matmul_roofline.window_moe",
                          experts)):
        assert reader.read(run, cell.metric_file(name)["params"]) is None
    run.result["counters"] = {"s_window_rows_read": 10,
                              "s_full_rows_read": 30}
    assert rows.read(run, cell.metric_file("attn.window_rows_share")[
        "params"]) == pytest.approx(25.0)
    # 16 experts held on each of the six routed layers (layer 0 is dense)
    run.result["counters"] = {"s_moe_experts_hit": 48, "s_decode_steps": 2}
    assert hit.read(run, cell.metric_file(
        "moe.experts_hit_share.window_moe")["params"]) == pytest.approx(25.0)
