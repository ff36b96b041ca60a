"""The cell ``sala_serve_longdoc_chat``: its entries in ``BENCHMARK.json``,
found by name (``check_entries``), and its traffic are those it was added
with, its lead-in holds the long sessions first, ``correct`` follows them
and holds what their decode steps selected and read to the reference's,
the float8 control fails where the program and the stated precision pass,
a part of the mathematics left out of the program or a faulty read of the
selected blocks comes out not correct, and its counters read in a traced
rehearsal.

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

CELL = "sala_serve_longdoc_chat"
CONTROL = os.path.join("benchmarks", "tools", "control.py")
FAULTY = os.path.join("tests", "benchmark", "faulty_sala.py")
OWN = ["decode.step_mfu.sparse_linear", "decode.step_roofline.sparse_linear",
       "sparse.blocks_read_share", "kernel.sparse_decode_roofline"]


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell(rehearse=False):
    from benchmarks.lib import spec
    return spec.Cell(REPO, CELL, rehearse=rehearse)


def test_the_cell_and_its_configuration_are_the_issues():
    check_entries(_bench())


def check_entries(bench):
    """The cell and its configuration, found by name: the traffic and
    ``reduced`` it was added with, the four per-layer metrics that are the
    cell's own in the order it brought them, each moving ``tpot_p90_ms``,
    which it reports, and the generic serving metrics that the other two
    serving cells report. Where the entries stand is ``test_spec.py``'s to
    check."""
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["name"] == "minicpm-sala-1chip"
    assert cell["chips"] == 1
    assert cell["traffic"] == "serve_longdoc_chat_steady"
    assert config["reduced"] == ["num_hidden_layers", "mixer_types",
                                 "max_position_embeddings"]
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert [m["name"] for m in bench["per_layer"]
            if m["workloads"] == [CELL]] == OWN
    assert all(m["moves"] == "tpot_p90_ms" for m in bench["per_layer"]
               if m["name"] in OWN)
    assert "tpot_p90_ms" in reports
    # the generic serving metrics, as the second family's cell has them
    other = {m["name"] for m in bench["per_layer"]
             if "sarvam105_serve_reason" in m["workloads"]
             and "opt13_serve_chat" in m["workloads"]}
    assert other <= reports


def test_the_configuration_is_the_catalogs_with_the_issues_cut():
    """Every published key as published; the cut: published layers 9 to
    24 (sparse 9, 16, 17, 22 and twelve lightning layers), a slot of 36 864
    positions; every assumed item of ISSUE 33 part 1 is listed."""
    cfg = _cell().config
    published = cfg["published"]
    assert cfg["num_hidden_layers"] == 16 == len(cfg["mixer_types"])
    assert cfg["mixer_types"] == published["mixer_types"][9:25]
    assert [i + 9 for i, k in enumerate(cfg["mixer_types"])
            if k == "minicpm4"] == [9, 16, 17, 22]
    assert len(published["mixer_types"]) == 32 \
        == published["num_hidden_layers"]
    assert cfg["max_position_embeddings"] == 36864
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["lightning_nh"]) \
        == (4096, 16384, 73448, 32, 2, 128, 32)
    assert cfg["vocab_held"] == cfg["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "mup_denominator", "qk_norm", "sparse_config", "output_gates",
        "use_output_norm", "rotary_pairing", "lightning_decay",
        "departures", "stated_precision", "control_precision"}
    assert cfg["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64}
    assert cfg["deployment"]["pipeline_stages"] == 2
    assert set(cfg["reduced_why"]) == {"num_hidden_layers", "mixer_types",
                                       "max_position_embeddings"}


@pytest.mark.parametrize("group", ["prompt", "answer", "long", "rate",
                                   "server"])
def test_the_traffic_is_the_issues(group):
    traffic = _cell().traffic
    if group == "prompt":
        assert traffic["prompt"]["median"] == 256
        assert traffic["prompt"]["sigma"] == 0.8
        assert traffic["prompt"]["min"] == 64
        # ISSUE 33's range for the largest prompt: one chunk at most
        assert 512 <= traffic["prompt"]["max"] <= 1024 \
            == traffic["prefill_chunk"]
    elif group == "answer":
        assert traffic["answer"]["median"] == 448
        assert traffic["answer"]["sigma"] == 0.35
        assert 256 <= traffic["answer"]["min"] <= 384
        assert traffic["answer"]["max"] == 768
    elif group == "long":
        long = traffic["long"]
        assert (long["sessions"], long["min"], long["max"], long["answer"]) \
            == (10, 24576, 32768, 3584)
        assert (long["check_sessions"], long["check_tokens"]) == (2, 128)
        assert long["max"] + long["answer"] <= 36864
    elif group == "rate":
        knee = traffic["knee"]
        assert traffic["rate_rps"] == pytest.approx(
            knee["share"] * knee["rps"], rel=0.02)
        assert 0.6 <= knee["share"] <= 0.8
        assert len(knee["sweep"]) >= 3
    else:
        assert traffic["max_sequences"] == 24 and traffic["greedy"]
        assert traffic["seq_buckets"][-1] == 36864
        # the smallest long session is past every bucket but the last:
        # one decode bucket from the window's open to its close
        assert traffic["seq_buckets"][-2] <= traffic["long"]["min"]
        assert traffic["driver"] == "serve_longdoc"
        assert traffic["kind"] == "longdoc_chat"


def test_the_plan_holds_the_long_sessions_first_and_repeats():
    """The ten lengths, evenly spaced, the same for every seed, before the
    chat lead-in; the window's lengths are the same multisets for every
    seed and no prompt of it is longer than one chunk."""
    from benchmarks.generators import longdoc_chat
    cell = _cell()
    cfg = dict(cell.config, vocab_size=cell.config["vocab_held"])
    a, b = (longdoc_chat.plan(cell.traffic, cfg, seed, 51.0)
            for seed in (2 ** 31 + 1, 2 ** 31 + 2))
    lengths = longdoc_chat.long_lengths(cell.traffic)
    assert lengths[0] == 24576 and lengths[-1] == 32768 and len(lengths) == 10
    assert max(abs((y - x) - 8192 / 9.0)
               for x, y in zip(lengths, lengths[1:])) < 1
    for plan in (a, b):
        lead = plan["lead_in"]
        assert [len(r["prompt"]) for r in lead[:10]] == lengths
        assert all(r["answer"] == 3584 for r in lead[:10])
        assert len(lead) == 10 + cell.traffic["lead_in"]["requests"]
        assert all(len(r["prompt"]) <= 1024 for r in lead[10:])
        n = len(plan["window"])
        assert n == round(cell.traffic["rate_rps"] * 51.0)
        assert max(len(r["prompt"]) for r in plan["window"]) \
            <= cell.traffic["prefill_chunk"]
        assert min(r["answer"] for r in plan["window"]) \
            >= cell.traffic["answer"]["min"]
    assert sorted(len(r["prompt"]) for r in a["window"]) \
        == sorted(len(r["prompt"]) for r in b["window"])
    assert sorted(r["answer"] for r in a["window"]) \
        == sorted(r["answer"] for r in b["window"])
    assert not np.array_equal(a["lead_in"][0]["prompt"],
                              b["lead_in"][0]["prompt"])
    assert [r["due"] for r in a["window"]] != [r["due"] for r in b["window"]]


def test_correct_follows_the_long_sessions():
    """``drivers/serve_longdoc.py``: the rows the reference follows are the
    window's finished turns, each padded to the window's longest, then the
    long sessions' prompts with their first tokens, padded to theirs."""
    from benchmarks.drivers import serve_longdoc
    cell = _cell(rehearse=True)
    rng = np.random.default_rng(0)

    def rec(n, m, ok=True):
        return {"ok": ok, "prompt": rng.integers(0, 9, n),
                "tokens": list(range(m))}
    records = [rec(5, 9), rec(30, 20), rec(31, 24, ok=False), rec(8, 3)]
    longs = [dict(rec(100, 24), served=300), dict(rec(140, 24), served=300)]
    rows = serve_longdoc.followed_rows(cell, 3, records, longs)
    assert len(rows) == 4
    assert [len(r[0]) + len(r[1]) for r in rows[:2]][0] == 50
    assert all(r[2:] == (64, 32, None) for r in rows[:2])
    assert sorted(len(r[0]) for r in rows[2:]) == [100, 140]
    assert all(r[2:] == (192, 32, None) for r in rows[2:])
    # a long session that served too few tokens, or failed, is not ok
    offered = {"t_open": 10.0, "t_close": 12.0, "everything": [
        types.SimpleNamespace(
            kind="lead_in", prompt=np.zeros(120, np.int32), answer=300,
            error=None, times=[9.0 + 0.01 * i for i in range(served)],
            handle=types.SimpleNamespace(
                exception=None, tokens_so_far=lambda n=served: [1] * n))
        for served in (300, 10)] + [types.SimpleNamespace(
            kind="lead_in", prompt=np.zeros(12, np.int32), answer=5,
            error=None, times=[9.5], handle=None)]}
    kept = {"pos": np.arange(120, 140)}
    got = serve_longdoc.long_sessions(cell, offered, types.SimpleNamespace(
        followed=lambda prompt: kept if len(prompt) == 120 else None))
    assert [r["ok"] for r in got] == [True, False]
    assert all(r["seen"] is kept for r in got)
    assert [len(r["tokens"]) for r in got] == [24, 10]
    assert [r["first_token_before_open"] for r in got] == [True, True]
    # the first finished its 300 tokens at 11.99 s, before the close
    assert [r["resident_at_close"] for r in got] == [False, False]


def test_what_the_decode_steps_selected_and_read_is_held_to_the_reference():
    """``named_blocks``, ``unselected`` and ``selection_numbers``: the
    steps that served the followed tokens after the first, as the program
    kept them (none if it kept too few or other positions); the blocks a
    program without scores names; the share of the judged blocks the
    reference did not select and the distance of what was read."""
    from benchmarks.drivers import serve_longdoc
    cell = _cell(rehearse=True)
    sc = cell.config["assumed"]["sparse_config"]
    assert (sc["block_size"], sc["window_size"], sc["topk"]) == (16, 32, 4)
    prompt, tokens = np.zeros(100, np.int32), list(range(24))
    blocks = np.zeros((30, 2, 2, 4), np.int32)
    seen = {"pos": 100 + np.arange(30), "blocks": blocks,
            "attended": np.ones((30, 2, 4), np.float32)}
    at, got = serve_longdoc.named_blocks((prompt, tokens, 192, 32, seen))
    assert at.tolist() == list(range(100, 123)) and got.shape[0] == 23
    for bad in (None, dict(seen, pos=seen["pos"][:20]),
                dict(seen, pos=seen["pos"] + 1)):
        assert serve_longdoc.named_blocks(
            (prompt, tokens, 192, 32, bad)) is None
    # position 100 lies in block 6: blocks 0 (initial), 5 and 6 (the
    # window) are forced, block 1 is the lowest-numbered other
    plain = serve_longdoc.unselected(cell, at, got)
    assert plain.shape == got.shape
    assert plain[0, 1, 1].tolist() == [0, 5, 6, 1]
    assert plain[-1, 0, 0].tolist() == [0, 6, 7, 1]
    chosen = serve_longdoc._mask(plain[:, 0], 12)
    assert chosen.shape == (23, 2, 12) and chosen.sum() == 23 * 2 * 4
    read = np.full((23, 4), 2.0, np.float32)
    truth = [None, [(chosen, read, read)]]
    miss, gap, notes = serve_longdoc.selection_numbers(
        truth, [None, [(chosen, read)]])
    assert (miss, gap) == (0.0, 0.0)
    assert notes["selection_layers"][0]["selected"] == 184
    other = chosen.copy()
    other[:, :, 1], other[:, :, 2] = False, True      # one block in four
    miss, gap, _n = serve_longdoc.selection_numbers(
        truth, [None, [(other, 0.0 * read)]])
    assert miss == pytest.approx(0.25) and gap == pytest.approx(1.0)
    # nothing kept by the program reads as not correct, not as 0; nor is
    # one long session of two half a comparison
    miss, gap, _n = serve_longdoc.selection_numbers([None, None],
                                                    [None, None])
    assert miss == gap == float("inf")
    rows = [(prompt, [1, 2], 64, 32, None)] + 2 * [
        (prompt, [1, 2], 192, 32, seen)]
    zs = [np.eye(2, 4, dtype=np.float32)] * 3
    judged = [[0, 1]] * 3
    whole = serve_longdoc._compared(
        cell, rows, zs, judged, 1, [None] + 2 * truth[1:],
        [None] + 2 * [[(chosen, read)]])
    assert [(n, v) for n, v, _l in whole["numbers"]] == [
        ("mean_gap", 0.0), ("selection_miss", 0.0), ("attend_gap", 0.0)]
    half = serve_longdoc._compared(
        cell, rows, zs, judged, 1, [None, None] + truth[1:],
        [None, None, [(chosen, read)]])
    assert [v for _n, v, _l in half["numbers"]][1:] == [float("inf")] * 2


def test_the_control_fails_and_the_program_passes_on_every_seed():
    """Served tokens judged by the reference with every product's operands
    rounded to float8_e4m3fn, at the rehearsal's size and against the
    rehearsal's limit; the reference rounded to the stated bfloat16 is
    read beside it (at the rehearsal's size it need not pass: the
    rehearsal's limit is float32's). A program without scores
    (``unselected``) fails by its selection and by what it read, whatever
    its tokens."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, CONTROL, "--workload", CELL, "--seeds", "31,32",
         "--seconds", "3", "--rehearse"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert [r["seed"] for r in rows] == [31, 32]
    for row in rows:
        assert list(row["cases"]) == ["program", "stated", "control",
                                      "unselected"]
        program, control = row["cases"]["program"], row["cases"]["control"]
        stated = row["cases"]["stated"]
        assert program["passes"] is True, row
        assert control["passes"] is False, row
        assert list(control["numbers"]) == ["mean_gap", "selection_miss",
                                            "attend_gap"]
        assert program["numbers"]["selection_miss"]["value"] == 0
        assert program["numbers"]["attend_gap"]["value"] < 1e-5
        plain = row["cases"]["unselected"]
        assert plain["passes"] is False
        assert plain["numbers"]["mean_gap"] == program["numbers"]["mean_gap"]
        for name in ("selection_miss", "attend_gap"):
            assert plain["numbers"][name]["value"] \
                > 3 * plain["numbers"][name]["limit"], row
        assert control["numbers"]["mean_gap"]["value"] \
            > 10 * max(stated["numbers"]["mean_gap"]["value"],
                       control["numbers"]["mean_gap"]["limit"]), row
        notes = program["read_not_compared"]
        assert notes["requests_compared"] == 4 and notes["window_rows"] == 2
        assert notes["long_mean_gap"] is not None
        assert notes["tpot_p90_ms"] > 0 and notes["failed"] == 0


@pytest.mark.parametrize("fault,by", [
    ("no_selection", "selection_miss"), ("no_decay", "mean_gap"),
    ("no_output_norm", "mean_gap"), ("no_gate", "mean_gap"),
    ("other_blocks_read", "attend_gap"), ("nothing_read", "attend_gap")])
def test_a_part_of_the_mathematics_left_out_comes_out_not_correct(fault, by):
    """ISSUE 33 part 6: the selection, the decay, the output norm or a
    gate left out of the program, other blocks read than those selected,
    or nothing read (``faulty_sala.py``), rehearsed through the whole
    harness. ``by`` is the number that sees the fault whatever the size of
    the weights: the selection and the read are held by numbers of their
    own, because at published widths the logits do not show them."""
    proc, line = run_harness(
        [fault, "--workload", CELL, "--seed", str(2 ** 31 + 5),
         "--seconds", "2", "--trace", "0"], cwd=REPO, script=FAULTY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["failed"] == 0 and line["correct"] is False
    assert line["compared"][by]["value"] > 3 * line["compared"][by]["limit"]


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
    # the long sessions hold 7 to 28 blocks and read 4; a chat slot reads
    # all of its few
    assert 10 < got["sparse.blocks_read_share"]["value"] < 100
    assert not [n for n in got if "mfu" in n or "roofline" in n]


def test_the_readers_read_nothing_without_the_counters():
    """A program older than the family has no ``sparse_blocks_read``
    counter: the new readers return None and do not raise."""
    from benchmarks.readers import decode_step_share_sparse_linear as share
    from benchmarks.readers import sparse_decode_roofline as roof
    cell = _cell()
    run = types.SimpleNamespace(
        reduced=None, cell=cell, peaks=None,
        result={"traced": None, "server_name": "s", "counters": {},
                "window": {"all_requests": []}})
    params = cell.metric_file("decode.step_roofline.sparse_linear")["params"]
    assert share.read(run, params) is None
    assert roof.read(run, cell.metric_file(
        "kernel.sparse_decode_roofline")["params"]) is None
