"""ISSUE 19: the configuration autotuner (``mxnet_tpu.tune``).

* **grad_accum cost model** (satellite): the static activation
  high-water prices the microbatch peak inside the ``lax.scan`` carry —
  parity-tested against ``analyze_program_memory`` on the zoo
  transformer at N in {1, 4}.
* **search determinism**: the same (module, budget, seed) yields an
  identical ``TunedConfig`` in static mode — byte-equal dicts.
* **probe isolation**: probes run in subprocesses and leak no counters,
  gauges or executables into the searching process.
* **store**: fingerprint-keyed persistence round-trips; any program
  delta changes the key.
* **fit(tune=)**: the winner is applied (counter-asserted), explicit
  user arguments keep precedence, and the knob overrides are
  fit-scoped (restored when fit returns).
* **zero-cost gate**: with ``MXNET_TPU_TUNE`` unset, a full fit never
  imports ``mxnet_tpu.tune`` (subprocess-asserted).

The CI-scale end-to-end pass (bounded search + warm-restart
zero-compile) lives in ``tools/tune_smoke.py``; the tuner-vs-hand-tuned
MFU evidence in ``tools/perf/tune_bench.py`` -> ``BENCH_tune.json``.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, sym
from mxnet_tpu.models import transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    d = sym.Variable("data")
    h = sym.FullyConnected(d, num_hidden=16, name="fc1")
    h = sym.Activation(h, act_type="relu")
    h = sym.FullyConnected(h, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(h, name="softmax")


def _tfm():
    return transformer.get_symbol(vocab_size=64, num_layers=2,
                                  d_model=32, n_heads=2, seq_len=16)


# ===================================================== grad_accum model


class TestGradAccumCostModel:
    def test_act_peak_prices_microbatch(self):
        from mxnet_tpu.analysis import tuning
        shapes = {"data": (8, 16), "softmax_label": (8, 16)}
        batch_inputs = ["data", "softmax_label"]
        r1 = tuning.cost_report(_tfm(), shapes,
                                batch_inputs=batch_inputs)
        r4 = tuning.cost_report(_tfm(), shapes, grad_accum=4,
                                batch_inputs=batch_inputs)
        c1, c4 = r1.extras["cost"], r4.extras["cost"]
        assert c1["grad_accum"] == 1 and c4["grad_accum"] == 4
        # no scan at N=1: no gradient carry priced
        assert c1["grad_carry_bytes"] == 0
        assert c4["grad_carry_bytes"] > 0
        # microbatch activations (carry excluded) must shrink
        act1 = c1["activation_peak_bytes"] - c1["grad_carry_bytes"]
        act4 = c4["activation_peak_bytes"] - c4["grad_carry_bytes"]
        assert act4 < act1
        # FLOPs stay full-batch: the scan still runs all N microbatches
        assert c4["flops"] == c1["flops"]

    def test_accum_must_divide_batch(self):
        from mxnet_tpu.analysis import tuning
        shapes = {"data": (6, 16), "softmax_label": (6, 16)}
        r = tuning.cost_report(_tfm(), shapes, grad_accum=4,
                               batch_inputs=["data", "softmax_label"])
        c = r.extras["cost"]
        # 4 does not divide 6: no scaling, no carry — full-batch pricing
        assert c["grad_carry_bytes"] == 0

    @pytest.mark.slow
    def test_parity_program_memory_transformer(self):
        """The model's N=1 -> N=4 activation scaling must match the
        measured program twin: the real grad program at the full batch
        vs at the microbatch slice (what one ``lax.scan`` iteration
        materializes), both via ``analyze_program_memory``."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.analysis import analyze_program_memory, tuning

        net = _tfm()
        B, N = 8, 4
        shapes = {"data": (B, 16), "softmax_label": (B, 16)}
        bi = ["data", "softmax_label"]
        c1 = tuning.cost_report(net, shapes,
                                batch_inputs=bi).extras["cost"]
        c4 = tuning.cost_report(net, shapes, grad_accum=N,
                                batch_inputs=bi).extras["cost"]
        model_ratio = (
            (c1["activation_peak_bytes"] - c1["grad_carry_bytes"])
            / (c4["activation_peak_bytes"] - c4["grad_carry_bytes"]))

        def measured_peak(b):
            m = mx.mod.Module(net, context=mx.cpu(0))
            m.bind(data_shapes=[("data", (b, 16))],
                   label_shapes=[("softmax_label", (b, 16))])
            m.init_params(mx.init.Xavier())
            ex = m._exec
            fn = ex._fn
            params = {n: a.data for n, a in ex.arg_dict.items()
                      if n not in ("data", "softmax_label")}
            inputs = {n: ex.arg_dict[n].data
                      for n in ("data", "softmax_label")}
            key = jax.random.PRNGKey(0)

            def g(p):
                def loss_fn(p_):
                    return fn({**p_, **inputs}, {}, key, True)
                (outs, new_aux), vjp = jax.vjp(loss_fn, p)
                cts = [jnp.ones_like(o) for o in outs]
                return vjp((cts, {k: jnp.zeros_like(v)
                                  for k, v in new_aux.items()}))[0]

            return analyze_program_memory(g, params).extras[
                "program_memory"]["activation_peak_bytes"]

        measured_ratio = measured_peak(B) / measured_peak(B // N)
        # both ratios sit between 1 (all weight-side) and N (all
        # batch-side); the model must land within 35% of the program
        assert 1.0 < model_ratio <= N + 0.01
        assert 1.0 < measured_ratio <= N + 0.01
        assert abs(model_ratio - measured_ratio) <= 0.35 * measured_ratio, \
            "model %.2fx vs program %.2fx" % (model_ratio, measured_ratio)


# ====================================================== search statics


class TestSpaceAndPrune:
    def test_space_deterministic_default_first(self):
        from mxnet_tpu.tune.space import DEFAULT, enumerate_space
        s1 = enumerate_space(32)
        s2 = enumerate_space(32)
        assert s1 == s2
        assert s1[0] == DEFAULT
        assert len(set(s1)) == len(s1)
        # grad_accum rungs must divide the batch
        assert {c.grad_accum for c in enumerate_space(6)} == {1, 2}

    def test_budget_prunes_and_audits(self):
        from mxnet_tpu.tune.prune import static_rank
        from mxnet_tpu.tune.space import enumerate_space
        shapes = {"data": (8, 16), "softmax_label": (8, 16)}
        cands = enumerate_space(8)
        with profiler.counter_delta() as d:
            kept, audit = static_rank(
                _tfm(), shapes, ["data", "softmax_label"], cands,
                budget_bytes=1)   # nothing fits in 1 byte
        assert kept == []
        assert d.get("tune_pruned") == len(cands)
        assert all(a["fate"] == "pruned" for a in audit)
        assert all("budget" in a["why"] for a in audit)
        # unbudgeted: everything survives, rank is deterministic
        kept2, _ = static_rank(_tfm(), shapes,
                               ["data", "softmax_label"], cands)
        kept3, _ = static_rank(_tfm(), shapes,
                               ["data", "softmax_label"], cands)
        assert kept2 == kept3 and len(kept2) == len(cands)

    def test_static_rank_multi_device_layout_ties(self):
        """Regression: DEFAULT (layout=None) ties the top-ranked layout
        candidate with default knobs on the whole score prefix, so the
        final tie-break must be total-orderable — the old raw-Candidate
        tail raised TypeError comparing a None layout against a tuple,
        crashing every multi-device search."""
        from mxnet_tpu.analysis.tuning import rank_layouts
        from mxnet_tpu.tune.prune import static_rank
        from mxnet_tpu.tune.space import DEFAULT, enumerate_space
        shapes = {"data": (8, 16), "softmax_label": (8, 16)}
        layout_rank = rank_layouts(8, param_bytes=1 << 20,
                                   activation_bytes=1 << 18)
        layouts = [(r["data"], r["fsdp"], r["tp"]) for r in layout_rank]
        cands = enumerate_space(8, n_devices=8, layouts=layouts)
        assert DEFAULT in cands
        kept, audit = static_rank(_tfm(), shapes,
                                  ["data", "softmax_label"], cands,
                                  layout_rank=layout_rank)
        assert len(kept) == len(cands)
        # the rank is a pure total order: input order cannot change it
        kept2, _ = static_rank(_tfm(), shapes, ["data", "softmax_label"],
                               list(reversed(cands)),
                               layout_rank=layout_rank)
        assert kept == kept2

    def test_rank_layouts_comm_model(self):
        from mxnet_tpu.analysis.tuning import rank_layouts
        recs = rank_layouts(8, param_bytes=1 << 20,
                            activation_bytes=1 << 18)
        assert all(r["data"] * r["fsdp"] * r["tp"] == 8 for r in recs)
        # pure data-parallel ranks ahead of pure TP for a param-dominated
        # net (TP all-reduces activations per layer but FSDP/TP shard
        # memory; comm model orders, mem breaks ties)
        assert recs == sorted(recs, key=lambda r: (r["comm_bytes"],
                                                   r["mem_bytes"],
                                                   -r["data"]))


class TestSearchDeterminism:
    def test_static_search_identical(self, tmp_path):
        from mxnet_tpu.tune import search
        net = _mlp()
        kw = dict(optimizer="sgd", budget="1G", mode="static",
                  use_store=False, seed=3)
        a = search(net, [("data", (16, 8))], [("softmax_label", (16,))],
                   **kw)
        b = search(net, [("data", (16, 8))], [("softmax_label", (16,))],
                   **kw)
        # identical up to wall-clock (searched_s is timing, not decision)
        da = {k: v for k, v in a.to_dict().items() if k != "searched_s"}
        db = {k: v for k, v in b.to_dict().items() if k != "searched_s"}
        assert da == db
        assert a.source == "static"
        assert a.key == b.key

    def test_program_key_sensitivity(self):
        from mxnet_tpu.tune.store import program_key
        j = _mlp().tojson()
        base = program_key(j, [("data", (16, 8))], [], "sgd", {}, "1G", 1)
        assert base == program_key(j, [("data", (16, 8))], [], "sgd",
                                   {}, "1G", 1)
        assert base != program_key(j, [("data", (32, 8))], [], "sgd",
                                   {}, "1G", 1)
        assert base != program_key(j, [("data", (16, 8))], [], "adam",
                                   {}, "1G", 1)
        assert base != program_key(j, [("data", (16, 8))], [], "sgd",
                                   {}, "2G", 1)
        assert base != program_key(j, [("data", (16, 8))], [], "sgd",
                                   {}, "1G", 8)
        assert base != program_key(_tfm().tojson(), [("data", (16, 8))],
                                   [], "sgd", {}, "1G", 1)


# ========================================================== the store


class TestStore:
    def test_round_trip(self, tmp_path, monkeypatch):
        from mxnet_tpu.tune.space import Candidate
        from mxnet_tpu.tune.store import (TunedConfig, load_config,
                                          store_config)
        monkeypatch.setenv("MXNET_TPU_TUNE_STORE", str(tmp_path))
        cfg = TunedConfig(candidate=Candidate(grad_accum=4,
                                              async_window=0),
                          key="k" * 64, source="probe",
                          score={"mfu": 0.5}, searched_s=1.25,
                          n_probed=3, n_pruned=7)
        with profiler.counter_delta() as d:
            path = store_config(cfg)
            got = load_config("k" * 64)
        assert path and os.path.exists(path)
        assert d.get("tune_store_write") == 1
        assert d.get("tune_store_hit") == 1
        assert got.to_dict() == cfg.to_dict()
        assert got.candidate.grad_accum == 4

    def test_miss_and_future_version(self, tmp_path, monkeypatch):
        from mxnet_tpu.tune.store import load_config
        monkeypatch.setenv("MXNET_TPU_TUNE_STORE", str(tmp_path))
        with profiler.counter_delta() as d:
            assert load_config("absent" * 10) is None
        assert d.get("tune_store_miss") == 1
        with open(os.path.join(str(tmp_path),
                               "tune-%s.json" % ("v" * 64)), "w") as f:
            json.dump({"version": 99, "candidate": {}}, f)
        assert load_config("v" * 64) is None

    def test_older_record_loads_and_its_extra_fields_are_ignored(
            self, tmp_path, monkeypatch):
        """A record stored when the space still had scan-over-layers and
        the grouped update is read; the two fields are dropped."""
        from mxnet_tpu.tune.space import Candidate
        from mxnet_tpu.tune.store import load_config
        monkeypatch.setenv("MXNET_TPU_TUNE_STORE", str(tmp_path))
        with open(os.path.join(str(tmp_path),
                               "tune-%s.json" % ("o" * 64)), "w") as f:
            json.dump({"version": 1, "key": "o" * 64, "source": "probe",
                       "candidate": {"remat": "auto", "grad_accum": 2,
                                     "scan_layers": "auto",
                                     "group_update": False,
                                     "async_window": 0, "layout": None}},
                      f)
        got = load_config("o" * 64)
        assert got.candidate == Candidate(remat="auto", grad_accum=2,
                                          async_window=0)
        assert set(got.candidate.knobs()) == {"MXNET_TPU_REMAT",
                                              "MXNET_TPU_ASYNC_WINDOW"}
        assert set(got.candidate.knobs()) <= set(mx.config.KNOBS)

    def test_no_store_dir_is_none(self, monkeypatch):
        from mxnet_tpu.tune.space import Candidate
        from mxnet_tpu.tune.store import TunedConfig, store_config
        monkeypatch.delenv("MXNET_TPU_TUNE_STORE", raising=False)
        assert store_config(TunedConfig(candidate=Candidate(),
                                        key="x" * 64)) is None


# =================================================== probes + fit(tune=)


def _fit_data(nbatch=4, batch=8):
    X = np.zeros((nbatch * batch, 8), np.float32)
    Y = np.zeros((nbatch * batch,), np.float32)
    return mx.io.NDArrayIter(X, Y, batch_size=batch)


@pytest.mark.slow
class TestProbeIsolation:
    def test_probes_leak_nothing_into_parent(self, tmp_path):
        from mxnet_tpu.tune import search
        before_counters = dict(profiler.counters())
        before_execs = {e.get("label")
                        for e in mx.obs.report()["executors"]}
        cfg = search(_mlp(), [("data", (8, 8))],
                     [("softmax_label", (8,))], optimizer="sgd",
                     mode="auto", probe_steps=2, max_probes=1,
                     probe_deadline_s=240, use_store=False)
        # max_probes budgets the RANKED candidates; the default is
        # always probed in addition (the MAX_PROBES help-text contract)
        assert cfg.n_probed == 2
        after = profiler.counters()
        # the probe's own loop/obs counters must NOT appear here;
        # only the tuner's bookkeeping may move
        moved = {k for k in after
                 if after[k] != before_counters.get(k, 0)}
        # the static phase legitimately moves analysis_* hazard counters
        assert all(k.startswith(("tune", "analysis")) for k in moved), \
            moved
        # no executable registered in the parent's obs accounting
        after_execs = {e.get("label")
                       for e in mx.obs.report()["executors"]}
        assert after_execs == before_execs
        # probe subprocesses must not leave knob overrides behind
        assert mx.config.get("MXNET_TPU_ASYNC_WINDOW") == 2

    def test_failed_probe_keeps_partials(self):
        from mxnet_tpu.tune.probe import run_probe
        # an unparseable spec: the child dies, the parent scores it
        # failed and moves on — no exception, counters tell the story
        with profiler.counter_delta() as d:
            score = run_probe({"candidate": {}, "symbol": "not json",
                               "data_shapes": [], "label_shapes": [],
                               "steps": 1, "optimizer": "sgd"},
                              deadline_s=240)
        assert score["ok"] is False and score["why"]
        assert d.get("tune_probe") == 1
        assert d.get("tune_probe_fail") == 1


@pytest.mark.slow
class TestFitTune:
    def test_fit_applies_static_winner(self):
        with profiler.counter_delta() as d:
            mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
            mod.fit(_fit_data(), num_epoch=1, tune="static",
                    optimizer_params={"learning_rate": 0.01})
        assert d.get("tune_applied") == 1
        assert not d.get("tune_probe")   # static mode: no probes
        assert not d.get("loop_recompile")

    def test_explicit_args_beat_tuned(self):
        # caller's grad_accum wins over whatever the tuner picked
        mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
        mod.fit(_fit_data(), num_epoch=1, tune="static", grad_accum=2,
                optimizer_params={"learning_rate": 0.01})
        assert mod._grad_accum == 2

    def test_tuned_knobs_do_not_outlive_fit(self):
        # the winner's config overrides are fit-scoped: a later fit
        # with tune off must not inherit them, and a pre-existing user
        # override must survive the tuned fit untouched
        from mxnet_tpu import config as _cfg
        knobs = ("MXNET_TPU_REMAT", "MXNET_TPU_ASYNC_WINDOW")
        _cfg.set("MXNET_TPU_REMAT", "off")
        try:
            before = _cfg.snapshot_overrides(knobs)
            mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
            mod.fit(_fit_data(), num_epoch=1, tune="static",
                    optimizer_params={"learning_rate": 0.01})
            assert _cfg.snapshot_overrides(knobs) == before
        finally:
            for k in knobs:
                _cfg.reset(k)


def test_config_snapshot_restore_overrides():
    """The scoped-set primitive fit(tune=) rides: restore re-instates
    old overrides and DROPS ones that did not exist (back to
    environment/default, not a frozen copy of the computed value)."""
    from mxnet_tpu import config as _cfg
    names = ("MXNET_TPU_REMAT", "MXNET_TPU_ASYNC_WINDOW")
    _cfg.set("MXNET_TPU_ASYNC_WINDOW", 3)
    try:
        snap = _cfg.snapshot_overrides(names)
        _cfg.set("MXNET_TPU_REMAT", "auto")
        _cfg.set("MXNET_TPU_ASYNC_WINDOW", 0)
        _cfg.restore_overrides(snap)
        assert _cfg.get("MXNET_TPU_ASYNC_WINDOW") == 3
        # REMAT had no override: restore drops it entirely (back to
        # environment/default) instead of pinning the computed value
        assert _cfg.snapshot_overrides(names) == snap
        assert snap["MXNET_TPU_REMAT"] is _cfg._NO_OVERRIDE
    finally:
        for k in names:
            _cfg.reset(k)


# ======================================================= zero-cost gate


def test_tune_off_is_zero_cost():
    """With MXNET_TPU_TUNE unset, a full fit must never import the
    tuner package nor touch a tune_* counter."""
    prog = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import numpy as np
        import mxnet_tpu as mx
        from mxnet_tpu import sym
        d = sym.Variable("data")
        net = sym.SoftmaxOutput(
            sym.FullyConnected(d, num_hidden=4), name="softmax")
        X = np.zeros((16, 8), np.float32)
        Y = np.zeros((16,), np.float32)
        it = mx.io.NDArrayIter(X, Y, batch_size=8)
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1,
                optimizer_params={"learning_rate": 0.01})
        bad_mods = [m for m in sys.modules
                    if m.startswith("mxnet_tpu.tune")]
        assert not bad_mods, bad_mods
        bad_counters = [k for k in mx.profiler.counters()
                        if k.startswith("tune")]
        assert not bad_counters, bad_counters
        print("TUNE_ZERO_COST_OK")
    """) % (REPO,)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    for k in list(env):
        if k.startswith("MXNET_TPU_TUNE"):
            env.pop(k)
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr
    assert "TUNE_ZERO_COST_OK" in res.stdout
