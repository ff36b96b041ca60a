"""Autotuner bench: tuner winner vs the hand-tuned bench config
(ISSUE 19 acceptance — ``BENCH_tune.json``).

For each zoo net the search runs with the probe budget of a real
``fit(tune="auto")`` cold start. The DEFAULT candidate — exactly the
hand-tuned configuration ``bench.py`` runs (repo knob defaults: remat
off, async window 2) — is always probed
first, so every record carries the honest head-to-head: the tuner's
winner and the hand-tuned baseline scored by the SAME obs probe
harness on the same machine. Recorded per net:

* ``default`` / ``winner`` — the two probe scores (mfu, steps/s);
* ``mfu_delta`` / ``steps_delta`` — winner over default;
* ``search_s`` — total search wall-clock, ``n_probed``/``n_pruned``.

The gate (``--check``): the tuner must strictly beat the hand-tuned
config on MFU for >= 2 nets, and every search must finish inside its
bounded wall-clock (probes carry per-subprocess deadlines; a config
that wedges scores failed and the partials stand).

Usage: python tools/perf/tune_bench.py [--quick] [--check] [--json P]
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

# CPU probes need an explicit MFU denominator (no device table entry)
os.environ.setdefault("MXNET_TPU_OBS_PEAK_FLOPS", "1e12")

NETS = ("mlp", "transformer", "resnet8")


def bench_net(name, batch, steps, max_probes, deadline_s):
    from mxnet_tpu.tune import search
    from mxnet_tpu.tune.__main__ import _zoo
    sym, data_shapes, label_shapes, dtypes = _zoo(name, batch)
    t0 = time.perf_counter()
    cfg = search(sym, data_shapes, label_shapes, optimizer="sgd",
                 mode="auto", probe_steps=steps,
                 probe_deadline_s=deadline_s, max_probes=max_probes,
                 data_dtypes=dtypes, use_store=False,
                 log=lambda m: print("  " + str(m), flush=True))
    wall = round(time.perf_counter() - t0, 2)

    def _pick(s):
        if not s:
            return None
        return {"mfu": s.get("mfu"), "steps_per_sec": s.get("steps_per_sec"),
                "wall_s": s.get("wall_s")}

    win, base = cfg.score, cfg.baseline
    rec = {
        "net": name, "batch": batch, "probe_steps": steps,
        "winner_knobs": cfg.candidate.to_dict(), "source": cfg.source,
        "winner": _pick(win), "default": _pick(base),
        "search_s": wall, "n_probed": cfg.n_probed,
        "n_pruned": cfg.n_pruned,
    }
    if win and base and base.get("mfu"):
        rec["mfu_delta"] = round(win["mfu"] / base["mfu"], 3)
        rec["steps_delta"] = round(
            win["steps_per_sec"] / base["steps_per_sec"], 3)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="2 nets, fewer probes")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless the tuner beats the "
                         "hand-tuned config on >= 2 nets")
    ap.add_argument("--json", default=None, help="write BENCH_tune.json")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--max-probes", type=int, default=4)
    ap.add_argument("--deadline", type=float, default=180.0)
    args = ap.parse_args()

    nets = NETS[:2] if args.quick else NETS
    records = []
    for name in nets:
        print("tune_bench: %s" % name, flush=True)
        batch = 8 if name == "transformer" else 32
        rec = bench_net(name, batch, args.steps,
                        2 if args.quick else args.max_probes,
                        args.deadline)
        records.append(rec)
        print("  winner=%s source=%s mfu_delta=%s search_s=%s"
              % (rec["winner_knobs"], rec["source"],
                 rec.get("mfu_delta"), rec["search_s"]), flush=True)

    beats = sum(1 for r in records
                if r.get("mfu_delta") and r["mfu_delta"] > 1.0)
    out = {
        "metric": "tune_search", "unit": "mfu_ratio_vs_hand_tuned",
        "nets": records,
        "nets_tuner_beats_hand_tuned": beats,
        "total_search_s": round(sum(r["search_s"] for r in records), 2),
    }
    print(json.dumps(out), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")
    if args.check:
        ok = beats >= 2
        print("tune_bench gate: %s (tuner beats hand-tuned on %d nets)"
              % ("PASS" if ok else "FAIL", beats), flush=True)
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
