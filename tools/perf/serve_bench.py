"""Serving throughput: sequential batch-1 prediction vs the dynamic
batcher (mxnet_tpu/serve), closed-loop load generator.

Two models, the same pair the trainer-step bench uses:

* the doc-evidence MLP (Dense 128 relu -> Dense 10) — dispatch-bound,
  where batching pays the most;
* a small ResNet stem (conv/BN/pool/FC mix) — some real compute per
  request.

Protocol: ``C`` closed-loop clients (each submits one request, waits
for its result, repeats — the classic closed-loop load model) against
one InferenceServer; the baseline is ONE caller doing batch-1 forwards
back-to-back, i.e. exactly what today's ``Predictor`` offers concurrent
traffic once serialized. Reported per model: requests/sec both ways,
speedup, p50/p95/p99 latency under load, batch occupancy and the
compile count (must equal the touched bucket set — zero steady-state
recompiles).

Usage: python tools/perf/serve_bench.py [--quick] [--json PATH]
"""
import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

import numpy as np


def _build_mlp():
    from mxnet_tpu.gluon import nn
    net = nn.Sequential()
    net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    return net, (64,)


def _build_resnet_stem():
    from mxnet_tpu.gluon import nn
    net = nn.Sequential()
    net.add(nn.Conv2D(16, kernel_size=7, strides=2, padding=3),
            nn.BatchNorm(),
            nn.Activation("relu"),
            nn.MaxPool2D(pool_size=3, strides=2, padding=1),
            nn.Flatten(),
            nn.Dense(10))
    return net, (3, 32, 32)


def _sequential_rps(net, xs, n_req):
    """One caller, batch-1 forwards back-to-back — the Predictor
    status quo for concurrent traffic."""
    import mxnet_tpu as mx
    # warmup / compile
    float(np.asarray(net(mx.nd.array(xs[0][None])).asnumpy()).sum())
    t0 = time.perf_counter()
    for i in range(n_req):
        out = net(mx.nd.array(xs[i % len(xs)][None]))
        out.asnumpy()                 # fence: latency the caller sees
    dt = time.perf_counter() - t0
    return n_req / dt


def _served_rps(net, xs, n_req, clients, max_batch):
    from mxnet_tpu import serve

    srv = serve.InferenceServer(net, max_batch_size=max_batch,
                                max_delay_us=2000,
                                name="serve_bench")
    try:
        # warm the batch-bucket grid so the timed window is steady-state
        for b in srv.buckets.batch_buckets:
            srv.submit(np.stack(xs[:1] * b), batched=True).result(60)
        compiles_warm = srv.stats()["compiles"]
        srv.latency.reset()     # warmup compiles are not serving latency
        per_client = n_req // clients
        errors = []

        def client(cid):
            try:
                for i in range(per_client):
                    srv.submit(xs[(cid + i * clients) % len(xs)]) \
                        .result(timeout=120)
            except Exception as exc:               # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise errors[0]
        stats = srv.stats()
        recompiles = stats["compiles"] - compiles_warm
        return per_client * clients / dt, stats, recompiles
    finally:
        srv.close()


def _bench_one(build, n_req, clients, max_batch):
    import mxnet_tpu as mx

    net, sample_shape = build()
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(0)
    xs = [rng.rand(*sample_shape).astype(np.float32) for _ in range(64)]
    net(mx.nd.array(xs[0][None]))     # shape probe

    seq_rps = _sequential_rps(net, xs, max(n_req // 4, 20))
    served_rps, stats, recompiles = _served_rps(net, xs, n_req, clients,
                                                max_batch)
    lat = stats["latency"] or {}
    return {
        "n_requests": n_req,
        "clients": clients,
        "max_batch": max_batch,
        "sequential_rps": round(seq_rps, 1),
        "served_rps": round(served_rps, 1),
        "speedup": round(served_rps / seq_rps, 2),
        "p50_ms": lat.get("p50_ms"),
        "p95_ms": lat.get("p95_ms"),
        "p99_ms": lat.get("p99_ms"),
        "avg_batch_rows": stats["avg_batch_rows"],
        "occupancy": stats["occupancy"],
        "bucket_compiles": stats["compiles"],
        "steady_state_recompiles": recompiles,
    }


# ===================================================================
# Generative decode: continuous batching vs sequential batch-1
# ===================================================================

_DECODE_GEO = dict(vocab_size=128, num_layers=2, d_model=32, n_heads=2,
                   seq_len=64)


def _build_decode_module(seed=11):
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(**_DECODE_GEO)
    mod = mx.mod.Module(net, context=mx.cpu())
    s = _DECODE_GEO["seq_len"]
    mod.bind(data_shapes=[("data", (1, s))],
             label_shapes=[("softmax_label", (1, s))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Uniform(0.05))
    return mod


def _decode_prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, _DECODE_GEO["vocab_size"],
                             size=rng.randint(2, 12)))
            for _ in range(n)]


def _decode_closed_loop(mod, clients, n_req, new_tokens, max_sequences):
    """``clients`` closed-loop generators against one GenerativeServer;
    returns (tok/s, ttft snapshot, tpot snapshot, steady recompiles,
    executable bound). ``max_sequences=1`` with ``clients=1`` IS the
    sequential batch-1 baseline — same engine, no co-residency."""
    from mxnet_tpu import profiler, serve
    name = "dbench%d_%d" % (clients, max_sequences)
    srv = serve.GenerativeServer(mod, n_heads=_DECODE_GEO["n_heads"],
                                 max_sequences=max_sequences, page=16,
                                 int8=False, queue_bound=4 * clients + 8,
                                 name=name)
    prompts = _decode_prompts(64)
    try:
        # warmup wave: the LONGEST prompt in the pool decodes to the
        # deepest position any timed request reaches, so every
        # prompt/decode bucket is compiled before the timed window —
        # one stray bucket compile (~400ms) would otherwise dominate a
        # sub-second measurement
        longest = max(prompts, key=len)
        warm = [srv.submit_generate(longest, max_new_tokens=new_tokens)
                for _ in range(min(clients, max_sequences) or 1)]
        for h in warm:
            h.result(timeout=300)
        compiles_warm = profiler.get_counter(name + "_compile")
        srv.latency.reset()
        per_client = max(n_req // clients, 1)
        tokens_out = [0] * clients
        errors = []

        def client(cid):
            try:
                for i in range(per_client):
                    h = srv.submit_generate(
                        prompts[(cid + i * clients) % len(prompts)],
                        max_new_tokens=new_tokens)
                    tokens_out[cid] += len(h.result(timeout=300))
            except Exception as exc:               # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise errors[0]
        st = srv.stats()
        recompiles = profiler.get_counter(name + "_compile") - compiles_warm
        return (sum(tokens_out) / dt, st["ttft"], st["tpot"], recompiles,
                st["executable_bound"])
    finally:
        srv.close()


_COLD_START_SCRIPT = r"""
import os, sys, time, json
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(root)r)
t_proc = time.perf_counter()
import mxnet_tpu as mx
from mxnet_tpu.models import transformer
net = transformer.get_symbol(**%(geo)r)
mod = mx.mod.Module(net, context=mx.cpu())
s = %(geo)r["seq_len"]
mod.bind(data_shapes=[("data", (1, s))],
         label_shapes=[("softmax_label", (1, s))])
mx.random.seed(11)
mod.init_params(mx.init.Uniform(0.05))
srv = mx.serve.GenerativeServer(mod, n_heads=%(geo)r["n_heads"],
                                max_sequences=4, page=16, int8=False,
                                name="coldbench")
t0 = time.perf_counter()
h = srv.submit_generate([3, 1, 4, 1, 5], max_new_tokens=4)
first = next(iter(h))
ttft = time.perf_counter() - t0
h.result(timeout=300)
srv.close()
snap = mx.obs.report()
backend = len([c for c in snap["compiles"] if c.get("scope") == "coldbench"])
print(json.dumps({"ttft_s": ttft, "backend_compiles": backend,
                  "proc_s": time.perf_counter() - t_proc}))
"""


def _cold_start_ttft():
    """Fresh process -> first generated token. Returns the subprocess's
    own measurement."""
    import subprocess
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "..")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    code = _COLD_START_SCRIPT % {"root": os.path.abspath(root),
                                 "geo": _DECODE_GEO}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError("cold-start probe failed:\n" + out.stderr)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _bench_decode(quick=False, reps=1):
    """The ISSUE 16 acceptance table: aggregate tok/s continuous vs
    sequential batch-1, TTFT/TPOT percentiles, zero steady-state
    recompiles, cold-start-to-first-token."""
    mod = _build_decode_module()
    new_tokens = 8 if quick else 16
    client_loads = [8] if quick else [8, 32]
    out = {"new_tokens_per_request": new_tokens,
           "geometry": dict(_DECODE_GEO)}

    # baseline: batch-1 SCHEDULING on the SAME deployment — one
    # closed-loop client against the identical 32-slot server, so the
    # cache geometry and executable set match and the comparison
    # isolates the scheduling policy (the Orca/vLLM experimental
    # control), not a smaller cache's cheaper step
    seq_tps = 0.0
    for _ in range(reps):
        tps, _, _, _, _ = _decode_closed_loop(
            mod, clients=1, n_req=4 if quick else 12,
            new_tokens=new_tokens, max_sequences=32)
        seq_tps = max(seq_tps, tps)
    out["sequential_tps"] = round(seq_tps, 1)

    for clients in client_loads:
        best = None
        for _ in range(reps):
            tps, ttft, tpot, recompiles, bound = _decode_closed_loop(
                mod, clients=clients,
                n_req=2 * clients if quick else 3 * clients,
                new_tokens=new_tokens, max_sequences=32)
            if best is None or tps > best["tps"]:
                best = {"tps": tps, "ttft": ttft, "tpot": tpot,
                        "recompiles": recompiles, "bound": bound}
        assert best["recompiles"] == 0, (
            "steady-state decode recompiled %d times" % best["recompiles"])
        out["clients_%d" % clients] = {
            "continuous_tps": round(best["tps"], 1),
            "speedup_vs_sequential": round(best["tps"] / seq_tps, 2),
            "ttft": best["ttft"],
            "tpot": best["tpot"],
            "steady_state_recompiles": best["recompiles"],
            "executable_bound": best["bound"],
        }
        print("decode c=%-3d seq %7.1f tok/s  continuous %8.1f tok/s  "
              "%5.2fx  ttft p50 %s ms  tpot p50 %s ms  recompiles %d"
              % (clients, seq_tps, best["tps"], best["tps"] / seq_tps,
                 (best["ttft"] or {}).get("p50_ms"),
                 (best["tpot"] or {}).get("p50_ms"),
                 best["recompiles"]))

    if not quick:
        cold = _cold_start_ttft()
        out["cold_start"] = {"ttft_s": round(cold["ttft_s"], 3)}
        print("decode cold-start ttft: %.3fs" % cold["ttft_s"])
    return out


# ===================================================================
# Fleet: multi-replica gateway scaling + kill-one-under-load
# ===================================================================

_FLEET_STEP_MS = 20.0
_FLEET_SLOTS = 8
_FLEET_NEW_TOKENS = 32


def _fleet_closed_loop(gw, clients, n_req, new_tokens):
    """``clients`` closed-loop generators against one Gateway; returns
    (aggregate tok/s, gateway stats snapshot)."""
    prompts = _decode_prompts(64)
    tokens_out = [0] * clients
    errors = []

    def client(cid):
        try:
            per = max(n_req // clients, 1)
            for i in range(per):
                h = gw.submit_generate(
                    prompts[(cid + i * clients) % len(prompts)],
                    max_new_tokens=new_tokens)
                tokens_out[cid] += len(h.result(timeout=600))
        except Exception as exc:                           # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return sum(tokens_out) / dt, gw.stats()


def _fleet_scaling(quick=False):
    """Aggregate tok/s and gateway TTFT for 1/2/3 DEVICE-PACED replicas
    at matched per-replica deployments (1xS vs 2xS vs 3xS slots).

    Replicas are real subprocesses behind the real wire, but their
    decode step is the scripted simulator's timed wait — the TPU regime
    where the device does the work and the host idles between steps.
    The host-side fleet fabric (gateway scheduler, routing, sockets,
    per-token frame handling) is measured for real; only device time is
    simulated. On this device-less bench host a REAL model's decode
    step is host CPU, so N co-resident replica processes just split one
    core N ways — that anti-scaling measures the box, not the gateway
    (recorded honestly in the ``real_model`` section)."""
    from mxnet_tpu.fleet import Gateway
    spec = {"kind": "scripted", "slots": _FLEET_SLOTS,
            "step_ms": _FLEET_STEP_MS, "prefill_ms_per_token": 1.0,
            "name": "benchrep"}
    new_tokens = 16 if quick else _FLEET_NEW_TOKENS
    out = {
        "mode": "device_paced_scripted_replicas",
        "pacing": {"step_ms": _FLEET_STEP_MS,
                   "slots_per_replica": _FLEET_SLOTS,
                   "new_tokens_per_request": new_tokens,
                   "device_paced_ceiling_tps_per_replica": round(
                       _FLEET_SLOTS / (_FLEET_STEP_MS / 1e3), 1)},
    }
    base_tps = None
    for n in ((1, 2) if quick else (1, 2, 3)):
        gw = Gateway(spec=spec, replicas=n, port=None, stats_period=0.2,
                     name="bench_fleet%d" % n)
        try:
            live = gw.wait_ready(n, timeout=300.0)
            assert live == n, "only %d/%d replicas live" % (live, n)
            clients = 2 * _FLEET_SLOTS * n
            n_req = (2 if quick else 4) * clients
            tps, st = _fleet_closed_loop(gw, clients, n_req, new_tokens)
        finally:
            gw.close(drain=False, timeout=60.0)
        rec = {"replicas": n, "clients": clients,
               "aggregate_tps": round(tps, 1),
               "ttft": st["ttft"], "tpot": st["tpot"],
               "failover": st["failover"], "shed": st["shed"]}
        if base_tps is None:
            base_tps = tps
        else:
            rec["speedup_vs_1_replica"] = round(tps / base_tps, 2)
        out["replicas_%d" % n] = rec
        print("fleet r=%d  %8.1f tok/s  %s  ttft p50 %s ms p99 %s ms"
              % (n, tps,
                 ("%.2fx" % (tps / base_tps)) if n > 1 else "  1x ",
                 (st["ttft"] or {}).get("p50_ms"),
                 (st["ttft"] or {}).get("p99_ms")))
    ratio = out["replicas_2"]["speedup_vs_1_replica"]
    assert ratio >= 1.6, (
        "2-replica aggregate only %.2fx of 1 replica on matched "
        "per-replica deployments (want >= 1.6x)" % ratio)
    return out


def _fleet_kill_under_load():
    """REAL model replicas: kill one mid-stream under load; record
    recovery time and assert zero token duplication (every stream
    bit-equal to a single-server reference)."""
    import os as _os
    import signal as _signal
    from mxnet_tpu.fleet import Gateway
    from mxnet_tpu.fleet.replica import build_from_spec
    geo = dict(_DECODE_GEO, seq_len=32)
    spec = {"kind": "transformer", "geo": geo, "seed": 11, "slots": 4,
            "page": 8, "name": "benchkill"}
    ref_srv = build_from_spec(dict(spec, name="benchkillref"))
    prompts = [[3, 1, 4], [1, 5, 9], [2, 6], [5, 3, 5],
               [8, 9, 7], [3, 2], [7, 7, 1], [9, 4]]
    new_tokens = 12
    try:
        ref = {tuple(p): ref_srv.submit_generate(
                   p, max_new_tokens=new_tokens).result(timeout=600)
               for p in prompts}
    finally:
        ref_srv.close()
    gw = Gateway(spec=spec, replicas=2, port=None, stats_period=0.2,
                 name="bench_kill")
    try:
        assert gw.wait_ready(2, timeout=600.0) == 2
        handles = [(p, gw.submit_generate(p, max_new_tokens=new_tokens))
                   for p in prompts]
        # kill a replica once streams are moving
        deadline = time.perf_counter() + 60
        victim_pid = None
        while time.perf_counter() < deadline and victim_pid is None:
            st = gw.stats()
            for r in st["replicas"]:
                if r["assigned"] > 0 and r["stats"].get("pid"):
                    victim_pid = r["stats"]["pid"]
                    break
            time.sleep(0.02)
        assert victim_pid, "no replica ever took load"
        t_kill = time.perf_counter()
        _os.kill(victim_pid, _signal.SIGKILL)
        dup_tokens = 0
        for p, h in handles:
            got = h.result(timeout=600)
            assert got == ref[tuple(p)], \
                "stream for %s diverged after the kill" % (p,)
        recovery_s = time.perf_counter() - t_kill
        st = gw.stats()
        assert st["dup_dropped"] == 0, st["dup_dropped"]
        heal_deadline = time.perf_counter() + 300
        while time.perf_counter() < heal_deadline \
                and gw.stats()["live"] < 2:
            time.sleep(0.2)
        respawn_s = time.perf_counter() - t_kill
        rec = {
            "replicas": 2, "in_flight_at_kill": len(prompts),
            "all_streams_complete_after_kill_s": round(recovery_s, 3),
            "respawned_to_full_strength_s": round(respawn_s, 3),
            "failover": st["failover"],
            "duplicated_tokens": dup_tokens + st["dup_dropped"],
            "streams_bit_equal_to_reference": True,
        }
        print("fleet kill drill: %d streams recovered in %.2fs, world "
              "healed in %.2fs, 0 duplicated tokens"
              % (len(prompts), recovery_s, respawn_s))
        return rec
    finally:
        gw.close(drain=False, timeout=60.0)


def _fleet_real_model_record():
    """The honest number: real-model replicas on THIS host. Decode here
    is host-CPU-bound (no device), so replica processes contend for the
    same core and aggregate throughput does NOT scale — recorded as-is
    with the reason, next to the device-paced table that models the TPU
    regime."""
    from mxnet_tpu.fleet import Gateway
    from mxnet_tpu.fleet.replica import build_from_spec
    geo = dict(_DECODE_GEO, seq_len=32)
    spec = {"kind": "transformer", "geo": geo, "seed": 11, "slots": 4,
            "page": 8, "name": "benchreal"}
    new_tokens, n_req = 12, 24
    solo = build_from_spec(dict(spec, name="benchrealsolo"))
    prompts = _decode_prompts(16)
    try:
        done = 0
        t0 = time.perf_counter()
        hs = [solo.submit_generate(prompts[i % len(prompts)],
                                   max_new_tokens=new_tokens)
              for i in range(n_req)]
        for h in hs:
            done += len(h.result(timeout=600))
        solo_tps = done / (time.perf_counter() - t0)
    finally:
        solo.close()
    gw = Gateway(spec=spec, replicas=2, port=None, stats_period=0.2,
                 name="bench_real")
    try:
        assert gw.wait_ready(2, timeout=600.0) == 2
        fleet_tps, _ = _fleet_closed_loop(gw, clients=8, n_req=n_req,
                                          new_tokens=new_tokens)
    finally:
        gw.close(drain=False, timeout=60.0)
    rec = {
        "single_server_tps": round(solo_tps, 1),
        "fleet_2_replica_tps": round(fleet_tps, 1),
        "ratio": round(fleet_tps / solo_tps, 2),
        "note": ("decode on this bench host is CPU-bound (no "
                 "accelerator), so the ratio measures host scheduling "
                 "across 2 replica processes sharing the same cores, "
                 "not device scaling; the device_paced table above "
                 "models the TPU regime where the device decodes and "
                 "the host-side fleet fabric is the measured part"),
    }
    print("fleet real-model (host-CPU-bound): solo %.1f tok/s vs "
          "2-replica %.1f tok/s (%.2fx) — see note"
          % (solo_tps, fleet_tps, rec["ratio"]))
    return rec


def _bench_fleet(quick=False):
    """The ISSUE 20 acceptance table: aggregate tok/s + TTFT p50/95/99
    for 1/2/3 replicas at matched per-replica deployments, the
    kill-one-replica-under-load record (recovery time, zero token
    duplication), and the honest real-model record for this host."""
    from mxnet_tpu import config as _config
    _config.set("MXNET_TPU_FLEET", True)
    _config.set("MXNET_TPU_ELASTIC_BACKOFF", 0.2)
    out = _fleet_scaling(quick=quick)
    out["kill_under_load"] = _fleet_kill_under_load()
    if not quick:
        out["real_model"] = _fleet_real_model_record()
    return out


def run(quick=False, reps=1):
    n_req = 400 if quick else 4000
    clients = 16 if quick else 32
    max_batch = 32
    results = {}
    models = [("mlp", _build_mlp)]
    if not quick:
        models.append(("resnet_stem", _build_resnet_stem))
    for name, build in models:
        # best-of-reps, same policy as trainer_step_bench: this shared
        # host's available CPU swings ~3x run to run, so a single rep
        # measures the box, not the batcher. Sequential and served each
        # keep their own best (both sides at box-best is the fair pair).
        r = None
        best_seq = 0.0
        for _ in range(reps):
            cur = _bench_one(build, n_req, clients, max_batch)
            best_seq = max(best_seq, cur["sequential_rps"])
            if r is None or cur["served_rps"] > r["served_rps"]:
                r = cur
        r["sequential_rps"] = best_seq
        r["speedup"] = round(r["served_rps"] / best_seq, 2)
        r["reps"] = reps
        results[name] = r
        print("%-12s seq %8.1f req/s   served %8.1f req/s   %5.2fx   "
              "p50 %s ms  p99 %s ms  occ %s"
              % (name, r["sequential_rps"], r["served_rps"], r["speedup"],
                 r["p50_ms"], r["p99_ms"], r["occupancy"]))
    results["decode"] = _bench_decode(quick=quick, reps=reps)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast smoke variant (fewer requests, MLP only)")
    ap.add_argument("--reps", type=int, default=1,
                    help="repetitions; best throughput per side is kept")
    ap.add_argument("--json", default=None, help="write results to PATH")
    ap.add_argument("--decode-only", action="store_true",
                    help="run only the generative-decode section")
    ap.add_argument("--decode-json", default=None,
                    help="write the decode section to PATH "
                         "(BENCH_decode.json)")
    ap.add_argument("--fleet", action="store_true",
                    help="run only the multi-replica fleet section")
    ap.add_argument("--fleet-json", default=None,
                    help="write the fleet section to PATH "
                         "(BENCH_fleet.json)")
    args = ap.parse_args()
    if args.fleet:
        results = {"fleet": _bench_fleet(quick=args.quick)}
    elif args.decode_only:
        results = {"decode": _bench_decode(quick=args.quick,
                                           reps=args.reps)}
    else:
        results = run(quick=args.quick, reps=args.reps)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "serving", "results": results}, f,
                      indent=2)
        print("wrote", args.json)
    if args.decode_json:
        payload = dict(results["decode"])
        payload["bench"] = "serve_decode"
        payload["reps"] = args.reps
        with open(args.decode_json, "w") as f:
            json.dump(payload, f, indent=2)
        print("wrote", args.decode_json)
    if args.fleet_json:
        payload = dict(results["fleet"])
        payload["bench"] = "fleet"
        with open(args.fleet_json, "w") as f:
            json.dump(payload, f, indent=2)
        print("wrote", args.fleet_json)
    return results


if __name__ == "__main__":
    main()
