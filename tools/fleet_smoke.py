"""CI ``fleet`` job: multi-replica kill-mid-stream drill + zero-cost
gate (ISSUE 20 satellite).

Two checks, real model replicas (tiny zoo transformer, CPU backend),
every subprocess wait under a hard timeout (the PhaseGuard discipline —
a wedged drill must fail the job, not hang it):

1. **Fleet drill** — a gateway supervises THREE replica processes
   serving bit-identical weights.
   ``MXNET_TPU_FLEET_FAULT_REPLICA=1:replica.die@6:hostkill`` arms rank
   1 (first spawn only) to SIGKILL itself after its 6th emitted token
   frame. Under a concurrent request wave:

   - every stream — the victim's in-flight sequences included — must
     complete BIT-EQUAL to a single-server reference (exact at-most-once
     fail-over: re-prefill from prompt + delivered prefix, no token
     duplicated, none lost, ``fleet_dup_dropped == 0``);
   - survivors are undisturbed (their streams are part of the same
     bit-equality check);
   - the supervisor respawns rank 1, which rejoins and serves real
     traffic in the next wave;
   - the federated ``/metrics`` text parses strictly and carries
     ``replica="0|1|2"`` labeled samples.

2. **Zero-cost gate** — a subprocess that imports ``mxnet_tpu``, runs a
   plain ``GenerativeServer`` request, and asserts the fleet package
   never imported and no ``fleet*`` counter exists in the registry: a
   plain serve process pays NOTHING for the fleet's existence.

Exit code 0 = all gates passed.
"""
import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

GEO = dict(vocab_size=128, num_layers=2, d_model=32, n_heads=2, seq_len=32)
SPEC = {"kind": "transformer", "geo": GEO, "seed": 11, "slots": 2,
        "page": 8, "name": "fleetrep"}
PROMPTS = [[3, 1, 4], [1, 5, 9], [2, 6], [5, 3, 5], [8, 9, 7, 9], [3, 2]]
NEW_TOKENS = 12


def _reference_streams():
    """Single-server ground truth: same spec, same seeded init — what
    every fleet stream must equal bit-for-bit."""
    from mxnet_tpu.fleet.replica import build_from_spec
    srv = build_from_spec(dict(SPEC, name="fleetref"))
    try:
        return {tuple(p): srv.submit_generate(
                    p, max_new_tokens=NEW_TOKENS).result(timeout=600)
                for p in PROMPTS}
    finally:
        srv.close()


def _wave(gw, ref):
    handles = [(p, gw.submit_generate(p, max_new_tokens=NEW_TOKENS))
               for p in PROMPTS]
    for p, h in handles:
        got = h.result(timeout=600)
        assert got == ref[tuple(p)], (
            "stream for prompt %s diverged:\n got %s\nwant %s"
            % (p, got, ref[tuple(p)]))


def check_fleet_drill():
    from mxnet_tpu import config as _config
    from mxnet_tpu.obs.prometheus import parse_prometheus

    # rank 1, FIRST spawn only, dies after its 6th emitted token frame;
    # hostkill (with the coordinated-parent marker stripped by the
    # supervisor) SIGKILLs exactly the replica process — no cleanup,
    # the honest analog of a host loss
    os.environ["MXNET_TPU_FLEET_FAULT_REPLICA"] = "1:replica.die@6:hostkill"
    _config.set("MXNET_TPU_FLEET", True)
    _config.set("MXNET_TPU_ELASTIC_BACKOFF", 0.2)

    ref = _reference_streams()
    print("reference streams computed (%d prompts)" % len(ref))

    from mxnet_tpu.fleet import Gateway
    gw = Gateway(spec=SPEC, replicas=3, port=None, stats_period=0.2,
                 name="drill_fleet")
    try:
        t0 = time.monotonic()
        live = gw.wait_ready(3, timeout=600.0)
        assert live == 3, "only %d/3 replicas came up" % live
        print("3 replicas live in %.1fs" % (time.monotonic() - t0))

        # ---- wave 1: rank 1 dies mid-stream under this load
        t0 = time.monotonic()
        _wave(gw, ref)
        st = gw.stats()
        assert st["failover"] >= 1, \
            "the armed kill never triggered a fail-over: %s" % st
        assert st["replica_dead"] >= 1, st
        assert st["dup_dropped"] == 0, \
            "at-most-once violated: %d duplicate frames" % st["dup_dropped"]
        print("PASS kill drill: all %d streams bit-equal through the "
              "rank-1 death (failover=%d, dup_dropped=0) in %.1fs"
              % (len(PROMPTS), st["failover"], time.monotonic() - t0))

        # ---- respawn: rank 1 rejoins
        t0 = time.monotonic()
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            st = gw.stats()
            r1 = st["replicas"][1]
            if st["live"] == 3 and r1["state"] == "live" \
                    and r1["stats"].get("pid"):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("rank 1 never rejoined: %s" % st)
        print("rank 1 respawned and live in %.1fs (restarts=%d)"
              % (time.monotonic() - t0, st["replicas"][1]["restarts"]))
        # ---- wave 2: the healed world serves, rank 1 takes traffic
        _wave(gw, ref)
        r1_tokens = gw.stats()["replicas"][1]["stats"].get("tokens", 0)
        deadline = time.monotonic() + 30.0
        while r1_tokens == 0 and time.monotonic() < deadline:
            time.sleep(0.2)     # stats lag one heartbeat
            r1_tokens = gw.stats()["replicas"][1]["stats"].get("tokens", 0)
        assert r1_tokens > 0, "respawned replica never took traffic"
        print("PASS healed wave: all streams bit-equal, respawned "
              "replica decoded %d tokens" % r1_tokens)

        # ---- federated metrics
        text = gw.metrics_text()
        samples = parse_prometheus(text)    # strict parse
        replicas = {dict(lbls).get("replica") for _n, lbls in samples}
        assert {"0", "1", "2"} <= replicas, \
            "federation missing replica labels: %s" % replicas
        print("PASS federation: /metrics carries replica=0/1/2 samples "
              "(%d total)" % len(samples))
    finally:
        gw.close(drain=False, timeout=60.0)
        os.environ.pop("MXNET_TPU_FLEET_FAULT_REPLICA", None)


_GATE_CHILD = """
import sys
sys.path.insert(0, %(root)r)
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import mxnet_tpu as mx
from mxnet_tpu.models import transformer
net = transformer.get_symbol(**%(geo)r)
mod = mx.mod.Module(net, context=mx.cpu())
s = %(geo)r["seq_len"]
mod.bind(data_shapes=[("data", (1, s))],
         label_shapes=[("softmax_label", (1, s))])
mod.init_params(mx.init.Uniform(0.05))
srv = mx.serve.GenerativeServer(mod, n_heads=%(geo)r["n_heads"],
                                max_sequences=2, page=8, name="plain")
srv.submit_generate([3, 1, 4], max_new_tokens=4).result(timeout=300)
srv.close()
assert "mxnet_tpu.fleet" not in sys.modules, "plain serve imported fleet"
from mxnet_tpu import profiler
bad = [k for k in profiler.counters() if k.startswith("fleet")]
assert not bad, "plain serve grew fleet counters: %%s" %% bad
print("GATE-OK")
"""


def check_zero_cost_gate():
    env = dict(os.environ)
    env.pop("MXNET_TPU_FLEET", None)
    env.pop("MXNET_TPU_FLEET_FAULT_REPLICA", None)
    out = subprocess.run(
        [sys.executable, "-c", _GATE_CHILD % {"root": _ROOT, "geo": GEO}],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "GATE-OK" in out.stdout, out.stdout + out.stderr
    print("PASS zero-cost gate: plain serve never imports the fleet and "
          "grows no fleet counters")


def main():
    check_fleet_drill()
    check_zero_cost_gate()
    print("fleet smoke: ALL PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
