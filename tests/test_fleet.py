"""mxnet_tpu.fleet — gateway routing, replica supervision, fail-over
(ISSUE 20 tentpole).

The contract under test: the wire round-trips the serve API (streaming
tokens + the exception hierarchy) over real sockets; the gateway routes
least-loaded and keeps sequences sticky; a replica death mid-stream
fails over with an EXACT at-most-once continuation (the scripted
decoder's pure-autoregressive token function makes bit-equality
checkable without a model); shed/deadline/closed propagate as the same
exception classes a local ``GenerativeServer`` raises; the
``gateway.route`` fault site kills one request legibly; ``/metrics``
federates replica-labeled expositions into one parseable text; and the
package stays zero-cost: a plain ``import mxnet_tpu`` never loads it.

In-process tests front :class:`ScriptedDecodeServer` instances with
real ``ServeWire`` sockets and run the gateway in ``addresses=`` mode
(no subprocesses — the supervised-spawn path is exercised by
``tools/fleet_smoke.py`` with real model replicas). Replica "death"
here is wire-stop + drain=False close, which exercises both fail-over
triggers: transport death AND the clean-early-END a gracefully
shutting-down replica produces.
"""
import subprocess
import sys
import threading
import time

import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as _config
from mxnet_tpu import faults
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import DeadlineExceeded, QueueFull, ServerClosed
from mxnet_tpu.serve.server import ServeError


@pytest.fixture(autouse=True)
def _fleet_knob():
    snap = _config.snapshot_overrides(["MXNET_TPU_FLEET"])
    _config.set("MXNET_TPU_FLEET", True)
    yield
    _config.restore_overrides(snap)


def _scripted_pair(n=2, step_s=0.005, **kw):
    from mxnet_tpu.fleet import ScriptedDecodeServer, ServeWire
    srvs, wires = [], []
    for r in range(n):
        s = ScriptedDecodeServer(step_s=step_s,
                                 name="t%d_%s" % (r, _uniq()), **kw)
        wires.append(ServeWire(s, rank=r))
        srvs.append(s)
    return srvs, wires


_SEQ = [0]


def _uniq():
    _SEQ[0] += 1
    return "u%d" % _SEQ[0]


def _ref_stream(prompt, n):
    from mxnet_tpu.fleet import scripted_token
    seq, out = list(prompt), []
    for _ in range(n):
        t = scripted_token(seq)
        out.append(t)
        seq.append(t)
    return out


def _teardown(gw, srvs, wires):
    gw.close(drain=False, timeout=10.0)
    for w in wires:
        w.stop()
    for s in srvs:
        try:
            s.close(drain=False, timeout=2.0)
        except Exception:                                   # noqa: BLE001
            pass


# ---------------------------------------------------------------- wire

def test_wire_streams_and_roundtrips_stats():
    from mxnet_tpu.fleet import FleetClient
    srvs, wires = _scripted_pair(n=1)
    try:
        cli = FleetClient(wires[0].address)
        assert cli.ping()
        toks = cli.generate([1, 2, 3], max_new_tokens=8,
                            result_timeout=30.0)
        assert toks == _ref_stream([1, 2, 3], 8)
        snap = cli.stats()
        assert snap["tokens"] >= 8
        assert snap["kv"]["max_slots"] == 4
        text = cli.metrics_text()
        assert 'replica="0"' in text
    finally:
        for w in wires:
            w.stop()
        for s in srvs:
            s.close(drain=False, timeout=2.0)


def test_wire_rehydrates_serve_exceptions():
    from mxnet_tpu.fleet import FleetClient, ScriptedDecodeServer, ServeWire
    s = ScriptedDecodeServer(slots=1, step_s=0.05, queue_bound=1,
                             name="shed_" + _uniq())
    w = ServeWire(s, rank=0)
    try:
        cli = FleetClient(w.address)
        # the client submit is async (a daemon thread drives the wire),
        # so sequence the fill deterministically: [1] resident FIRST,
        # then [2] into the one queue slot — submitting both at once
        # races [2] against [1]'s admission and the shed lands on the
        # wrong request

        def _wait(pred, what):
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if pred(s.stats()):
                    return
                time.sleep(0.01)
            pytest.fail("server never reached " + what)

        h1 = cli.submit_generate([1], max_new_tokens=50)
        _wait(lambda st: st["active_sequences"] >= 1, "slot-full")
        h2 = cli.submit_generate([2], max_new_tokens=50)
        _wait(lambda st: st["waiting"] >= 1, "queue-full")
        with pytest.raises(QueueFull):
            cli.generate([3], max_new_tokens=4, result_timeout=10.0)
        h1.cancel()
        h2.cancel()
    finally:
        w.stop()
        s.close(drain=False, timeout=2.0)


def test_wire_end_reason_distinguishes_done_from_released():
    from mxnet_tpu.fleet import wire as fwire
    srvs, wires = _scripted_pair(n=1, step_s=0.005)
    s, w = srvs[0], wires[0]
    try:
        # finished on the server's own terms -> reason "done"
        got = []
        end = fwire.stream_generate(
            w.address,
            {"prompt": [1], "prefix": [], "start": 0,
             "max_new_tokens": 4, "eos_id": None, "temperature": 0.0,
             "seed": None, "timeout": None},
            lambda i, t: got.append(t))
        assert end["n"] == 4 and end["reason"] == "done"
        assert got == _ref_stream([1], 4)
        # a draining shutdown cancels the sequence -> reason "released"
        box = {}

        def run():
            try:
                box["end"] = fwire.stream_generate(
                    w.address,
                    {"prompt": [2], "prefix": [], "start": 0,
                     "max_new_tokens": 10000, "eos_id": None,
                     "temperature": 0.0, "seed": None, "timeout": None},
                    lambda i, t: None)
            except BaseException as exc:                    # noqa: BLE001
                box["exc"] = exc

        t = threading.Thread(target=run, daemon=True)
        t.start()
        time.sleep(0.1)             # a few tokens in
        s.close(drain=False, timeout=5.0)
        t.join(10.0)
        assert box.get("end", {}).get("reason") == "released"
    finally:
        w.stop()
        s.close(drain=False, timeout=2.0)


def test_probe_adjudicates_alive_dead_ambiguous():
    import socket
    from mxnet_tpu.fleet import probe
    from mxnet_tpu.parallel.dist import free_port
    srvs, wires = _scripted_pair(n=1)
    try:
        assert probe(wires[0].address, timeout=2.0) == "alive"
    finally:
        wires[0].stop()
        srvs[0].close(drain=False, timeout=2.0)
    # connection refused = the probe-confirmed death signal
    assert probe(("127.0.0.1", free_port()), timeout=1.0) == "dead"
    # a peer answering garbage is never grounds for a kill verdict
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def answer():
        conn, _ = srv.accept()
        conn.recv(64)
        conn.sendall(b"WAT\n")
        conn.close()

    t = threading.Thread(target=answer, daemon=True)
    t.start()
    try:
        assert probe(srv.getsockname(), timeout=2.0) == "ambiguous"
    finally:
        srv.close()


# ------------------------------------------------------------- gateway

def test_gateway_requires_opt_in_knob():
    from mxnet_tpu.fleet import Gateway
    _config.set("MXNET_TPU_FLEET", False)
    with pytest.raises(MXNetError):
        Gateway(addresses=[("127.0.0.1", 1)], port=None)


def test_gateway_streams_through_client_wire():
    from mxnet_tpu.fleet import FleetClient, Gateway
    srvs, wires = _scripted_pair(n=2)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="gwt_" + _uniq(), stats_period=0.1)
    try:
        assert gw.wait_ready(timeout=10.0) == 2
        cli = FleetClient(("127.0.0.1", gw.port))
        toks = cli.generate([4, 5], max_new_tokens=10,
                            result_timeout=30.0)
        assert toks == _ref_stream([4, 5], 10)
        snap = cli.stats()          # gateway stats through the same wire
        assert snap["live"] == 2 and snap["tokens"] >= 10
    finally:
        _teardown(gw, srvs, wires)


def test_routing_spreads_load_least_loaded():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=2, slots=2, step_s=0.01)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="lb_" + _uniq(), stats_period=0.05)
    try:
        assert gw.wait_ready(timeout=10.0) == 2
        handles = [gw.submit_generate([i + 1], max_new_tokens=20)
                   for i in range(4)]
        for h in handles:
            assert len(h.result(timeout=60.0)) == 20
        # with 4 concurrent 2-slot replica loads, least-loaded MUST
        # have spread: both replicas decoded something
        per = [s.stats()["tokens"] for s in srvs]
        assert all(t > 0 for t in per), per
    finally:
        _teardown(gw, srvs, wires)


def test_sticky_one_replica_per_stream():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=2)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="stick_" + _uniq(), stats_period=0.05)
    try:
        assert gw.wait_ready(timeout=10.0) == 2
        h = gw.submit_generate([7], max_new_tokens=30)
        assert len(h.result(timeout=60.0)) == 30
        # no fail-over happened, so exactly ONE replica carried the
        # whole stream (stickiness is by construction; this pins it)
        per = [s.stats()["tokens"] for s in srvs]
        assert sorted(per) == [0, 30], per
    finally:
        _teardown(gw, srvs, wires)


def test_gateway_sheds_at_admission_bound():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=1, step_s=0.05)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="bound_" + _uniq(), queue_bound=1,
                 stats_period=0.1)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        h = gw.submit_generate([1], max_new_tokens=40)
        with pytest.raises(QueueFull):
            gw.submit_generate([2], max_new_tokens=4)
        h.cancel()
    finally:
        _teardown(gw, srvs, wires)


def test_ttft_deadline_propagates():
    from mxnet_tpu.fleet import Gateway
    # one slot, long resident sequence: the queued request's TTFT
    # deadline expires inside the REPLICA queue and comes back as
    # DeadlineExceeded through the wire
    srvs, wires = _scripted_pair(n=1, slots=1, step_s=0.05)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="dl_" + _uniq(), stats_period=0.1)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        h1 = gw.submit_generate([1], max_new_tokens=60)
        time.sleep(0.1)
        h2 = gw.submit_generate([2], max_new_tokens=4, timeout=0.2)
        with pytest.raises(DeadlineExceeded):
            h2.result(timeout=30.0)
        h1.cancel()
    finally:
        _teardown(gw, srvs, wires)


def test_close_rejects_new_submits():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=1)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="cl_" + _uniq(), stats_period=0.1)
    gw.wait_ready(timeout=10.0)
    gw.close(drain=True, timeout=10.0)
    with pytest.raises(ServerClosed):
        gw.submit_generate([1], max_new_tokens=4)
    for w in wires:
        w.stop()
    for s in srvs:
        s.close(drain=False, timeout=2.0)


# ------------------------------------------------------------ fail-over

def test_failover_midstream_exact_continuation():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=2, step_s=0.01)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="fo_" + _uniq(), stats_period=0.05)
    try:
        assert gw.wait_ready(timeout=10.0) == 2
        witness = gw.submit_generate([9], max_new_tokens=40)
        time.sleep(0.08)            # a few tokens in
        st = gw.stats()
        victim = next(r["rank"] for r in st["replicas"]
                      if r["assigned"] > 0)
        survivor = 1 - victim
        # a co-resident sequence on the SURVIVOR must ride through the
        # victim's death untouched
        bystander = gw.submit_generate([3, 3], max_new_tokens=40)
        time.sleep(0.05)
        wires[victim].stop()
        srvs[victim].close(drain=False, timeout=2.0)
        out = witness.result(timeout=60.0)
        assert out == _ref_stream([9], 40)      # exact, no dup, no gap
        assert bystander.result(timeout=60.0) == _ref_stream([3, 3], 40)
        st = gw.stats()
        assert st["failover"] >= 1
        assert st["dup_dropped"] == 0
        # every token the survivor decoded for the witness re-prefilled
        # from prompt + delivered prefix — delivered exactly once
        assert st["replicas"][survivor]["state"] == "live"
    finally:
        _teardown(gw, srvs, wires)


def test_failover_redispatch_drops_ttft_and_derives_seed(monkeypatch):
    # the TTFT deadline constrains only the FIRST token: a fail-over
    # re-dispatch after delivery must not carry the (long-expired)
    # deadline into the survivor's admission, and a seeded request's
    # continuation seed derives from the fail-over point instead of
    # replaying the original seed's draws at the wrong positions
    from mxnet_tpu.fleet import Gateway
    from mxnet_tpu.fleet import wire as fwire
    srvs, wires = _scripted_pair(n=1, step_s=0.005)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="rdp_" + _uniq(), stats_period=0.1)
    payloads = []
    real = fwire.stream_generate

    def fake(addr, payload, on_frame, **kw):
        payloads.append(dict(payload))
        if len(payloads) == 1:
            for i, t in enumerate(_ref_stream([7], 2)):
                on_frame(i, t)      # two tokens out, then die
            raise ConnectionResetError("mid-stream death")
        return real(addr, payload, on_frame, **kw)

    monkeypatch.setattr(fwire, "stream_generate", fake)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        h = gw.submit_generate([7], max_new_tokens=8, timeout=5.0,
                               seed=123)
        assert h.result(timeout=30.0) == _ref_stream([7], 8)
        assert len(payloads) == 2
        assert payloads[0]["timeout"] is not None
        assert payloads[0]["seed"] == 123
        assert payloads[1]["start"] == 2
        assert payloads[1]["prefix"] == _ref_stream([7], 2)
        assert payloads[1]["timeout"] is None
        assert payloads[1]["seed"] not in (None, 123)
    finally:
        _teardown(gw, srvs, wires)


def test_short_done_end_is_a_complete_result(monkeypatch):
    # a replica's KV-capacity truncation ENDs the stream cleanly SHORT
    # with reason "done": the gateway must finish the request as a bare
    # server would — not burn fail-over budget re-prefilling a prompt
    # that already outgrew max_seq
    from mxnet_tpu.fleet import Gateway
    from mxnet_tpu.fleet import wire as fwire

    def fake(addr, payload, on_frame, **kw):
        for i, t in enumerate(_ref_stream([5], 3)):
            on_frame(i, t)
        return {"n": 3, "reason": "done"}

    monkeypatch.setattr(fwire, "stream_generate", fake)
    srvs, wires = _scripted_pair(n=1)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="trunc_" + _uniq(), stats_period=0.1)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        h = gw.submit_generate([5], max_new_tokens=64)
        assert h.result(timeout=30.0) == _ref_stream([5], 3)
        assert gw.stats()["failover"] == 0
    finally:
        _teardown(gw, srvs, wires)


def test_all_replicas_dead_fails_legibly():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=1, step_s=0.01)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="dead_" + _uniq(), stats_period=0.05)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        h = gw.submit_generate([5], max_new_tokens=60)
        time.sleep(0.05)
        wires[0].stop()
        srvs[0].close(drain=False, timeout=2.0)
        with pytest.raises(ServeError):
            h.result(timeout=120.0)
    finally:
        _teardown(gw, srvs, wires)


def test_gateway_route_fault_kills_one_request():
    from mxnet_tpu.fleet import Gateway
    srvs, wires = _scripted_pair(n=1)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="fr_" + _uniq(), stats_period=0.1)
    try:
        assert gw.wait_ready(timeout=10.0) == 1
        faults.install("gateway.route@1:raise")
        try:
            h1 = gw.submit_generate([1], max_new_tokens=4)
            with pytest.raises(ServeError):
                h1.result(timeout=30.0)
            # the site fired once; the next request routes normally
            h2 = gw.submit_generate([2], max_new_tokens=4)
            assert len(h2.result(timeout=30.0)) == 4
        finally:
            faults.clear()
    finally:
        _teardown(gw, srvs, wires)


# ------------------------------------------------------------- metrics

def test_metrics_federation_parses_with_replica_labels():
    from mxnet_tpu.fleet import Gateway
    from mxnet_tpu.obs.prometheus import parse_prometheus
    srvs, wires = _scripted_pair(n=2)
    gw = Gateway(addresses=[w.address for w in wires],
                 name="met_" + _uniq(), stats_period=0.05)
    try:
        assert gw.wait_ready(timeout=10.0) == 2
        gw.submit_generate([1], max_new_tokens=4).result(timeout=30.0)
        text = gw.metrics_text()
        samples = parse_prometheus(text)    # strict: raises on bad text
        assert samples, "federated exposition empty"
        replicas = {dict(lbls).get("replica")
                    for (_name, lbls) in samples}
        assert "0" in replicas and "1" in replicas
    finally:
        _teardown(gw, srvs, wires)


def test_merge_prometheus_dedupes_metadata():
    from mxnet_tpu.fleet import merge_prometheus
    a = ("# HELP m a counter\n# TYPE m counter\n"
         'm{replica="0"} 1\n')
    b = ("# HELP m a counter\n# TYPE m counter\n"
         'm{replica="1"} 2\n')
    merged = merge_prometheus([a, b])
    assert merged.count("# HELP m") == 1
    assert merged.count("# TYPE m") == 1
    assert 'm{replica="0"} 1' in merged and 'm{replica="1"} 2' in merged


# ------------------------------------------------------------ zero cost

def test_zero_cost_import_gate():
    """A plain import must not load the fleet (lazy PEP 562 hook)."""
    code = ("import sys; import mxnet_tpu; "
            "assert 'mxnet_tpu.fleet' not in sys.modules, 'fleet loaded'; "
            "import mxnet_tpu.serve; "
            "assert 'mxnet_tpu.fleet' not in sys.modules, 'serve pulls fleet'; "
            "print('OK')")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, env=_child_env())
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def _child_env():
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ""
    return env


def test_client_accepts_host_port_string():
    """A 'host:port' string must parse, not be indexed char-by-char
    into the silently-wrong address ('1', 2)."""
    from mxnet_tpu.fleet import FleetClient
    assert FleetClient("127.0.0.1:4242").address == ("127.0.0.1", 4242)
    assert FleetClient(("10.0.0.1", 7)).address == ("10.0.0.1", 7)
    with pytest.raises(ValueError):
        FleetClient("localhost")            # no port
    with pytest.raises(ValueError):
        FleetClient("host:notaport")
