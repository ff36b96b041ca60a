"""CI ``serve-decode`` job: continuous-batching drill + budget/gate
checks (ISSUE 16 satellite).

Five checks, all on the tiny zoo transformer, CPU backend:

1. **Continuous-batching drill** — requests join a RUNNING decode batch
   mid-flight, stream per-token, and evict on finish; after the warm
   wave the compile counter must move ZERO and the executable set must
   stay <= |prompt buckets| + |decode buckets|.
2. **Fault matrix** — ``serve.decode@1`` kills exactly ONE sequence's
   future (legible error naming the site + slot) while co-residents
   finish; ``serve.evict@1`` fails the handle but still frees the pages
   (slots_in_use == 0 after).
3. **hbm-budget rejection** — ``MXNET_TPU_ANALYZE=strict`` with a 1K
   budget must reject the cache reservation at server START, naming it.
4. **Zero-cost gate** — a subprocess importing ``mxnet_tpu.serve`` must
   NOT have ``serve.decode`` / ``serve.kv_cache`` in sys.modules.
5. **int8 capacity** — ``max_slots_for`` doubles under int8 at a fixed
   budget.

Exit code 0 = all gates passed.
"""
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

GEO = dict(vocab_size=128, num_layers=2, d_model=32, n_heads=2, seq_len=32)


def _module():
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(**GEO)
    mod = mx.mod.Module(net, context=mx.cpu())
    s = GEO["seq_len"]
    mod.bind(data_shapes=[("data", (1, s))],
             label_shapes=[("softmax_label", (1, s))])
    mx.random.seed(11)
    mod.init_params(mx.init.Uniform(0.05))
    return mod


def check_continuous_batching():
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    mod = _module()
    srv = mx.serve.GenerativeServer(mod, n_heads=GEO["n_heads"],
                                    max_sequences=4, page=8, int8=False,
                                    name="drill")
    try:
        # warm wave: one request per prompt bucket the drill traffic
        # uses, decoding deep enough to touch every decode bucket it
        # reaches (short prompts rung up through bucket 8 and 16; the
        # long one crosses into 32)
        srv.submit_generate([1], max_new_tokens=10).result(timeout=300)
        srv.submit_generate(list(range(1, 12)),
                            max_new_tokens=10).result(timeout=300)
        warm = profiler.get_counter("drill_compile")
        bound = srv.engine.executable_bound()
        assert warm <= bound, (warm, bound)

        # the drill proper: a long-runner, joins mid-flight, streaming
        long_run = srv.submit_generate([1, 2, 3], max_new_tokens=10)
        while not long_run.tokens_so_far():
            time.sleep(0.005)
        streamed = []
        joiner = srv.submit_generate([4, 5], max_new_tokens=6,
                                     on_token=streamed.append)
        late = srv.submit_generate([6], max_new_tokens=4)
        assert len(list(joiner)) == 6          # iterator streaming
        assert streamed == joiner.result(timeout=60)   # callback parity
        assert len(long_run.result(timeout=300)) == 10
        assert len(late.result(timeout=300)) == 4
        assert profiler.get_counter("drill_compile") == warm, \
            "steady-state decode recompiled"
        st = srv.stats()
        assert st["compiles"] <= st["executable_bound"], st
        assert st["kv"]["slots_in_use"] == 0, "pages leaked after evict"
        print("PASS continuous-batching: %d compiles <= bound %d, "
              "0 steady-state recompiles, streams ok"
              % (st["compiles"], st["executable_bound"]))
        return srv, mod
    except BaseException:
        srv.close()
        raise


def check_faults(srv):
    from mxnet_tpu import faults
    from mxnet_tpu.serve import ServeError
    # co-residency setup: once b streams its FIRST token it is resident,
    # and a (still decoding, lower slot) is the deterministic victim.
    # Decode steps are ~1ms here, so a's whole lifetime is a few dozen
    # ms — under GIL scheduling the observer thread can miss the whole
    # window, hence the retry loop.
    for _ in range(10):
        a = srv.submit_generate([1, 2, 3], max_new_tokens=40)
        while not a.tokens_so_far():
            time.sleep(0.001)
        b = srv.submit_generate([4, 5], max_new_tokens=8)
        while not b.tokens_so_far():
            time.sleep(0.0005)
        if not a.done():
            break
        b.result(timeout=300)          # drain the attempt and retry
    else:
        raise AssertionError("never caught a and b co-resident")
    faults.install("serve.decode@1")
    try:
        # the contract: EXACTLY ONE sequence's future dies, with a
        # legible error naming the site; the co-resident completes its
        # full generation (slot reuse is LIFO, so which handle holds
        # the victim slot varies — the batch surviving is the point)
        outcomes = []
        for h, want in ((a, (29, 40)), (b, (8,))):
            try:
                outcomes.append(("ok", h, len(h.result(timeout=300)),
                                 want))
            except ServeError as exc:
                assert "serve.decode" in str(exc), exc
                outcomes.append(("killed", h, None, want))
    finally:
        faults.clear()
    killed = [o for o in outcomes if o[0] == "killed"]
    assert len(killed) == 1, "decode fault killed %d of 2 sequences" \
        % len(killed)
    for kind, _h, n, want in outcomes:
        if kind == "ok":
            assert n in want, "co-resident sequence truncated: %s" % n

    faults.install("serve.evict@1")
    try:
        h = srv.submit_generate([7], max_new_tokens=2)
        try:
            h.result(timeout=300)
            raise AssertionError("injected evict fault did not surface")
        except ServeError as exc:
            assert "pages were still freed" in str(exc), exc
    finally:
        faults.clear()
    st = srv.stats()
    assert st["kv"]["slots_in_use"] == 0, "evict fault leaked pages"
    srv.close()
    print("PASS faults: decode fault killed one stream, evict fault "
          "freed pages")


_BUDGET_CHILD = """
import os, sys
sys.path.insert(0, %(root)r)
os.environ["JAX_PLATFORMS"] = "cpu"
import mxnet_tpu as mx
from mxnet_tpu.models import transformer
net = transformer.get_symbol(**%(geo)r)
mod = mx.mod.Module(net, context=mx.cpu())
s = %(geo)r["seq_len"]
mod.bind(data_shapes=[("data", (1, s))],
         label_shapes=[("softmax_label", (1, s))])
mod.init_params(mx.init.Uniform(0.05))
# strict budget goes on AFTER bind: the drill targets the SERVER-start
# reservation audit, not the bind-time program pass
os.environ["MXNET_TPU_ANALYZE"] = "strict"
os.environ["MXNET_TPU_ANALYZE_HBM_BUDGET"] = "1K"
mx.config.reset("MXNET_TPU_ANALYZE")
mx.config.reset("MXNET_TPU_ANALYZE_HBM_BUDGET")
try:
    mx.serve.GenerativeServer(mod, n_heads=%(geo)r["n_heads"],
                              max_sequences=8, page=8, name="overbudget")
except mx.base.MXNetError as exc:
    msg = str(exc)
    assert "hbm-budget" in msg, msg
    assert "overbudget_kv_cache" in msg, msg  # the reservation is NAMED
    print("BUDGET-REJECTED")
else:
    raise AssertionError("1K budget admitted the KV reservation")
"""


def check_budget_rejection():
    out = subprocess.run(
        [sys.executable, "-c",
         _BUDGET_CHILD % {"root": _ROOT, "geo": GEO}],
        capture_output=True, text=True, timeout=600, env=dict(os.environ))
    assert "BUDGET-REJECTED" in out.stdout, out.stdout + out.stderr
    print("PASS hbm-budget: strict 1K budget rejected the reservation "
          "naming it")


_GATE_CHILD = """
import sys
sys.path.insert(0, %(root)r)
import mxnet_tpu
import mxnet_tpu.serve
bad = [m for m in sys.modules
       if m in ("mxnet_tpu.serve.decode", "mxnet_tpu.serve.kv_cache")]
assert not bad, bad
print("GATE-OK")
"""


def check_zero_cost_gate():
    out = subprocess.run(
        [sys.executable, "-c", _GATE_CHILD % {"root": _ROOT}],
        capture_output=True, text=True, timeout=600, env=dict(os.environ))
    assert "GATE-OK" in out.stdout, out.stdout + out.stderr
    print("PASS zero-cost gate: decode path unimported when unused")


def check_int8_capacity():
    from mxnet_tpu.serve.kv_cache import dense_planes, max_slots_for
    geo = dict(num_layers=4, n_heads=8, d_head=64, max_seq=2048, page=16)
    budget = 8 * 1024 ** 3
    f32 = max_slots_for(budget, dense_planes(int8=False, **geo), 2048)
    i8 = max_slots_for(budget, dense_planes(int8=True, **geo), 2048)
    assert i8 >= 2 * f32, (f32, i8)
    print("PASS int8 capacity: %d -> %d resident sequences under the "
          "same budget" % (f32, i8))


def main():
    srv, _ = check_continuous_batching()
    check_faults(srv)
    check_budget_rejection()
    check_zero_cost_gate()
    check_int8_capacity()
    print("serve-decode smoke: ALL PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
