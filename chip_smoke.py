"""Quickest proof that the system still starts on the chip.

One process drives the repo's main path once on the TPU it finds, through
the entry points a user calls, at the full width of the one full-width
model the repo has — the 0.67B dense decoder LM of ``bench.py`` (L12 D2048
H16 T1024 V32000 B8, flash attention, bf16 compute) with random weights
from a seed — and checks what comes out by the repo's own means:

* device — the default backend is a TPU the peaks table knows;
* train  — ``Module.fit`` over an ``NDArrayIter``, two epochs of four
  identical batches: fused step taken, Mosaic kernels in the lowered
  step, parameters on the TPU, loss finite and falling, epoch two
  compiles nothing;
* serve  — ``GenerativeServer`` on the weights just trained: four prompts
  of about 5/40/300/900 tokens, two in flight together, one repeated;
  first tokens agree with ``Module.forward``;
* gluon  — a small ``HybridBlock`` + ``Trainer.step``, three donated
  steps;
* four chips (only when JAX sees four) — the same LM and ``fit`` data
  parallel over four contexts; first-step loss matches one chip.

It never sets ``JAX_PLATFORMS`` and starts no other process (the package
may compile its native data library with g++ on first import of
``mxnet_tpu.native``; whether that worked is reported). Any failed check or
exception in any phase — one surfaced through a request's handle
included — ends the run non-zero. With no accelerator it exits non-zero
and prints no result. Otherwise it ends with two lines of standard
output, one JSON object each: the report — versions, the compile-cache
directory, seconds per phase split into trace, compile and run, and step
time / tok/s as information (not a benchmark) — and, last, the verdict
and nothing else: ``{"ok": true, "device": {"platform": "tpu", "kind":
"...", "count": 1}}`` with the device as JAX reports it.
"""
import gc
import json
import os
import re
import sys
import time
import traceback

import numpy as np

SEED = 0
# the dense LM bench.py builds (section_transformer)
LAYERS, D_MODEL, HEADS, SEQ, VOCAB, BATCH = 12, 2048, 16, 1024, 32000, 8
BATCHES_PER_EPOCH, EPOCHS = 4, 2
# SGD on a batch-mean loss that Module rescales by 1/BATCH once more:
# large enough that eight steps on one repeated batch visibly memorize it
LEARNING_RATE = 2.0
PROMPT_LENS = (5, 40, 300, 900)
NEW_TOKENS = 16
# GenerativeServer (f32 weights) against Module.forward (bf16 compute) on
# the same weights. The whole first-token distribution must agree: the
# distance between the two log-probability rows, relative to the row's own
# spread over the vocabulary, stays under LOGPROB_RTOL (bf16 rounding gives
# ~1e-2; a wrong position, weight or layer gives ~1). Then the token: at
# random init the top two of 32000 logits are ~0.08 nats apart, so rounding
# may swap them, and the server's token may lie up to FIRST_TOKEN_NATS below
# the module's best; the rows agreeing is what keeps a real divergence of
# that size from passing.
LOGPROB_RTOL = 0.05
FIRST_TOKEN_NATS = 0.1
# first-step loss, four chips against one, same rows and initial weights:
# two bf16 programs that reduce in a different order
LOSS_RTOL = 2e-3

_START = time.perf_counter()


def _log(msg):
    print("chip_smoke[%6.1fs] %s" % (time.perf_counter() - _START, msg),
          file=sys.stderr, flush=True)


def check(cond, what):
    """A check that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


class Phases:
    """Wall seconds per phase, split by the always-on ``mx.obs`` compile
    accounting into ``trace`` (trace + lower: host Python, which no cache
    saves), ``compile`` (backend compile, or the read from the persistent
    cache that replaces it) and ``run`` (the rest)."""

    def __init__(self, counters):
        self._counters = counters
        self.seconds = {}

    def run(self, name, fn, *args):
        _log("phase %s" % name)
        c0, t0 = self._counters(), time.perf_counter()
        try:
            return fn(*args)
        finally:
            wall = time.perf_counter() - t0
            c1 = self._counters()

            def delta(counter):
                return c1.get(counter, 0) - c0.get(counter, 0)

            bind_s = delta("obs_bind_ms_total") / 1e3
            compile_s = delta("obs_compile_ms_total") / 1e3
            self.seconds[name] = {
                "wall": round(wall, 2),
                "trace": round(bind_s - compile_s, 2),
                "compile": round(compile_s, 2),
                "run": round(max(wall - bind_s, 0.0), 2),
                "executables": delta("obs_compile_count")}


# ------------------------------------------------------------------ device

def phase_device():
    """Fails within seconds without an accelerator — before the package
    (and its slower imports) is touched."""
    import jax
    backend = jax.default_backend()
    dev = jax.devices()[0]
    check(backend == "tpu" and dev.platform == "tpu",
          "no TPU: jax.default_backend() is %r and jax.devices()[0] is "
          "%r (platform %r)" % (backend, dev, dev.platform))
    from mxnet_tpu.obs.mfu import table_peak_flops
    check(table_peak_flops(dev.device_kind) is not None,
          "device_kind %r is not in the peaks table (mxnet_tpu/obs/mfu.py)"
          % dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ------------------------------------------------------------------- train

def _lm_symbol():
    from mxnet_tpu.models import transformer
    return transformer.get_symbol(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=D_MODEL, n_heads=HEADS,
        seq_len=SEQ, attention="flash")


def _rows(n, seed):
    """``n`` rows of token ids and their next-token labels."""
    rng = np.random.RandomState(seed)
    x = rng.randint(0, VOCAB, (n, SEQ))
    return x.astype(np.float32), np.roll(x, -1, axis=1).astype(np.float32)


def _fit_lm(contexts, x, y, batch, num_epoch, kvstore):
    """``Module.fit`` on the LM; returns the module, the per-step losses
    on the first BATCH rows of each batch (device scalars chained behind
    each step — no host sync inside the loop), per-epoch
    ``(counters, end time)``, and the bound inputs as the last step saw
    them (after ``fit`` they are re-laid out with the parameters)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx

    np.random.seed(SEED)
    mx.random.seed(SEED)
    mod = mx.mod.Module(_lm_symbol(), context=contexts)
    it = mx.io.NDArrayIter(x, y, batch_size=batch,
                           label_name="softmax_label")
    rows = BATCH * SEQ
    first_labels = y[:BATCH].reshape(-1).astype(np.int32)

    @jax.jit
    def first_rows_loss(probs, labels):
        p = jnp.take_along_axis(probs[:rows], labels[:, None], axis=1)
        return -jnp.mean(jnp.log(p[:, 0].astype(jnp.float32) + 1e-12))

    losses, epochs, inputs = [], [], {}

    def on_batch(_param):
        losses.append(first_rows_loss(mod.get_outputs()[0].data,
                                      first_labels))
        inputs.update((n, mod._exec.arg_dict[n].data)
                      for n in mod._data_names + mod._label_names)

    def on_epoch(*_args):
        epochs.append((dict(mx.profiler.counters()), time.perf_counter()))

    mod.fit(it, num_epoch=num_epoch, eval_metric="ce", kvstore=kvstore,
            optimizer="sgd",
            optimizer_params={"learning_rate": LEARNING_RATE},
            initializer=mx.init.Xavier(),
            batch_end_callback=on_batch, epoch_end_callback=on_epoch)
    return mod, [float(v) for v in jax.device_get(losses)], epochs, inputs


def _lowered_fused_step(mod, inputs):
    """The fused step as ``fit`` ran it, lowered — the arguments
    ``Module._build_fused_step``'s ``run`` passes, rebuilt from the bound
    state (every parameter of this model is trained; it has no aux)."""
    import jax
    import jax.numpy as jnp
    ex = mod._exec
    params = {n: ex.arg_dict[n].data for n in mod._param_names}
    return mod._fused_jit.lower(
        params, mod._fused_states, {}, inputs, {},
        jax.random.fold_in(ex._base_key, 1),
        jnp.asarray(LEARNING_RATE, jnp.float32),
        jnp.asarray(1, jnp.int32))


def _check_mosaic_kernels(mod, inputs):
    text = _lowered_fused_step(mod, inputs).as_text()
    for kernel in ("_fa_kernel", "_fa_bwd_kernel"):
        check("tpu_custom_call" in text
              and 'kernel_name = "%s"' % kernel in text,
              "no Mosaic custom call for %s in the lowered fused step "
              "(interpret mode or an XLA substitute?)" % kernel)


def _flash_rows_per_chip(mod, inputs):
    """Leading (batch*heads) sizes of the q/k/v-shaped operands and results
    of every Mosaic custom call in the compiled, partitioned fused step."""
    hlo = _lowered_fused_step(mod, inputs).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    check(len(calls) >= 2, "no Mosaic custom calls in the compiled step")
    pattern = r"bf16\[(\d+),%d,%d\]" % (SEQ, D_MODEL // HEADS)
    return {int(n) for line in calls for n in re.findall(pattern, line)}


def _bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def _on_tpu(arr):
    return all(d.platform == "tpu" for d in arr.devices())


def phase_train():
    import mxnet_tpu as mx
    x1, y1 = _rows(BATCH, SEED)
    x = np.tile(x1, (BATCHES_PER_EPOCH, 1))
    y = np.tile(y1, (BATCHES_PER_EPOCH, 1))
    c0 = dict(mx.profiler.counters())
    mod, losses, epochs, inputs = _fit_lm(mx.tpu(0), x, y, BATCH, EPOCHS,
                                          "local")
    steps = BATCHES_PER_EPOCH * EPOCHS

    check(mod._fused is not None and mod._fused_num_update == steps,
          "fused step not taken: _fused=%r after %d updates"
          % (mod._fused, mod._fused_num_update))
    check(all(_on_tpu(mod._exec.arg_dict[n].data)
              for n in mod._param_names), "parameters are not on a TPU")
    _check_mosaic_kernels(mod, inputs)
    check(len(losses) == steps and np.all(np.isfinite(losses)),
          "losses not finite: %r" % (losses,))
    check(losses[-1] < losses[0], "loss did not fall on a repeated batch: "
          "%r" % (losses,))

    (c1, t1), (c2, t2) = epochs
    for name in ("obs_compile_count", "loop_recompile"):
        check(c2.get(name, 0) == c1.get(name, 0),
              "epoch 2 compiled: %s went %d -> %d"
              % (name, c1.get(name, 0), c2.get(name, 0)))
    check(c2.get("loop_host_sync", 0) == c0.get("loop_host_sync", 0),
          "the fit loop synced the host per batch (device metrics off?)")
    step_s = (t2 - t1) / BATCHES_PER_EPOCH
    return mod, {"losses": [round(v, 4) for v in losses],
                 "step_ms": round(step_s * 1e3, 1),
                 "tok_s": round(BATCH * SEQ / step_s, 1)}


# ------------------------------------------------------------------- serve

def phase_serve(mod):
    import jax
    import mxnet_tpu as mx

    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in PROMPT_LENS]
    counters = mx.profiler.counters

    def generate(srv, *which):
        handles = [srv.submit_generate(prompts[i],
                                       max_new_tokens=NEW_TOKENS)
                   for i in which]
        # a scheduler-thread failure surfaces here, through the handle
        return [h.result(timeout=900) for h in handles]

    with mx.serve.GenerativeServer(mod, n_heads=HEADS) as srv:
        check(all(_on_tpu(a) for a in srv.cache.state()),
              "KV cache is not on a TPU")
        check(all(_on_tpu(a) for a in srv.engine.params.values()),
              "served parameters are not on a TPU")
        out = {}
        out[0], out[2] = generate(srv, 0, 2)      # two in flight together
        (out[1],) = generate(srv, 1)
        (out[3],) = generate(srv, 3)
        c0, t0 = dict(counters()), time.perf_counter()
        (repeat,) = generate(srv, 1)
        c1, repeat_s = dict(counters()), time.perf_counter() - t0
        stats = srv.stats()

    for i, toks in out.items():
        check(len(toks) == NEW_TOKENS
              and all(0 <= t < VOCAB for t in toks),
              "prompt of %d tokens: bad generation %r"
              % (PROMPT_LENS[i], toks))
    check(repeat == out[1], "greedy repeat differs: %r vs %r"
          % (repeat, out[1]))
    for name in ("obs_compile_count", srv.name + "_compile"):
        check(c1.get(name, 0) == c0.get(name, 0),
              "the repeated request compiled: %s went %d -> %d"
              % (name, c0.get(name, 0), c1.get(name, 0)))

    # the first token of every prompt against Module.forward on the same
    # weights: one forward over a batch whose rows are the padded prompts
    # (causal attention: padding after a prompt cannot reach it), against
    # the logits the server's own prefill program gives for that prompt
    x = np.zeros((BATCH, SEQ), np.float32)
    for i, p in enumerate(prompts):
        x[i, :len(p)] = p
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(np.zeros_like(x))]),
                is_train=False)
    probs = mod.get_outputs()[0].data.reshape(BATCH, SEQ, VOCAB)
    exact, worst = 0, 0.0
    for i, p in enumerate(prompts):
        logp = np.log(np.asarray(jax.device_get(probs[i, len(p) - 1]),
                                 np.float64) + 1e-30)
        slot = srv.cache.acquire(len(p))
        picked, logits = srv.engine.prefill(p, slot, logits=True)
        logits = np.asarray(logits, np.float64)
        srv.cache.release(slot)
        check(picked == int(logits.argmax()) == out[i][0],
              "prompt of %d tokens: the server answered %d, its prefill "
              "picked %d, its logits say %d"
              % (len(p), out[i][0], picked, int(logits.argmax())))
        served = logits - logits.max()
        served -= np.log(np.exp(served).sum())
        err = float(np.linalg.norm(served - logp)
                    / np.linalg.norm(logp - logp.mean()))
        check(err <= LOGPROB_RTOL,
              "prompt of %d tokens: first-token log-probabilities of the "
              "server and Module.forward differ by %.3f of their spread"
              % (len(p), err))
        gap = float(logp.max() - logp[out[i][0]])
        check(gap <= FIRST_TOKEN_NATS,
              "prompt of %d tokens: server's first token %d is %.3f nats "
              "below Module.forward's best (%d)"
              % (len(p), out[i][0], gap, int(logp.argmax())))
        exact += int(logp.argmax() == out[i][0])
        worst = max(worst, err)
    return {"requests": stats["requests"], "tokens": stats["tokens"],
            "executables": stats["compiles"],
            "first_token_argmax_equal": "%d/%d" % (exact, len(prompts)),
            "first_token_logprob_rel_err_max": round(worst, 4),
            "repeat_request_ms": round(repeat_s * 1e3, 1),
            "tpot_ms_p50": stats["tpot"]["p50_ms"]}


# ------------------------------------------------------------------- gluon

def phase_gluon():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    np.random.seed(SEED)
    mx.random.seed(SEED)
    ctx = mx.tpu(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(SEED)
    x = mx.nd.array(rng.randn(64, 128).astype(np.float32), ctx=ctx)
    y = mx.nd.array(rng.randint(0, 10, (64,)).astype(np.float32), ctx=ctx)
    c0 = dict(mx.profiler.counters())
    losses = []
    for _ in range(3):
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(64)
        losses.append(float(loss.asnumpy().mean()))
    c1 = mx.profiler.counters()

    def delta(name):
        return c1.get(name, 0) - c0.get(name, 0)

    # one structure-cached donated program, compiled once and hit twice;
    # a failed build falls back to per-parameter updates without a word
    check(delta("trainer_step_compile") == 1
          and delta("trainer_step_cache_hit") == 2
          and delta("trainer_step_compile_failed") == 0,
          "the fused trainer step was not taken three times: compile=%d "
          "cache_hit=%d failed=%d"
          % (delta("trainer_step_compile"), delta("trainer_step_cache_hit"),
             delta("trainer_step_compile_failed")))
    check(all(_on_tpu(p.data().data)
              for p in net.collect_params().values()),
          "gluon parameters are not on a TPU")
    check(np.all(np.isfinite(losses)) and losses[-1] < losses[0],
          "gluon loss not finite and falling: %r" % (losses,))
    return {"losses": [round(v, 4) for v in losses]}


# -------------------------------------------------------------- four chips

def phase_four_chips(one_chip_first_loss):
    import jax
    import mxnet_tpu as mx
    devices = jax.devices()[:4]
    # rows 0..BATCH-1 are the one-chip batch; the other chips get their own
    x1, y1 = _rows(BATCH, SEED)
    x2, y2 = _rows(3 * BATCH, SEED + 2)
    x = np.tile(np.concatenate([x1, x2]), (2, 1))
    y = np.tile(np.concatenate([y1, y2]), (2, 1))
    mod, losses, _, inputs = _fit_lm([mx.tpu(i) for i in range(4)], x, y,
                                     4 * BATCH, 1, "device")

    check(mod._fused is not None and mod._fused_num_update == 2,
          "fused step not taken on four chips")
    shards = {s.device: s.data.shape
              for s in inputs["data"].addressable_shards}
    check(set(shards) == set(devices)
          and set(shards.values()) == {(BATCH, SEQ)},
          "the batch is not sharded 4 x %s: %r" % ((BATCH, SEQ), shards))
    for n in mod._param_names:
        arr = mod._exec.arg_dict[n].data
        check({s.device for s in arr.addressable_shards} == set(devices),
              "parameter %s is not addressable on all four chips" % n)
    # a Mosaic custom call is opaque to the partitioner: the kernel's own
    # rule must leave every chip its own BATCH * HEADS rows, not gather all
    kernel_rows = _flash_rows_per_chip(mod, inputs)
    check(kernel_rows == {BATCH * HEADS},
          "the flash kernels do not run on each chip's own %d rows: "
          "operand rows %r" % (BATCH * HEADS, sorted(kernel_rows)))
    in_use = [_bytes_in_use(d) for d in devices]
    check(all(b > 0 for b in in_use),
          "a chip reports no memory in use: %r" % (in_use,))
    check(np.all(np.isfinite(losses)), "losses not finite: %r" % (losses,))
    check(abs(losses[0] - one_chip_first_loss)
          <= LOSS_RTOL * abs(one_chip_first_loss),
          "first-step loss on four chips %.5f != one chip %.5f"
          % (losses[0], one_chip_first_loss))
    return {"losses": [round(v, 4) for v in losses],
            "one_chip_first_loss": round(one_chip_first_loss, 4),
            "flash_kernel_rows_per_chip": sorted(kernel_rows),
            "gib_in_use": [round(b / 2**30, 2) for b in in_use]}


# -------------------------------------------------------------------- main

def _native_build():
    """Whether libmxnative.so built from source here: a failed build is
    swallowed into the pure-Python reader."""
    from mxnet_tpu import native
    prebuilt = os.path.exists(native._SO)
    return {"prebuilt": prebuilt, "loaded": native.lib() is not None}


def main():
    import jax
    import jax.monitoring
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    device = phase_device()      # exits here, result-less, without a TPU
    import jaxlib
    import mxnet_tpu as mx
    mx.amp.init("bfloat16")
    phases = Phases(mx.profiler.counters)
    ok = False
    try:
        mod, train = phases.run("train", phase_train)
        serve = phases.run("serve", phase_serve, mod)
        gluon = phases.run("gluon", phase_gluon)
        info = {"train": train, "serve": serve, "gluon": gluon}
        if device["count"] >= 4:
            del mod
            gc.collect()
            info["four_chips"] = phases.run(
                "four_chips", phase_four_chips, train["losses"][0])
        ok = True
    except Exception:       # any failed check: report it, exit non-zero
        traceback.print_exc()
        info = {"failed": True}

    import libtpu
    report = {
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu.__version__,
                     "python": sys.version.split()[0]},
        "compile_cache": dict(cache,
                              dir=jax.config.jax_compilation_cache_dir),
        "native": _native_build(),
        "seconds": dict(phases.seconds,
                        total=round(time.perf_counter() - _START, 2)),
        "info": info,
    }
    print(json.dumps(report), flush=True)
    # the last line is the verdict alone, with exactly these keys
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
