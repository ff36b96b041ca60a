"""Driver benchmark: ResNet-50 fused training step, images/sec on one chip,
plus a transformer-LM train step as the MXU-bound secondary workload.

Baseline: the reference's published training number for ResNet-50 at batch 32
— 181.53 img/s on P100 (BASELINE.md, docs/how_to/perf.md:180-190). This
script runs the same workload through the TPU-native stack: one fused
forward+backward+SGD-update XLA program built by Module._build_fused_step,
in bf16 mixed precision (fp32 master weights, bf16 MXU compute — mx.amp).

ResNet-50's small-spatial convs are bandwidth-bound under XLA (the
roofline in docs/perf.md), so the bench also reports a transformer LM
(models/transformer.py) through the identical Module fused-step path —
the workload class whose large matmuls can actually feed the MXU.

A measurement needs a chip: with no TPU, or a ``device_kind`` the peaks
table (``mxnet_tpu/obs/mfu.py``) does not know, a section fails; nothing
falls back to the CPU. Every record names ``platform``, ``device_kind``
and ``device_count`` as JAX reports them.

One process holds a chip at a time, so this parent never imports JAX:
each workload runs as its own *section* in a child process, one after
another, with its own timeout, and every section's JSON record is printed
(and flushed) the moment it completes — a hang or an external kill loses
ONE section, not the whole artifact. Output protocol:

  {"section": "resnet", ...}        <- line per section, as it finishes
  {"section": "transformer", ...}
  {"metric": ..., "value": ...}     <- LAST line: merged record

Exit status is non-zero when any section failed.
Per-section timeout: $BENCH_SECTION_TIMEOUT_SECS (default 600).

The resnet_remat_accum section retries ResNet at 2x batch with
MXNET_TPU_REMAT=auto + grad_accum=2 (HBM headroom -> MFU).
"""
import json
import os
import subprocess
import sys
import time


def _note(msg):
    print(msg, file=sys.stderr, flush=True)

sys.path.insert(0, __file__.rsplit("/", 1)[0] if "/" in __file__ else ".")


BASELINE_IMG_S = 181.53   # P100 training, ResNet-50 batch 32
# batch 128 and a short timed window keep a section inside
# BENCH_SECTION_TIMEOUT_SECS; bind_secs is recorded per section so a
# bind-time regression shows up as a number, not as a timeout
BATCH = 128
WARMUP = 2
ITERS = 12
SECTIONS = ("resnet", "resnet_remat_accum", "transformer")

# Analytic model FLOPs: ResNet-50 @224x224 forward = 4.089e9 multiply-adds
# (= 8.18 GFLOP at 2 FLOPs/MAC); training step ~ 3x forward (fwd + 2x in bwd).
FWD_MACS_PER_IMG = 4.089e9
TRAIN_FLOPS_PER_IMG = 2 * FWD_MACS_PER_IMG * 3


def _device():
    """The chip this section measures, as JAX reports it, plus its bf16
    peak. No TPU, or a device the peaks table does not know, is a failure:
    a rate taken elsewhere must never be recorded under these names."""
    import jax
    from mxnet_tpu.obs.mfu import table_peak_flops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError("bench needs a TPU; JAX reports platform %r (%s)"
                           % (dev.platform, dev.device_kind))
    peak = table_peak_flops(dev.device_kind)
    if peak is None:
        raise RuntimeError("device_kind %r is not in the peaks table "
                           "(mxnet_tpu/obs/mfu.py)" % dev.device_kind)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}, peak


def _fence(mod, name):
    """Wait for the last dispatched step: its updated ``name`` parameter."""
    mod._exec.arg_dict[name].data.block_until_ready()


def _obs_crosscheck():
    """Framework-side MFU/compile accounting (mx.obs), reported next to
    this script's independent math: report() here closes the rate window
    the post-warmup report() opened, so the obs steps/s covers exactly
    the timed region. Divergence >10% between obs_mfu and the section's
    own mfu is a bug in one of them — that is the point of recording
    both (ISSUE 6 acceptance)."""
    import mxnet_tpu as mx
    rep = mx.obs.report()
    best = None
    for e in rep["executors"]:
        if e.get("flops_per_sec") and \
                (best is None or e["flops_per_sec"] > best["flops_per_sec"]):
            best = e
    return {
        "obs_mfu": round(best["mfu"], 4)
        if best and best.get("mfu") is not None else None,
        "obs_flops_per_sec": best["flops_per_sec"] if best else None,
        "obs_compile_count": rep["counters"].get("obs_compile_count"),
        "obs_bind_ms_total": rep["counters"].get("obs_bind_ms_total"),
    }


def _tune_provenance():
    """Where this section's config came from (ISSUE 19): ``tuned`` is
    True when an autotuner winner was applied in this process
    (``tune_applied`` counter — fit(tune=...) or MXNET_TPU_TUNE), and
    ``tune_knobs`` is the knob dict actually in effect either way, so a
    tuner-vs-hand-tuned bench delta is attributable to specific knobs
    rather than 'the tuner ran'."""
    import mxnet_tpu as mx
    return {
        "tuned": bool(mx.profiler.counters().get("tune_applied")),
        "tune_knobs": {k: mx.config.get(k) for k in (
            "MXNET_TPU_REMAT", "MXNET_TPU_ASYNC_WINDOW")},
    }


def section_transformer():
    """Transformer-LM fused train step: tokens/s + MFU on one chip."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer

    rec, peak = _device()
    mx.amp.init("bfloat16")
    # ~0.67B-param GPT-2-medium-class decoder LM with the Pallas flash
    # attention kernel (fused fwd + dQ/dK/dV backward): L12/B8 was the MFU
    # sweet spot of the docs/perf.md sweep; deeper/wider configs (1.5B)
    # hit the HBM ceiling with f32 master weights.
    L, D, H, T, V = 12, 2048, 16, 1024, 32000
    B = 8
    sym = transformer.get_symbol(vocab_size=V, num_layers=L, d_model=D,
                                 n_heads=H, seq_len=T, attention="flash")
    rng = np.random.RandomState(0)
    x = rng.randint(0, V, (B, T)).astype(np.float32)
    y = rng.randint(0, V, (B, T)).astype(np.float32)

    _note("bench: transformer bind start")
    t_bind = time.perf_counter()
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    rec["bind_secs"] = round(time.perf_counter() - t_bind, 3)
    db = mx.io.DataBatch(data=[mx.nd.array(x, ctx=mx.tpu(0))],
                         label=[mx.nd.array(y, ctx=mx.tpu(0))])
    _note("bench: transformer bound in %.1fs; compiling" % rec["bind_secs"])
    t0 = time.perf_counter()
    for _ in range(WARMUP):
        mod._fit_step(db)
    _fence(mod, "lm_head_weight")
    rec["first_step_secs"] = round(time.perf_counter() - t0, 3)
    mx.obs.report()     # open the obs rate window at the timed region
    _note("bench: transformer timing")
    t0 = time.perf_counter()
    for _ in range(ITERS):
        mod._fit_step(db)
    _fence(mod, "lm_head_weight")
    dt = time.perf_counter() - t0
    tok_s = B * T * ITERS / dt
    # PaLM-style accounting: 6*(non-embedding params) + 12*L*D*T per token
    n_params = transformer.param_count(V, L, D, H, seq_len=T)
    n_embed = V * D + T * D
    flops_per_tok = 6 * (n_params - n_embed) + 12 * L * D * T
    rec.update({"transformer_tok_s": round(tok_s, 1),
                "transformer_mfu": round(tok_s * flops_per_tok / peak, 4)})
    rec.update(_obs_crosscheck())
    rec.update(_tune_provenance())
    return rec


def _resnet_run(batch, grad_accum=None, remat=None, section="resnet"):
    """Shared ResNet-50 bf16 driver: bind, warm up, time the fused
    step."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet

    rec, peak = _device()
    ctx = mx.tpu(0)
    mx.amp.init("bfloat16")   # bf16 MXU compute, fp32 master weights
    if remat is not None:
        mx.config.set("MXNET_TPU_REMAT", remat)
    _note("bench: %s bind start" % section)
    t_bind = time.perf_counter()
    # space-to-depth stem: mathematically identical to the 7x7/2 stem on
    # the same parameter, ~2 ms/step faster (docs/perf.md round-5
    # restructuring sweep)
    sym = resnet.get_symbol(num_classes=1000, num_layers=50, stem="s2d",
                            image_shape="3,224,224")
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[("data", (batch, 3, 224, 224))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                   factor_type="in", magnitude=2))
    if grad_accum:
        mod.set_grad_accum(grad_accum)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4})
    rec["bind_secs"] = round(time.perf_counter() - t_bind, 3)
    _note("bench: %s bound in %.1fs" % (section, rec["bind_secs"]))

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 3, 224, 224)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    dbatch = mx.io.DataBatch(data=[mx.nd.array(x, ctx=ctx)],
                             label=[mx.nd.array(y, ctx=ctx)])

    _note("bench: %s compiling" % section)
    t0 = time.perf_counter()
    for _ in range(WARMUP):
        mod._fit_step(dbatch)
    _fence(mod, "fc1_weight")
    rec["first_step_secs"] = round(time.perf_counter() - t0, 3)
    mx.obs.report()     # open the obs rate window at the timed region
    _note("bench: %s timing" % section)

    rc0 = mx.profiler.counters().get("loop_recompile", 0)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        mod._fit_step(dbatch)
    _fence(mod, "fc1_weight")
    dt = time.perf_counter() - t0

    img_s = batch * ITERS / dt
    counters = mx.profiler.counters()
    rec.update({
        "metric": "resnet50_train_bf16",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "mfu": round(img_s * TRAIN_FLOPS_PER_IMG / peak, 4),
        "batch": batch,
        "flops_per_img": TRAIN_FLOPS_PER_IMG,
        "peak_flops": peak,
        # steady-state recompiles are a bug; record the timed window's
        # delta so the acceptance gate can counter-assert zero
        "loop_recompile": counters.get("loop_recompile", 0) - rc0,
        "remat_applied": counters.get("remat_applied", 0),
        "accum_steps": counters.get("accum_steps", 0),
    })
    rec.update(_obs_crosscheck())
    rec.update(_tune_provenance())
    return rec


def section_resnet():
    return _resnet_run(BATCH)


def section_resnet_remat_accum():
    """The ISSUE 9 memory levers applied: 2x the plain batch, fit in HBM
    via auto-remat + 2-way gradient accumulation."""
    return _resnet_run(2 * BATCH, grad_accum=2, remat="auto",
                       section="resnet_remat_accum")


def run_section(name):
    fn = {"resnet": section_resnet,
          "resnet_remat_accum": section_resnet_remat_accum,
          "transformer": section_transformer}[name]
    rec = dict(fn())
    rec["section"] = name
    print(json.dumps(rec), flush=True)


def _merge(records):
    """Assemble the flat single-record schema from whatever sections
    survived."""
    merged = {
        "metric": "resnet50_train_bf16", "value": None, "unit": "img/s",
        "vs_baseline": None, "mfu": None, "batch": None,
        "flops_per_img": TRAIN_FLOPS_PER_IMG, "peak_flops": None,
        "platform": None, "device_kind": None, "device_count": None,
        "transformer_tok_s": None, "transformer_mfu": None,
        "resnet_remat_accum_mfu": None, "resnet_remat_accum_img_s": None,
        "bind_secs": {},
        "first_step_secs": {},
        "obs_mfu": {},
        "obs_bind_ms_total": {},
        "tuned": {},
        "tune_knobs": {},
    }
    _per_section = ("bind_secs", "first_step_secs", "obs_mfu",
                    "obs_bind_ms_total", "tuned", "tune_knobs")
    errors = {}
    for name, rec in records.items():
        if "error" in rec:
            errors[name] = rec["error"]
            continue
        for k in ("platform", "device_kind", "device_count"):
            merged[k] = rec.get(k, merged[k])
        if name == "resnet_remat_accum":
            merged["resnet_remat_accum_mfu"] = rec.get("mfu")
            merged["resnet_remat_accum_img_s"] = rec.get("value")
        else:
            for k in merged:
                if k not in _per_section and k in rec:
                    merged[k] = rec[k]
        for k in _per_section:
            # per-section records: a slow bind is invisible in a
            # throughput-only record; obs_mfu is the framework's own MFU
            # next to this script's independent math
            if rec.get(k) is not None:
                merged[k][name] = rec[k]
    if errors:
        merged["errors"] = errors
    return merged


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--section":
        run_section(sys.argv[2])
        return 0
    timeout = float(os.environ.get("BENCH_SECTION_TIMEOUT_SECS", "600"))
    records = {}
    for name in SECTIONS:
        _note("bench: section %s (timeout %ds)" % (name, timeout))
        rec = {"section": name}
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--section", name],
                timeout=timeout, stdout=subprocess.PIPE, text=True)
        except subprocess.TimeoutExpired:
            # this section hung; its sibling sections still run and
            # still report
            rec["error"] = "timeout after %ds" % timeout
        else:
            if proc.returncode != 0:
                rec["error"] = "rc %d" % proc.returncode
            else:
                lines = (proc.stdout or "").strip().splitlines()
                try:
                    rec = json.loads(lines[-1])
                except (IndexError, ValueError):
                    rec["error"] = "no record"
        records[name] = rec
        # incremental line-per-section: flushed NOW, so a later hang
        # cannot take this section's result with it
        print(json.dumps(rec), flush=True)
    merged = _merge(records)
    print(json.dumps(merged), flush=True)
    return 1 if "errors" in merged else 0


if __name__ == "__main__":
    sys.exit(main())
