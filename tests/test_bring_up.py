"""Nothing on the path to the chip hides the device or the compile cache
(ISSUE 21): an accelerator context never resolves to a host device, a
mesh is never built from substitute devices, the compile-cache directory
follows one rule, cache entries read back, and ``chip_smoke.py`` refuses
to pass without a TPU or with a failed phase."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_accelerator_context_raises_on_cpu_backend():
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match="no accelerator"):
            ctx.jax_device
    assert mx.num_devices("tpu") == 0
    assert mx.num_devices("cpu") == 8
    with pytest.raises(MXNetError, match="no accelerator"):
        mx.nd.ones((2, 2), ctx=mx.tpu(0))


def test_mesh_larger_than_visible_devices_raises():
    from mxnet_tpu.parallel.mesh import make_mesh
    assert make_mesh({"data": 8}).devices.size == 8
    with pytest.raises(ValueError, match="needs 16 devices, only 8"):
        make_mesh({"data": 16})


def test_compile_cache_directory_rule(monkeypatch):
    import jax
    from mxnet_tpu import config
    before = jax.config.jax_compilation_cache_dir
    try:
        # not placed from outside: a fixed path beside the package
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        config._apply_import_knobs()
        default = os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == default
        # placed from outside: the program sets no other
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        monkeypatch.setattr(config, "DEFAULT_COMPILE_CACHE_DIR", "/not/this")
        config._apply_import_knobs()
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        if jax.config.jax_compilation_cache_dir != before:
            jax.config.update("jax_compilation_cache_dir", before)
    assert "MXNET_COMPILATION_CACHE_DIR" not in config.KNOBS


def test_second_compile_is_a_cache_read(capfd):
    import jax
    import jax.monitoring
    import jax.numpy as jnp
    events = []

    def listener(event, **_kw):
        events.append(event)

    salt = float(np.random.RandomState().rand())    # a program never seen
    x = jnp.ones(3)
    jax.monitoring.register_event_listener(listener)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")     # a failed read only warns
            jax.jit(lambda v: v * salt)(x)
            first = list(events)
            del events[:]
            # a new function object misses jit's in-memory cache; the
            # identical program must come back from the persistent one
            capfd.readouterr()
            jax.jit(lambda v: v * salt)(x)
            said = capfd.readouterr().err
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert "/jax/compilation_cache/cache_misses" in first
    assert "/jax/compilation_cache/cache_hits" in events
    assert "/jax/compilation_cache/cache_misses" not in events
    # XLA:CPU's loader complains on every read unless conftest's
    # TF_CPP_MIN_LOG_LEVEL holds
    assert said == "", said[:300]


def test_chip_smoke_refuses_to_pass_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""      # no result line


def test_chip_smoke_exits_non_zero_when_a_phase_fails(monkeypatch, capsys):
    import jax.monitoring
    from mxnet_tpu import amp
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda listener: None)
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})

    def broken():
        raise RuntimeError("the train phase broke")

    monkeypatch.setattr(chip_smoke, "phase_train", broken)
    try:
        code = chip_smoke.main()
    finally:
        amp.off()           # main() turns bf16 compute on for the process
    captured = capsys.readouterr()
    assert code == 1
    assert "the train phase broke" in captured.err
    report, last = map(json.loads, captured.out.strip().splitlines()[-2:])
    assert report["info"] == {"failed": True}
    # the last line is the verdict alone: exactly these keys
    assert last == {"ok": False, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
