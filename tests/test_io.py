"""Data IO tests (reference: tests/python/unittest/test_io.py,
test_recordio.py)."""
import gzip
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def test_ndarray_iter_basic():
    data = np.arange(40, dtype=np.float32).reshape(10, 4)
    label = np.arange(10, dtype=np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=4, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 4)
    assert batches[-1].pad == 2
    # padded batch wraps around
    assert_almost_equal(batches[-1].data[0].asnumpy()[2:], data[:2])
    it.reset()
    assert len(list(it)) == 3


def test_ndarray_iter_discard_and_shuffle():
    data = np.arange(10, dtype=np.float32).reshape(10, 1)
    it = mx.io.NDArrayIter(data, None, batch_size=3,
                           last_batch_handle="discard", shuffle=True)
    batches = list(it)
    assert len(batches) == 3
    seen = np.concatenate([b.data[0].asnumpy().ravel() for b in batches])
    assert len(set(seen.astype(int))) == 9


def test_ndarray_iter_dict_input():
    it = mx.io.NDArrayIter({"a": np.zeros((6, 2)), "b": np.ones((6, 3))},
                           np.arange(6), batch_size=2)
    names = [d.name for d in it.provide_data]
    assert sorted(names) == ["a", "b"]
    b = next(it)
    assert len(b.data) == 2


def test_csv_iter(tmp_path):
    data = np.random.rand(8, 3).astype(np.float32)
    label = np.arange(8, dtype=np.float32)
    np.savetxt(tmp_path / "d.csv", data, delimiter=",")
    np.savetxt(tmp_path / "l.csv", label, delimiter=",")
    it = mx.io.CSVIter(data_csv=str(tmp_path / "d.csv"), data_shape=(3,),
                       label_csv=str(tmp_path / "l.csv"), batch_size=4)
    b = next(it)
    assert b.data[0].shape == (4, 3)
    assert_almost_equal(b.data[0], data[:4], rtol=1e-5, atol=1e-6)


def _write_idx(path, arr):
    ndim = arr.ndim
    magic = 0x800 + ndim if arr.dtype == np.uint8 else 0x800 + ndim
    with open(path, "wb") as f:
        f.write(struct.pack(">I", (0x08 << 8) | ndim))
        for d in arr.shape:
            f.write(struct.pack(">I", d))
        f.write(arr.astype(np.uint8).tobytes())


def test_mnist_iter(tmp_path):
    images = (np.random.rand(20, 28, 28) * 255).astype(np.uint8)
    labels = (np.arange(20) % 10).astype(np.uint8)
    _write_idx(tmp_path / "img", images)
    _write_idx(tmp_path / "lbl", labels)
    it = mx.io.MNISTIter(image=str(tmp_path / "img"),
                         label=str(tmp_path / "lbl"),
                         batch_size=5, shuffle=False, flat=False)
    b = next(it)
    assert b.data[0].shape == (5, 1, 28, 28)
    assert b.label[0].shape == (5,)
    assert_almost_equal(b.data[0].asnumpy()[0, 0], images[0] / 255.0,
                        rtol=1e-5, atol=1e-6)
    flat_it = mx.io.MNISTIter(image=str(tmp_path / "img"),
                              label=str(tmp_path / "lbl"),
                              batch_size=5, shuffle=False, flat=True)
    b = next(flat_it)
    assert b.data[0].shape == (5, 784)


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "t.rec")
    rec = mx.recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"x" * 1000, b"", b"abc\x00def"]
    for p in payloads:
        rec.write(p)
    rec.close()
    rec = mx.recordio.MXRecordIO(path, "r")
    got = []
    while True:
        r = rec.read()
        if r is None:
            break
        got.append(r)
    # empty payload reads back as empty bytes
    assert got == [b"hello", b"x" * 1000, b"", b"abc\x00def"]


def test_indexed_recordio(tmp_path):
    path = str(tmp_path / "t.rec")
    idx_path = str(tmp_path / "t.idx")
    rec = mx.recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(5):
        rec.write_idx(i, b"rec%d" % i)
    rec.close()
    rec = mx.recordio.MXIndexedRecordIO(idx_path, path, "r")
    assert rec.read_idx(3) == b"rec3"
    assert rec.read_idx(0) == b"rec0"
    assert rec.keys == [0, 1, 2, 3, 4]


def test_pack_unpack_header():
    h = mx.recordio.IRHeader(0, 3.0, 7, 0)
    s = mx.recordio.pack(h, b"payload")
    h2, payload = mx.recordio.unpack(s)
    assert payload == b"payload"
    assert h2.label == 3.0 and h2.id == 7
    # multi-label
    h = mx.recordio.IRHeader(4, np.array([1, 2, 3, 4], np.float32), 9, 0)
    h2, payload = mx.recordio.unpack(mx.recordio.pack(h, b"z"))
    assert_almost_equal(h2.label, np.array([1, 2, 3, 4], np.float32))
    assert payload == b"z"


def test_image_record_iter(tmp_path):
    cv2 = pytest.importorskip("cv2")
    path = str(tmp_path / "img.rec")
    rec = mx.recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(10):
        img = (rng.rand(12, 12, 3) * 255).astype(np.uint8)
        rec.write(mx.recordio.pack_img(
            mx.recordio.IRHeader(0, float(i % 3), i, 0), img, img_fmt=".png"))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=4, shuffle=False,
                               preprocess_threads=2)
    b = next(it)
    assert b.data[0].shape == (4, 3, 8, 8)
    assert b.label[0].shape == (4,)
    assert_almost_equal(b.label[0], np.array([0.0, 1.0, 2.0, 0.0]))
    n = 1
    try:
        while True:
            b = next(it)
            n += 1
    except StopIteration:
        pass
    assert n == 3  # 10 imgs / bs 4 -> 2 full + 1 padded
    it.reset()
    b = next(it)
    assert b.data[0].shape == (4, 3, 8, 8)


def test_prefetching_iter():
    data = np.arange(24, dtype=np.float32).reshape(12, 2)
    base = mx.io.NDArrayIter(data, np.arange(12), batch_size=4)
    it = mx.io.PrefetchingIter(base)
    batches = []
    try:
        while True:
            batches.append(it.next())
    except StopIteration:
        pass
    assert len(batches) == 3
    assert_almost_equal(batches[0].data[0], data[:4])
    it.reset()
    b2 = it.next()
    assert_almost_equal(b2.data[0], data[:4])


def test_resize_iter():
    data = np.zeros((8, 2), np.float32)
    base = mx.io.NDArrayIter(data, None, batch_size=4)
    it = mx.io.ResizeIter(base, 5)
    assert len(list(it)) == 5


def test_recordio_multipart_write_roundtrip(tmp_path):
    # payloads >= 2**29 bytes are split into a cflag 1/2/3 chain
    # (dmlc-core writer behavior); small payloads stay single-part, and
    # the reader must reassemble a hand-forged chain.
    path = str(tmp_path / "big.rec")
    rec = mx.recordio.MXRecordIO(path, "w")
    payload = bytes(range(256)) * 40                      # 10240 bytes
    rec.write(payload)
    rec.close()
    rec = mx.recordio.MXRecordIO(path, "r")
    assert rec.read() == payload
    rec.close()
    # now forge a 3-part chain on disk and check the reader reassembles it
    kmagic = 0xced7230a
    with open(str(tmp_path / "chain.rec"), "wb") as f:
        parts = [payload[:4000], payload[4000:8000], payload[8000:]]
        for i, chunk in enumerate(parts):
            cflag = 1 if i == 0 else (3 if i == len(parts) - 1 else 2)
            f.write(struct.pack("<II", kmagic, (cflag << 29) | len(chunk)))
            f.write(chunk)
            f.write(b"\x00" * ((-len(chunk)) % 4))
    rec = mx.recordio.MXRecordIO(str(tmp_path / "chain.rec"), "r")
    assert rec.read() == payload
    assert rec.read() is None
    rec.close()


def test_image_record_iter_sharding(tmp_path):
    """num_parts/part_index must partition the records disjointly
    (distributed data parallelism; reference ImageRecParserParam)."""
    import cv2
    from mxnet_tpu import recordio
    rng = np.random.RandomState(0)
    path = str(tmp_path / "s.rec")
    rec = recordio.MXRecordIO(path, "w")
    n = 20
    for i in range(n):
        img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
        ok, enc = cv2.imencode(".png", img)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                enc.tobytes()))
    rec.close()

    seen = []
    for part in (0, 1):
        it = mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=(3, 8, 8), batch_size=5,
            num_parts=2, part_index=part, round_batch=False)
        assert it.num_data == n // 2
        labels = []
        for b in it:
            lab = np.asarray(b.label[0].asnumpy()).ravel()
            if b.pad:
                lab = lab[: len(lab) - b.pad]
            labels.extend(lab.tolist())
        seen.append(set(int(v) for v in labels))
    assert seen[0].isdisjoint(seen[1])
    assert seen[0] | seen[1] == set(range(n))
    with pytest.raises(ValueError):
        mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                              batch_size=5, num_parts=2, part_index=2)


@pytest.mark.parametrize("native", [True, False])
def test_image_record_iter_spans_tell_wait_from_placement(tmp_path, native):
    """``next`` does two things on its caller's thread, each under its
    own span on both of its paths, the native pipeline's and the Python
    queue's: it waits for the decoders (io_batch_wait, also when the wait
    ends the epoch), then places the batch (io_batch_place, with the
    batch's bytes). Off, both sites take the shared no-op."""
    from mxnet_tpu import profiler
    cv2 = pytest.importorskip("cv2")
    path = str(tmp_path / "img.rec")
    rec = mx.recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = (rng.rand(12, 12, 3) * 255).astype(np.uint8)
        rec.write(mx.recordio.pack_img(
            mx.recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".png"))
    rec.close()
    kw = {} if native else {"max_random_scale": 1.001}
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=4, shuffle=False, **kw)
    if native and it._native is None:
        pytest.skip("the native pipeline did not build here")
    assert (it._native is not None) == native
    with profiler.counter_delta() as d:
        assert next(it).data[0].shape == (4, 3, 8, 8)
    assert d.get("obs_spans") == 0
    profiler.set_span_listener(lambda *a: None)
    try:
        with profiler.span("test.consumer") as consumer:
            batch = next(it)
            with pytest.raises(StopIteration):
                next(it)
    finally:
        profiler.set_span_listener(None)
    mine = [r for r in profiler.spans() if r.parent == consumer.id]
    assert [r.name for r in mine] == ["io_batch_wait", "io_batch_place",
                                      "io_batch_wait"]
    wait, place, _last = mine
    assert wait.t_end <= place.t_start and wait.attrs == {}
    # the native path hands over the decoders' uint8, a byte a value,
    # the Python path finished float32
    size = batch.data[0].size
    if native:
        assert size <= place.attrs["bytes"] < 2 * size
    else:
        assert place.attrs["bytes"] >= 4 * size
    assert place.category == "io"


# -------------------------------------------- the batch and its owed finish

def _png_rec(tmp_path, n=10, side=12):
    pytest.importorskip("cv2")
    path = str(tmp_path / "img.rec")
    rec = mx.recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = (rng.rand(side, side, 3) * 255).astype(np.uint8)
        rec.write(mx.recordio.pack_img(
            mx.recordio.IRHeader(0, float(i % 3), i, 0), img, img_fmt=".png"))
    rec.close()
    return path


_NORM = dict(mean_r=123.68, mean_g=116.28, mean_b=103.53, std_r=58.395,
             std_g=57.12, std_b=57.375)


def _image_iter(path, native, cls=None, **kw):
    if not native:
        kw["max_random_scale"] = 1.0000001     # the Python path's trigger
    it = (cls or mx.io.ImageRecordIter)(
        path_imgrec=path, data_shape=(3, 8, 8), batch_size=4, **kw)
    if native and it._native is None:
        pytest.skip("the native pipeline did not build here")
    assert (it._native is not None) == native
    return it


@pytest.mark.parametrize("dtype", ["float32", "float16"])
@pytest.mark.parametrize("native", [True, False])
def test_image_record_iter_batch_contract(tmp_path, native, dtype):
    """provide_data, shapes, dtypes, labels and pad are the same on both
    paths; only the native one defers its finish, and reading the data
    on the host does not spend the deferred form."""
    it = _image_iter(_png_rec(tmp_path), native, dtype=dtype, **_NORM)
    desc, = it.provide_data
    assert (desc.name, desc.shape, desc.dtype) == \
        ("data", (4, 3, 8, 8), np.dtype(dtype))
    batches = list(it)
    assert [b.pad for b in batches] == [0, 0, 2]
    for b in batches:
        assert (b.deferred is not None) == native
        assert b.provide_data == it.provide_data
        data = b.data[0]
        assert isinstance(data, mx.nd.NDArray)
        assert data.shape == (4, 3, 8, 8) and data.dtype == np.dtype(dtype)
        assert b.data[0] is data               # finished once
        assert b.label[0].shape == (4,)
        assert (b.deferred is not None) == native
        assert "(4, 3, 8, 8)" in str(b)
    assert_almost_equal(batches[0].label[0], np.array([0.0, 1.0, 2.0, 0.0]))
    if native:
        b = batches[0]
        b.data = [mx.nd.zeros((4, 3, 8, 8))]   # whoever assigns it owns it
        assert b.deferred is None and b.data[0].asnumpy().max() == 0


@pytest.mark.parametrize("dtype", ["float32", "float16", "uint8"])
def test_deferred_finish_on_device_equals_host(tmp_path, dtype):
    """The same finish twice: numpy's on the host (``batch.data``) and
    the jitted one where the pixels were placed."""
    import jax
    # (a negative value has no uint8: that case halves the pixels only)
    kw = dict(scale=0.5) if dtype == "uint8" else dict(scale=1 / 3., **_NORM)
    it = _image_iter(_png_rec(tmp_path), True, dtype=dtype, rand_crop=True,
                     rand_mirror=True, **kw)
    for batch in it:
        owed, = batch.deferred
        assert owed.pixels.dtype == np.uint8 and owed.mean.shape == (1, 3, 1, 1)
        placed = jax.device_put(owed.pixels, jax.devices("cpu")[1])
        for to in (dtype, "float32"):
            got = owed.finish_placed(placed, np.dtype(to))
            assert got.dtype == np.dtype(to)
            assert got.devices() == placed.devices()
            want = batch.data[0].asnumpy().astype(to)
            if dtype == "uint8":
                np.testing.assert_array_equal(np.asarray(got), want)
            else:
                np.testing.assert_allclose(
                    np.asarray(got), want,
                    rtol=1e-3 if dtype == "float16" else 1e-6, atol=1e-6)


@pytest.mark.parametrize("native", [True, False])
def test_image_record_uint8_iter(tmp_path, native):
    """ImageRecordUInt8Iter is the deferred form with nothing owed but
    the pixels themselves."""
    it = _image_iter(_png_rec(tmp_path), native,
                     cls=mx.io.ImageRecordUInt8Iter)
    assert it.provide_data[0].dtype == np.uint8
    batch = next(it)
    data = batch.data[0].asnumpy()
    assert data.dtype == np.uint8 and data.shape == (4, 3, 8, 8)
    if native:
        owed, = batch.deferred
        assert owed.mean is None and owed.inv is None
        np.testing.assert_array_equal(data, owed.pixels)
        import jax
        placed = jax.device_put(owed.pixels)
        assert owed.finish_placed(placed, np.dtype("uint8")) is placed
        np.testing.assert_array_equal(
            np.asarray(owed.finish_placed(placed, np.dtype("float32"))),
            owed.pixels.astype(np.float32))


def _conv_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=4, name="conv")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


class _Finished(mx.io.DataIter):
    """The same batches with nothing owed: finished float32, as the
    iterator handed them over before it deferred."""

    def __init__(self, inner):
        super().__init__(inner.batch_size)
        self._inner = inner
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        b = self._inner.next()
        return mx.io.DataBatch(data=[mx.nd.array(b.data[0].asnumpy())],
                               label=b.label, pad=b.pad,
                               provide_data=b.provide_data,
                               provide_label=b.provide_label)


def _fit_two_steps(it, prefetch):
    from mxnet_tpu import config as cfg, profiler
    cfg.set("MXNET_TPU_DEVICE_PREFETCH", prefetch)
    try:
        rng = np.random.RandomState(11)
        start = {"conv_weight": (4, 3, 3, 3), "conv_bias": (4,),
                 "fc_weight": (3, 144), "fc_bias": (3,)}
        start = {k: mx.nd.array(rng.normal(0, 0.1, v))
                 for k, v in start.items()}
        mod = mx.mod.Module(_conv_symbol(), context=mx.cpu(0))
        with profiler.counter_delta() as d:
            mod.fit(it, num_epoch=1, optimizer="sgd", arg_params=start,
                    optimizer_params={"learning_rate": 0.05})
            counts = {k: d.get(k) for k in ("io_batches_finished_on_device",
                                            "loop_prefetch_placed")}
        args, _aux = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}, counts
    finally:
        cfg.reset("MXNET_TPU_DEVICE_PREFETCH")


@pytest.mark.parametrize("prefetch", [2, 0, "the_users_own"])
def test_fit_from_deferred_batches_equals_fit_from_float32(tmp_path, prefetch):
    """Two ``Module.fit`` steps: the batch finished on the device it was
    placed on trains as the finished float32 batch does, through the
    prefetch stage, through ``_load_batch`` alone, and through a
    ``PrefetchingIter`` the user wrapped himself (which hands the
    deferred form on); every batch of the deferred form counts, none of
    the other."""
    path = _png_rec(tmp_path, n=8)
    kw = dict(rand_crop=True, rand_mirror=True, seed=4, scale=0.5, **_NORM)
    wrap = (lambda it: it) if prefetch != "the_users_own" \
        else mx.io.PrefetchingIter
    depth = 0 if prefetch == 0 else 2
    owed_it = wrap(_image_iter(path, True, **kw))
    done_it = wrap(_Finished(_image_iter(path, True, **kw)))
    try:
        owed, c_owed = _fit_two_steps(owed_it, depth)
        done, c_done = _fit_two_steps(done_it, depth)
    finally:
        for it in (owed_it, done_it):
            getattr(it, "close", lambda: None)()
    assert c_owed["io_batches_finished_on_device"] == 2
    assert c_done["io_batches_finished_on_device"] == 0
    assert c_owed["loop_prefetch_placed"] == c_done["loop_prefetch_placed"] \
        == (2 if prefetch == 2 else 0)
    assert set(owed) == set(done)
    for k in owed:
        np.testing.assert_allclose(owed[k], done[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_placing_deferred_batches_compiles_once(tmp_path):
    """The finish is one program a (shape, dtype, device): the first batch
    compiles it, whether or not somebody read ``batch.data`` on the host
    first, and no later batch compiles anything."""
    from mxnet_tpu import profiler
    it = _image_iter(_png_rec(tmp_path, n=16), True, rand_crop=True, **_NORM)
    mod = mx.mod.Module(_conv_symbol(), context=mx.cpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    place = mod._device_placer()
    first = next(it)
    host = first.data[0].asnumpy()             # read on the host first
    with profiler.counter_delta() as d:
        placed = place(first)._mx_placed["data"]
        assert d.get("io_batches_finished_on_device") == 1
    np.testing.assert_allclose(np.asarray(placed), host, rtol=1e-6, atol=1e-6)
    mod.forward(first, is_train=False)         # the model's own program
    with profiler.counter_delta() as d:
        for batch in it:
            mod.forward(place(batch), is_train=False)
        assert d.get("io_batches_finished_on_device") == 3
        assert d.get("obs_compile_count") == 0


@pytest.mark.parametrize("chips", [1, 4])
def test_deferred_batch_lands_where_a_float32_batch_would(tmp_path, chips):
    """One device or a data-parallel mesh: the pixels take the sharding
    branch the finished batch takes, and the finish leaves them there."""
    it = _image_iter(_png_rec(tmp_path), True, rand_mirror=True, **_NORM)
    ctx = [mx.cpu(i) for i in range(chips)]
    mod = mx.mod.Module(_conv_symbol(), context=ctx if chips > 1 else ctx[0])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    batch = next(it)
    owed = mod._place_value("data", batch.deferred[0])
    done = mod._place_value("data", batch.data[0])
    assert owed.dtype == done.dtype == np.float32
    assert owed.sharding.is_equivalent_to(done.sharding, 4)
    assert len(owed.sharding.device_set) == chips
    np.testing.assert_allclose(np.asarray(owed), np.asarray(done),
                               rtol=1e-6, atol=1e-6)
    assert mod._place_value("nobody", batch.deferred[0]) is None


# ----------------------------------------------- recordio index validation

def _tamper_dataset(tmp_path, n=6):
    """A healthy indexed record file the tamper tests then corrupt."""
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "t.rec")
    idx_path = str(tmp_path / "t.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    for i in range(n):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), b"payload-%d" % i))
    w.close()
    return rec_path, idx_path


def test_indexed_recordio_rejects_offset_past_eof(tmp_path):
    """A stale/corrupt .idx whose offset cannot hold a record header is
    rejected AT OPEN with the index key named — not later as an opaque
    struct error from whatever read_idx happens to hit it."""
    from mxnet_tpu import recordio
    rec_path, idx_path = _tamper_dataset(tmp_path)
    size = os.path.getsize(rec_path)
    with open(idx_path) as fin:
        lines = fin.read().splitlines()
    lines[3] = "3\t%d" % (size + 100)          # key 3 -> past EOF
    with open(idx_path, "w") as fout:
        fout.write("\n".join(lines) + "\n")
    with pytest.raises(IOError) as err:
        recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    msg = str(err.value)
    assert "3" in msg and idx_path in msg and "stale or corrupt" in msg


def test_indexed_recordio_rejects_malformed_index_line(tmp_path):
    from mxnet_tpu import recordio
    rec_path, idx_path = _tamper_dataset(tmp_path)
    with open(idx_path, "a") as fout:
        fout.write("not-a-key\n")
    with pytest.raises(IOError) as err:
        recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert "malformed index entry" in str(err.value)
    assert idx_path in str(err.value)


def test_indexed_recordio_names_key_on_bad_magic(tmp_path):
    """An in-bounds offset that lands mid-record: the magic check fires
    and read_idx names the index key, offset, and file."""
    from mxnet_tpu import recordio
    rec_path, idx_path = _tamper_dataset(tmp_path)
    good = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    off = good.idx[2]
    good.close()
    with open(idx_path) as fin:
        lines = fin.read().splitlines()
    lines[2] = "2\t%d" % (off + 2)             # mid-record: valid bound,
    with open(idx_path, "w") as fout:          # garbage magic
        fout.write("\n".join(lines) + "\n")
    bad = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert bad.read_idx(1)                     # neighbors still fine
    with pytest.raises(IOError) as err:
        bad.read_idx(2)
    msg = str(err.value)
    assert "key 2" in msg and "magic" in msg.lower()
    bad.close()


def test_indexed_recordio_names_key_on_truncated_payload(tmp_path):
    """The record file ends mid-payload: the error names the promised
    vs available bytes and the index key being read."""
    from mxnet_tpu import recordio
    rec_path, idx_path = _tamper_dataset(tmp_path)
    good = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    last = good.idx[5]
    good.close()
    with open(rec_path, "r+b") as f:
        f.truncate(last + 10)                  # header intact, payload cut
    bad = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    with pytest.raises(IOError) as err:
        bad.read_idx(5)
    msg = str(err.value)
    assert "key 5" in msg and "truncated" in msg
    bad.close()


def test_indexed_recordio_missing_key_is_legible(tmp_path):
    from mxnet_tpu import recordio
    rec_path, idx_path = _tamper_dataset(tmp_path)
    r = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    with pytest.raises(KeyError) as err:
        r.read_idx(99)
    assert "99" in str(err.value) and idx_path in str(err.value)
    r.close()
