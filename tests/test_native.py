"""Native C++ data path (mxnet_tpu/native): RecordIO codec, image decode,
and the threaded batch pipeline, each checked against a Python oracle.

Reference parity: dmlc-core RecordIO framing + src/io/iter_image_recordio_2.cc
(SURVEY.md §2.8, §2.11).
"""
import ctypes
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native
from mxnet_tpu import recordio as rio

L = native.lib()
pytestmark = pytest.mark.skipif(
    L is None, reason="native library unavailable (no toolchain)")

u8p = ctypes.POINTER(ctypes.c_uint8)


def _write_rec(tmp_path, payloads):
    path = str(tmp_path / "t.rec")
    rec = rio.MXRecordIO(path, "w")
    for b in payloads:
        rec.write(b)
    rec.close()
    return path


def test_native_reader_matches_python_codec(tmp_path):
    payloads = [b"hello", b"x" * 1037, b"", os.urandom(4096), b"abcd"]
    path = _write_rec(tmp_path, payloads)
    r = L.mxrio_open(path.encode())
    assert r
    assert L.mxrio_count(r) == len(payloads)
    for i, b in enumerate(payloads):
        ptr = u8p()
        n = L.mxrio_get(r, i, ctypes.byref(ptr))
        got = bytes(bytearray(ptr[:n])) if n else b""
        assert got == b
        off = L.mxrio_offset(r, i)
        assert L.mxrio_index_of(r, off) == i
    L.mxrio_close(r)


def test_native_writer_matches_python_reader(tmp_path):
    path = str(tmp_path / "w.rec")
    payloads = [b"alpha", b"b" * 999, b"gamma"]
    w = L.mxrio_writer_open(path.encode())
    offs = [L.mxrio_writer_write(w, b, len(b)) for b in payloads]
    assert L.mxrio_writer_close(w) == 0
    assert offs[0] == 0 and all(o >= 0 for o in offs)
    rec = rio.MXRecordIO(path, "r")
    for b in payloads:
        assert rec.read() == b
    assert rec.read() is None
    rec.close()


def test_native_jpeg_png_decode_vs_cv2():
    cv2 = pytest.importorskip("cv2")
    img = (np.random.RandomState(0).rand(37, 53, 3) * 255).astype(np.uint8)
    out, h, w, c = u8p(), ctypes.c_int(), ctypes.c_int(), ctypes.c_int()
    for fmt, exact in ((".jpg", True), (".png", True)):
        ok, enc = cv2.imencode(fmt, img)
        buf = enc.tobytes()
        rc = L.mximg_decode(buf, len(buf), 3, ctypes.byref(out),
                            ctypes.byref(h), ctypes.byref(w),
                            ctypes.byref(c))
        assert rc == 0
        arr = np.ctypeslib.as_array(out, shape=(h.value, w.value,
                                                c.value)).copy()
        L.mximg_free(out)
        ref = cv2.cvtColor(cv2.imdecode(enc, cv2.IMREAD_COLOR),
                           cv2.COLOR_BGR2RGB)
        # same libjpeg/libpng underneath: decodes are bit-identical
        np.testing.assert_array_equal(arr, ref)


def test_native_resize_close_to_cv2():
    cv2 = pytest.importorskip("cv2")
    img = (np.random.RandomState(3).rand(41, 67, 3) * 255).astype(np.uint8)
    dst = np.zeros((23, 31, 3), np.uint8)
    L.mximg_resize(img.ctypes.data_as(u8p), 41, 67, 3,
                   dst.ctypes.data_as(u8p), 23, 31)
    ref = cv2.resize(img, (31, 23), interpolation=cv2.INTER_LINEAR)
    assert np.abs(dst.astype(int) - ref.astype(int)).max() <= 1


def _make_image_rec(tmp_path, n=11):
    cv2 = pytest.importorskip("cv2")
    rng = np.random.RandomState(1)
    path = str(tmp_path / "imgs.rec")
    rec = rio.MXRecordIO(path, "w")
    imgs = []
    for i in range(n):
        img = (rng.rand(40 + i, 48, 3) * 255).astype(np.uint8)  # HWC RGB
        imgs.append(img)
        ok, enc = cv2.imencode(".png", img[:, :, ::-1])
        rec.write(rio.pack(rio.IRHeader(0, float(i), i, 0), enc.tobytes()))
    rec.close()
    return path, imgs


def test_native_pipeline_vs_numpy_oracle(tmp_path):
    path, imgs = _make_image_rec(tmp_path)
    mean = np.array([123., 117., 104.], np.float32)
    std = np.array([58., 57., 57.], np.float32)
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
        mean_r=123, mean_g=117, mean_b=104, std_r=58, std_g=57, std_b=57)
    assert it._native is not None, "native pipeline should engage here"
    i = 0
    for batch in it:
        n = batch.data[0].shape[0] - batch.pad
        dat = batch.data[0].asnumpy()
        lab = batch.label[0].asnumpy()
        for k in range(n):
            img = imgs[i]
            h, w = img.shape[:2]
            y0, x0 = (h - 32) // 2, (w - 32) // 2
            ref = img[y0:y0 + 32, x0:x0 + 32].astype(np.float32)
            ref = ((ref - mean) / std).transpose(2, 0, 1)
            np.testing.assert_allclose(dat[k], ref, atol=1e-4)
            assert lab[k] == float(i)
            i += 1
    assert i == len(imgs)


def test_native_pipeline_shuffle_epochs_deterministic(tmp_path):
    path, _ = _make_image_rec(tmp_path)

    def labels_of(it):
        out = []
        for batch in it:
            n = batch.data[0].shape[0] - batch.pad
            out.extend(batch.label[0].asnumpy()[:n].astype(int).tolist())
        return out

    it1 = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                                batch_size=4, shuffle=True, seed=7)
    it2 = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                                batch_size=4, shuffle=True, seed=7)
    e1a = labels_of(it1)
    it1.reset()
    e1b = labels_of(it1)
    assert sorted(e1a) == list(range(11))
    assert e1a != list(range(11))          # actually shuffled
    assert e1b != e1a                      # reshuffled across epochs
    assert labels_of(it2) == e1a           # same seed → same stream


# ------------------------------------- the batch as the decoders leave it

def _uint8_batches(path, threads, rand, epochs=2, **kw):
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
        shuffle=False, rand_crop=rand, rand_mirror=rand, seed=3,
        preprocess_threads=threads, mean_r=123.68, std_r=58.395, **kw)
    assert it._native is not None, "native pipeline should engage here"
    out = []
    for _ in range(epochs):
        for batch in it:
            owed = batch.deferred[0]
            assert owed.pixels.dtype == np.uint8
            assert owed.pixels.shape == (4, 3, 32, 32)
            out.append((owed.pixels.copy(), batch.label[0].asnumpy(),
                        batch.pad))
        it.reset()
    return out


def _is_crop_of(chw, img, mirrored_too):
    """Is ``chw`` bit for bit a 32 x 32 window of ``img`` (HWC), as it
    lies or mirrored?"""
    want = [chw.transpose(1, 2, 0)]
    if mirrored_too:
        want.append(want[0][:, ::-1])
    h, w = img.shape[:2]
    return any((img[y:y + 32, x:x + 32] == t).all()
               for t in want
               for y in range(h - 32 + 1) for x in range(w - 32 + 1))


@pytest.mark.parametrize("threads,rand", [(1, False), (1, True), (3, True),
                                          (8, True)])
def test_native_uint8_batch_vs_numpy_oracle(tmp_path, threads, rand):
    """What leaves the decoders is the source's own pixels: the centre
    window bit for bit, or under random crops and mirrors some window of
    the source as it lies or mirrored; and the same batches whatever the
    number of threads, epoch after epoch."""
    path, imgs = _make_image_rec(tmp_path)
    got = _uint8_batches(path, threads, rand)
    assert len(got) == 6                       # 11 images, 4 a batch, twice
    for e in range(2):
        i = 0
        for pixels, labels, pad in got[3 * e:3 * e + 3]:
            for k in range(4 - pad):
                img = imgs[i]
                assert labels[k] == float(i)
                if rand:
                    assert _is_crop_of(pixels[k], img, True), (e, i)
                else:
                    h, w = img.shape[:2]
                    y0, x0 = (h - 32) // 2, (w - 32) // 2
                    np.testing.assert_array_equal(
                        pixels[k],
                        img[y0:y0 + 32, x0:x0 + 32].transpose(2, 0, 1))
                i += 1
            for k in range(4 - pad, 4):        # pad repeats the last one
                np.testing.assert_array_equal(pixels[k], pixels[3 - pad])
        assert i == len(imgs)
    if rand:
        # a new epoch draws new windows
        assert any((a[0] != b[0]).any() for a, b in zip(got[:3], got[3:]))
    one = got if threads == 1 else _uint8_batches(path, 1, rand)
    for (pa, la, pada), (pb, lb, padb) in zip(got, one):
        np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(la, lb)
        assert pada == padb


# sha256 (first 16 hex digits) of every float32 batch of two epochs as the
# parent's native float path wrote them (commit c30a798, this file's
# _make_image_rec, the arguments below): the uint8 batch through the host
# finish gives the same bits
_PARENT_FLOAT_PATH = {
    "plain": ["60da610b872a83a2", "ff72761c82484c05", "d7db5d52beb05f4e",
              "bebdf49cdfe9630f", "36021ff30c71df35", "15bb133a4815a505"],
    "resize": ["fde3c48a9945b56c", "c4a79a4aeea01b72", "291e5778e344135a",
               "0f0793bb9c036bbe", "f5d19dbc79323fc3", "fce71a8b6ac9cf38"],
    "scale": ["fb030eaa7ee96217", "e364fd7139a6d90d", "8d96a0e3ecded744",
              "908eedff4631c5f8", "caf45ce0a5a6de5d", "9cc1bb284367b88a"],
}


@pytest.mark.parametrize("case,kw", [("plain", {}), ("resize", {"resize": 36}),
                                     ("scale", {"scale": 1 / 255.})])
def test_host_finish_is_the_parents_float_path(tmp_path, case, kw):
    import hashlib
    path, _ = _make_image_rec(tmp_path)
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
        shuffle=True, rand_crop=True, rand_mirror=True, seed=5,
        mean_r=123.68, mean_g=116.28, mean_b=103.53, std_r=58.395,
        std_g=57.12, std_b=57.375, **kw)
    assert it._native is not None
    got = []
    for _ in range(2):
        for batch in it:
            data = batch.data[0].asnumpy()
            assert data.dtype == np.float32
            got.append(hashlib.sha256(data.tobytes()).hexdigest()[:16])
        it.reset()
    assert got == _PARENT_FLOAT_PATH[case]


# ------------------------------------------------------- the library's ABI

@pytest.mark.parametrize("stale", ["another_number", "no_number"])
def test_library_of_another_abi_is_rebuilt_not_loaded(tmp_path, stale):
    """``libmxnative.so`` is built on each machine by an mtime check; one
    that is new enough and yet answers another ABI number (or, built
    before there was one, none) would take the new MXPipeConfig for its
    own. ``_load`` rebuilds it from the sources beside it."""
    import shutil
    import subprocess
    src_dir = os.path.dirname(native.__file__)
    for name in native._DEPS:
        shutil.copy(os.path.join(src_dir, name), tmp_path / name)
    so = str(tmp_path / "libmxnative.so")
    body = {"another_number":
            'extern "C" int mxnative_abi(void) { return %d; }\n'
            % (native._ABI + 1),
            "no_number": 'extern "C" int mxpipe_next(void) { return 7; }\n'}
    (tmp_path / "stale.cc").write_text(body[stale])
    subprocess.run(["g++", "-shared", "-fPIC", "-o", so,
                    str(tmp_path / "stale.cc")], check=True)
    later = os.path.getmtime(so) + 60
    os.utime(so, (later, later))               # newer than every source
    assert native._build(str(tmp_path))        # the mtime check is content
    stale_bytes = open(so, "rb").read()
    lib = native._load(str(tmp_path))
    assert lib is not None
    assert native._abi_of(lib) == native._ABI
    assert open(so, "rb").read() != stale_bytes
    assert lib.mxrio_open(b"/nonexistent") is None    # bound, and its own
