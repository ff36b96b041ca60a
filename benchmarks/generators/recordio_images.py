"""JPEG images from a RecordIO file through ``mx.io.ImageRecordIter``.

Set-up writes the file once under the checkout (``.bench_data/``) and
later runs reuse it. The images are the same for every seed (drawn from
the mix's own ``data_seed``: smooth random fields with a little noise, so
that a JPEG is about as large as a photograph's), which keeps the
decoder's work the same from run to run; ``--seed`` sets the order the
iterator shuffles them into and its random crops and mirrors. The file is
cycled: at its end the iterator is reset and goes on.
"""
import os

import numpy as np


def _write(mx, path, traffic, cfg):
    import cv2
    rng = np.random.RandomState(int(traffic["data_seed"]))
    stored, coarse = int(traffic["stored_size"]), int(traffic["coarse"])
    tmp = path + ".tmp%d" % os.getpid()
    rec = mx.recordio.MXRecordIO(tmp, "w")
    try:
        for i in range(int(traffic["images"])):
            field = rng.randint(0, 256, (coarse, coarse, 3)).astype(np.uint8)
            img = cv2.resize(field, (stored, stored),
                             interpolation=cv2.INTER_CUBIC).astype(np.float32)
            img += rng.normal(0.0, float(traffic["noise"]), img.shape)
            ok, jpg = cv2.imencode(
                ".jpg", np.clip(img, 0, 255).astype(np.uint8),
                [cv2.IMWRITE_JPEG_QUALITY, int(traffic["jpeg_quality"])])
            if not ok:
                raise RuntimeError("cv2 could not encode image %d" % i)
            label = float(rng.randint(0, int(cfg["num_classes"])))
            header = mx.recordio.IRHeader(0, label, i, 0)
            rec.write(mx.recordio.pack(header, jpg.tobytes()))
    finally:
        rec.close()
    os.replace(tmp, path)


def ensure_file(mx, traffic, cfg, root):
    folder = os.path.join(root, ".bench_data")
    os.makedirs(folder, exist_ok=True)
    path = os.path.join(folder, "images-%dx%d-c%d-q%d-s%d-k%d.rec" % (
        traffic["images"], traffic["stored_size"], traffic["coarse"],
        traffic["jpeg_quality"], traffic["data_seed"], cfg["num_classes"]))
    if not os.path.exists(path):
        _write(mx, path, traffic, cfg)
    return path


class _Cycled:
    """The program's iterator, reset at the end of the file."""

    def __init__(self, inner):
        self._inner = inner
        self.batch_size = inner.batch_size
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label

    def next(self):
        try:
            return self._inner.next()
        except StopIteration:
            self._inner.reset()
            return self._inner.next()


def make_iter(mx, traffic, cfg, seed, rows, root):
    size = int(cfg["image_size"])
    norm = traffic["normalize"]
    return _Cycled(mx.io.ImageRecordIter(
        path_imgrec=ensure_file(mx, traffic, cfg, root),
        data_shape=(3, size, size), batch_size=rows, shuffle=True,
        rand_crop=True, rand_mirror=True,
        mean_r=norm["mean"][0], mean_g=norm["mean"][1],
        mean_b=norm["mean"][2], std_r=norm["std"][0],
        std_g=norm["std"][1], std_b=norm["std"][2],
        seed=int(seed) % (2 ** 31 - 1)))


def units_per_batch(traffic, rows):
    return rows
