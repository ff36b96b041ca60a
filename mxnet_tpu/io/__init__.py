"""mx.io — data iterators (reference: python/mxnet/io.py + src/io/)."""
from .io import (DataDesc, DataBatch, DeferredImages, DataIter, NDArrayIter,
                 ResizeIter, PrefetchingIter, CSVIter, MNISTIter)
from .image_record import ImageRecordIter, ImageRecordUInt8Iter

__all__ = ["DataDesc", "DataBatch", "DeferredImages", "DataIter",
           "NDArrayIter", "ResizeIter", "PrefetchingIter", "CSVIter",
           "MNISTIter", "ImageRecordIter", "ImageRecordUInt8Iter",
           "ImageDetRecordIter"]


def __getattr__(name):
    # lazy: mx.image imports mx.io, so the reverse edge must not be eager
    if name == "ImageDetRecordIter":
        from ..image.detection import ImageDetRecordIter
        return ImageDetRecordIter
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
