"""Image types and transformers (reference ``$B/dataset/image/``: 23 files).

Images are numpy (H, W, C) float32 channels-last throughout — the TPU layout —
labelled by a 1-based float class (Torch convention), mirroring the
reference's ``LabeledBGRImage``/``LabeledGreyImage`` (``dataset/image/Types.scala``).
Decode (JPEG etc.) is handled by ``LocalImgReader`` via Pillow when available;
the tensor-side transformers below are pure numpy and are the ones on the
training hot path.
"""

from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

from bigdl_tpu.dataset.base import ByteRecord, MiniBatch, Sample, Transformer
from bigdl_tpu.utils.rng import RandomGenerator


class LabeledImage:
    """(H, W, C) float image + 1-based label (reference ``Types.scala``)."""

    __slots__ = ("data", "label")

    def __init__(self, data: np.ndarray, label: float):
        self.data = np.asarray(data, np.float32)
        self.label = float(label)

    @property
    def shape(self):
        return self.data.shape


LabeledGreyImage = LabeledImage
LabeledBGRImage = LabeledImage


class BytesToGreyImg(Transformer[ByteRecord, LabeledImage]):
    """Decode row-major grey bytes (reference ``BytesToGreyImg``)."""

    def __init__(self, row: int, col: int):
        self.row, self.col = row, col

    def __call__(self, prev: Iterator[ByteRecord]) -> Iterator[LabeledImage]:
        for rec in prev:
            img = np.frombuffer(rec.data, np.uint8).astype(np.float32)
            yield LabeledImage(img.reshape(self.row, self.col, 1), rec.label)


class BytesToBGRImg(Transformer[ByteRecord, LabeledImage]):
    """Decode interleaved BGR bytes (reference ``BytesToBGRImg``)."""

    def __init__(self, row: int, col: int):
        self.row, self.col = row, col

    def __call__(self, prev: Iterator[ByteRecord]) -> Iterator[LabeledImage]:
        for rec in prev:
            img = np.frombuffer(rec.data, np.uint8).astype(np.float32)
            yield LabeledImage(img.reshape(self.row, self.col, 3), rec.label)


class GreyImgNormalizer(Transformer[LabeledImage, LabeledImage]):
    """(x - mean) / std with dataset-level stats
    (reference ``GreyImgNormalizer``)."""

    def __init__(self, mean: float, std: float):
        self.mean, self.std = mean, std

    @staticmethod
    def from_dataset(dataset) -> "GreyImgNormalizer":
        total, sq, n = 0.0, 0.0, 0
        for img in dataset.data(train=False):
            total += float(img.data.sum())
            sq += float((img.data ** 2).sum())
            n += img.data.size
        mean = total / n
        std = float(np.sqrt(sq / n - mean * mean))
        return GreyImgNormalizer(mean, std)

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        for img in prev:
            yield LabeledImage((img.data - self.mean) / self.std, img.label)


class BGRImgNormalizer(Transformer[LabeledImage, LabeledImage]):
    """Per-channel normalization (reference ``BGRImgNormalizer``)."""

    def __init__(self, mean: Tuple[float, float, float],
                 std: Tuple[float, float, float]):
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        for img in prev:
            yield LabeledImage((img.data - self.mean) / self.std, img.label)


class BGRImgCropper(Transformer[LabeledImage, LabeledImage]):
    """Center/random crop (reference ``BGRImgCropper``)."""

    def __init__(self, crop_width: int, crop_height: int, random: bool = True):
        self.cw, self.ch, self.random = crop_width, crop_height, random
        self.stochastic = random

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        rng = RandomGenerator.RNG()
        for img in prev:
            h, w = img.data.shape[:2]
            if self.random:
                y = int(rng.uniform(0, max(1, h - self.ch + 1)))
                x = int(rng.uniform(0, max(1, w - self.cw + 1)))
            else:
                y, x = (h - self.ch) // 2, (w - self.cw) // 2
            yield LabeledImage(img.data[y:y + self.ch, x:x + self.cw], img.label)


class BGRImgRdmCropper(BGRImgCropper):
    """Random crop with zero padding (reference ``BGRImgRdmCropper``)."""

    def __init__(self, crop_width: int, crop_height: int, padding: int = 0):
        super().__init__(crop_width, crop_height, random=True)
        self.padding = padding

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        def padded():
            for img in prev:
                if self.padding:
                    d = np.pad(img.data, ((self.padding, self.padding),
                                          (self.padding, self.padding), (0, 0)))
                    yield LabeledImage(d, img.label)
                else:
                    yield img

        return super().__call__(padded())


class HFlip(Transformer[LabeledImage, LabeledImage]):
    """Random horizontal flip (reference ``HFlip``)."""

    stochastic = True

    def __init__(self, threshold: float = 0.5):
        self.threshold = threshold

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        rng = RandomGenerator.RNG()
        for img in prev:
            if rng.uniform() < self.threshold:
                yield LabeledImage(img.data[:, ::-1], img.label)
            else:
                yield img


class ColorJitter(Transformer[LabeledImage, LabeledImage]):
    """Random brightness/contrast/saturation (reference ``ColorJitter``)."""

    stochastic = True

    def __init__(self, brightness: float = 0.4, contrast: float = 0.4,
                 saturation: float = 0.4):
        self.brightness, self.contrast, self.saturation = brightness, contrast, saturation

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        rng = RandomGenerator.RNG()
        for img in prev:
            d = img.data
            order = [0, 1, 2]
            rng.shuffle(order)
            for op in order:
                if op == 0 and self.brightness:
                    alpha = 1.0 + float(rng.uniform(-self.brightness, self.brightness))
                    d = d * alpha
                elif op == 1 and self.contrast:
                    alpha = 1.0 + float(rng.uniform(-self.contrast, self.contrast))
                    grey_mean = d.mean()
                    d = d * alpha + grey_mean * (1 - alpha)
                elif op == 2 and self.saturation:
                    alpha = 1.0 + float(rng.uniform(-self.saturation, self.saturation))
                    grey = d.mean(axis=2, keepdims=True)
                    d = d * alpha + grey * (1 - alpha)
            yield LabeledImage(d, img.label)


class Lighting(Transformer[LabeledImage, LabeledImage]):
    """AlexNet PCA-noise lighting (reference ``Lighting``)."""

    stochastic = True

    EIGVAL = np.asarray([0.2175, 0.0188, 0.0045], np.float32)
    EIGVEC = np.asarray([[-0.5675, 0.7192, 0.4009],
                         [-0.5808, -0.0045, -0.8140],
                         [-0.5836, -0.6948, 0.4203]], np.float32)

    def __init__(self, alphastd: float = 0.1):
        self.alphastd = alphastd

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        rng = RandomGenerator.RNG()
        for img in prev:
            alpha = rng.normal(0.0, self.alphastd, (3,)).astype(np.float32)
            delta = (self.EIGVEC * alpha * self.EIGVAL).sum(axis=1)
            yield LabeledImage(img.data + delta, img.label)


class _ImgToBatch(Transformer[LabeledImage, MiniBatch]):
    aggregating = True

    def __init__(self, batch_size: int, drop_remainder: bool = True):
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[MiniBatch]:
        feats, labels = [], []
        for img in prev:
            feats.append(img.data)
            labels.append(img.label)
            if len(feats) == self.batch_size:
                yield MiniBatch(np.stack(feats), np.asarray(labels, np.float32))
                feats, labels = [], []
        if feats and not self.drop_remainder:
            yield MiniBatch(np.stack(feats), np.asarray(labels, np.float32))


class GreyImgToBatch(_ImgToBatch):
    """reference ``GreyImgToBatch``."""


class BGRImgToBatch(_ImgToBatch):
    """reference ``BGRImgToBatch`` (also covering the multithreaded
    ``MTLabeledBGRImgToBatch`` — host threading lives in Engine.io_pool-based
    prefetch, not in the transformer)."""


class GreyImgToSample(Transformer[LabeledImage, Sample]):
    """reference ``GreyImgToSample``."""

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[Sample]:
        for img in prev:
            yield Sample(img.data, np.float32(img.label))


class BGRImgToSample(GreyImgToSample):
    """reference ``BGRImgToSample``."""


def _decode_scaled_bgr(source, scale_to: int, who: str) -> np.ndarray:
    """Shared PIL decode: RGB convert, short side to ``scale_to``, RGB->BGR
    float32 (the reference's BGR convention)."""
    try:
        from PIL import Image as PILImage
    except ImportError as e:  # pragma: no cover
        raise RuntimeError(f"{who} requires Pillow") from e
    with PILImage.open(source) as im:
        im = im.convert("RGB")
        w, h = im.size
        if min(w, h) != scale_to:
            if w < h:
                im = im.resize((scale_to, int(h * scale_to / w)))
            else:
                im = im.resize((int(w * scale_to / h), scale_to))
        return np.asarray(im, np.float32)[:, :, ::-1]


class EncodedBytesToBGRImg(Transformer[ByteRecord, LabeledImage]):
    """Decode encoded (JPEG/PNG/...) bytes to a scaled BGR image — the
    shard-ingest decode stage (reference seq-file path:
    ``LocalSeqFileToBytes`` -> decode; scaling rule as ``LocalImgReader``:
    short side to ``scale_to``). Requires Pillow."""

    def __init__(self, scale_to: int = 256):
        self.scale_to = scale_to

    def __call__(self, prev: Iterator[ByteRecord]) -> Iterator[LabeledImage]:
        import io
        for rec in prev:
            arr = _decode_scaled_bgr(io.BytesIO(rec.data), self.scale_to,
                                     type(self).__name__)
            yield LabeledImage(arr, rec.label)


class LocalImgReader(Transformer[Tuple[str, float], LabeledImage]):
    """Read + scale image files from disk (reference ``LocalImgReader``).
    Items are (path, label). Requires Pillow; raises cleanly otherwise."""

    def __init__(self, scale_to: int = 256):
        self.scale_to = scale_to

    def __call__(self, prev: Iterator[Tuple[str, float]]) -> Iterator[LabeledImage]:
        for path, label in prev:
            yield LabeledImage(
                _decode_scaled_bgr(path, self.scale_to, type(self).__name__),
                label)


IMAGE_EXTENSIONS = (".jpg", ".jpeg", ".png", ".bmp", ".gif", ".webp",
                    ".ppm", ".tif", ".tiff")


# Channel-agnostic crop: the reference's Grey variant is the same operation
# on 1-channel data (``dataset/image/GreyImgCropper.scala``).
GreyImgCropper = BGRImgCropper


class BGRImgPixelNormalizer(Transformer[LabeledImage, LabeledImage]):
    """Subtract a per-pixel mean image (reference
    ``BGRImgPixelNormalizer.scala``: ImageNet mean file); the mean must match
    the image shape."""

    def __init__(self, means: np.ndarray):
        self.means = np.asarray(means, np.float32)

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[LabeledImage]:
        for img in prev:
            if img.data.shape != self.means.shape:
                raise ValueError(f"mean image shape {self.means.shape} != "
                                 f"image shape {img.data.shape}")
            yield LabeledImage(img.data - self.means, img.label)


class MTLabeledBGRImgToBatch(Transformer[LabeledImage, "MiniBatch"]):
    """Multithreaded transform + collate (reference
    ``MTLabeledBGRImgToBatch.scala``: worker threads each run their own
    transformer clone, then batches are assembled). Composed from the
    generic pieces: ``MTTransformer(transformer)`` >> ``BGRImgToBatch``."""

    aggregating = True

    def __init__(self, width: int, height: int, batch_size: int,
                 transformer: Transformer, workers: int = 4):
        from bigdl_tpu.dataset.base import MTTransformer
        self.width, self.height = width, height
        self._chain = (MTTransformer(transformer, workers=workers)
                       >> BGRImgToBatch(batch_size))

    def __call__(self, prev: Iterator[LabeledImage]):
        for batch in self._chain(prev):
            h, w = batch.data.shape[1:3]
            if (h, w) != (self.height, self.width):
                raise ValueError(
                    f"transformed images are {h}x{w}, expected "
                    f"{self.height}x{self.width} (the declared batch "
                    "geometry — add a cropper/resizer to the transformer)")
            yield batch


class NativeBGRBatchDecoder(Transformer[ByteRecord, MiniBatch]):
    """ByteRecord -> MiniBatch in ONE native call per batch: threaded
    u8->f32 decode with the per-channel ``(x - mean) / std`` fused in
    (``native/src/decode.cc`` ``bt_decode_normalize``; numpy whole-batch
    fallback when the toolchain is absent).

    The round-4 gap this closes: the per-record Python path
    (``BytesToBGRImg >> BGRImgNormalizer``) costs ~1 ms/record of
    interpreter + three array passes — 6.7x under the chip's ResNet-50
    demand (PERF.md). The reference's answer was a threaded decode
    pipeline (``dataset/image/MTLabeledBGRImgToBatch.scala``); this is
    its native-batch form.
    """

    aggregating = True

    def __init__(self, row: int, col: int, batch_size: int,
                 mean: Tuple[float, float, float],
                 std: Tuple[float, float, float],
                 workers: int = 4, channels: int = 3,
                 drop_remainder: bool = True,
                 device_normalize: bool = False):
        self.row, self.col, self.channels = row, col, channels
        self.batch_size = batch_size
        self.workers = workers
        self.drop_remainder = drop_remainder
        # device_normalize: emit RAW uint8 batches (4x fewer host->device
        # bytes) and let ``nn.InputNormalize`` cast+normalize ON DEVICE —
        # the TPU-first split when the host->chip link is the ingest
        # bottleneck. The native kernel then has
        # nothing to do; the host path reduces to framing + collation.
        self.device_normalize = device_normalize
        n = 1 if channels == 1 else channels
        self.mean = np.ascontiguousarray(
            np.broadcast_to(np.asarray(mean, np.float32), (n,)))
        self.rstd = np.ascontiguousarray(
            1.0 / np.broadcast_to(np.asarray(std, np.float32), (n,)))

    def _decode(self, raw: np.ndarray, labels) -> MiniBatch:
        import ctypes

        from bigdl_tpu import native
        n = raw.shape[0]
        rec_len = raw.shape[1]
        if self.device_normalize:
            shape = ((n, self.row, self.col, self.channels)
                     if self.channels > 1 else (n, self.row, self.col))
            return MiniBatch(raw.reshape(shape).copy(),
                             np.asarray(labels, np.float32))
        lib = native.load()
        if lib is not None:
            out = np.empty((n, rec_len), np.float32)
            fp = ctypes.POINTER(ctypes.c_float)
            lib.bt_decode_normalize(
                raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(n), ctypes.c_int64(rec_len),
                self.mean.ctypes.data_as(fp), self.rstd.ctypes.data_as(fp),
                ctypes.c_int(self.channels), out.ctypes.data_as(fp),
                ctypes.c_int(self.workers))
        else:  # vectorized fallback: still whole-batch, no per-record Python
            out = raw.astype(np.float32).reshape(n, -1, self.channels)
            out = ((out - self.mean) * self.rstd).reshape(n, rec_len)
        shape = ((n, self.row, self.col, self.channels) if self.channels > 1
                 else (n, self.row, self.col))
        return MiniBatch(out.reshape(shape),
                         np.asarray(labels, np.float32))

    def __call__(self, prev: Iterator[ByteRecord]) -> Iterator[MiniBatch]:
        rec_len = self.row * self.col * self.channels
        raw = np.empty((self.batch_size, rec_len), np.uint8)
        labels: list = []
        for rec in prev:
            data = np.frombuffer(rec.data, np.uint8)
            if data.size != rec_len:
                raise ValueError(f"record has {data.size} bytes, expected "
                                 f"{rec_len} ({self.row}x{self.col}x"
                                 f"{self.channels})")
            raw[len(labels)] = data
            labels.append(rec.label)
            if len(labels) == self.batch_size:
                yield self._decode(raw, labels)
                labels = []
        if labels and not self.drop_remainder:
            yield self._decode(raw[:len(labels)], labels)


class BGRImgToImageVector(Transformer[LabeledImage, Sample]):
    """Flatten images to plain feature vectors for the sklearn-protocol
    classifier (reference ``BGRImgToImageVector.scala`` feeds Spark-ML
    DenseVectors to DLClassifier)."""

    def __call__(self, prev: Iterator[LabeledImage]) -> Iterator[Sample]:
        for img in prev:
            yield Sample(np.asarray(img.data, np.float32).ravel(), img.label)


class LocalImgReaderWithName(LocalImgReader):
    """Like LocalImgReader but yields (path, LabeledImage) so predictions
    can be joined back to files (reference
    ``LocalImgReaderWithName.scala``)."""

    def __call__(self, prev: Iterator[Tuple[str, float]]):
        for path, label in prev:
            yield path, LabeledImage(
                _decode_scaled_bgr(path, self.scale_to, type(self).__name__),
                label)


def image_folder_paths(folder: str, extensions=IMAGE_EXTENSIONS):
    """(path, 1-based label) pairs from a labeled image tree — one
    subdirectory per class, labels assigned by sorted class name (reference
    ``DataSet.ImageFolder.paths``, ``dataset/DataSet.scala:319-558``).
    ``extensions=None`` keeps every regular file (generic labeled-tree
    walker, reused by the text pipeline's category loader)."""
    import os
    pairs = []
    classes = sorted(d for d in os.listdir(folder)
                     if os.path.isdir(os.path.join(folder, d)))
    for label, cls in enumerate(classes, start=1):
        cls_dir = os.path.join(folder, cls)
        for name in sorted(os.listdir(cls_dir)):
            p = os.path.join(cls_dir, name)
            if not os.path.isfile(p):
                continue
            if extensions and not name.lower().endswith(extensions):
                continue
            pairs.append((p, float(label)))
    return pairs
