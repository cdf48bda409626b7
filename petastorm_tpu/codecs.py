"""Column codecs: encode tensor/scalar fields into Parquet-storable values and back.

Capability parity with the reference codec set (petastorm/codecs.py: ``DataframeColumnCodec``
~L30, ``ScalarCodec`` ~L60, ``NdarrayCodec`` ~L130, ``CompressedNdarrayCodec`` ~L170,
``CompressedImageCodec`` ~L200), redesigned for a TPU pipeline:

- The storage half (``encode``/``decode``) is host-side and Spark-free: codecs speak numpy and
  pyarrow types; Spark types are derived on demand (``spark_dtype`` needs pyspark only when
  actually writing through Spark).
- Codecs that admit an on-device decode path advertise it via ``device_decodable`` — the JAX
  loader batches the *encoded* bytes to the host staging area and runs the heavy half of the
  decode (dequant+IDCT+color for JPEG) as a Pallas kernel instead of per-row cv2 calls
  (see petastorm_tpu/ops/jpeg.py). ``decode`` always remains available as the portable path.
"""
from __future__ import annotations

import io
import time
import zlib

import numpy as np

from petastorm_tpu import types as ptypes
from petastorm_tpu.trace import tally


class DataframeColumnCodec:
    """Base codec contract (reference: petastorm/codecs.py ~L30)."""

    #: True when ops/ has a Pallas decode kernel for this codec's payload.
    device_decodable = False

    def encode(self, unischema_field, value):
        """Encode ``value`` into a Parquet-storable python value (scalar or bytes)."""
        raise NotImplementedError

    def decode(self, unischema_field, encoded):
        """Decode a stored value back into the numpy value declared by the field."""
        raise NotImplementedError

    def host_stage_decode(self, unischema_field, encoded):
        """On-device decode path, host half: stored value → staging object the reader
        pool produces in parallel (e.g. JPEG entropy decode → coefficient planes).
        Only meaningful when :attr:`device_decodable` is True."""
        raise NotImplementedError(
            "%s does not support on-device decode" % type(self).__name__
        )

    def device_decode_batch(self, unischema_field, staged, resize_to=None,
                            sharding=None, boxes=None):
        """On-device decode path, device half: list of staging objects (one per row) →
        one batched device array matching :meth:`decode`'s per-row output contract.
        ``resize_to=(h, w)`` (image codecs) asks for an on-device resize to one
        static shape so mixed-size stores can batch; ``boxes`` (``(n, 5)`` int32,
        :mod:`petastorm_tpu.ops.crop`) crops each row to its box first.
        ``sharding`` (optional batch-axis sharding) asks the decode to run SPMD — one
        batch shard per device."""
        raise NotImplementedError(
            "%s does not support on-device decode" % type(self).__name__
        )

    def arrow_dtype(self, unischema_field=None):
        """pyarrow storage type for this codec's column."""
        raise NotImplementedError

    def spark_dtype(self):
        """pyspark storage type (requires pyspark; only needed on the Spark write path)."""
        raise NotImplementedError

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash(type(self).__name__)


class ScalarCodec(DataframeColumnCodec):
    """Stores a scalar in a typed Parquet column (reference: petastorm/codecs.py ~L60).

    Accepts either a :mod:`petastorm_tpu.types` tag or (when pyspark is installed) a
    ``pyspark.sql.types`` instance, which is converted to the equivalent tag.
    """

    def __init__(self, scalar_type):
        if not isinstance(scalar_type, ptypes.ScalarType):
            scalar_type = _tag_from_spark_type(scalar_type)
        self._scalar_type = scalar_type

    @property
    def scalar_type(self):
        return self._scalar_type

    def encode(self, unischema_field, value):
        if isinstance(value, np.ndarray):
            if value.ndim != 0 and value.size != 1:
                raise ValueError(
                    "Expected a scalar for field %r, got array of shape %r"
                    % (unischema_field.name, value.shape)
                )
            value = value.reshape(())[()]
        t = self._scalar_type
        if isinstance(t, (ptypes.StringType,)):
            return str(value)
        if isinstance(t, ptypes.BinaryType):
            return bytes(value)
        if isinstance(t, ptypes.BooleanType):
            return bool(value)
        if isinstance(t, ptypes.DecimalType):
            import decimal

            return decimal.Decimal(str(value))
        if isinstance(t, (ptypes.DateType, ptypes.TimestampType)):
            return value
        np_dtype = t.to_numpy_dtype()
        if np_dtype.kind in "iu":
            return int(value)
        if np_dtype.kind == "f":
            return float(value)
        return value

    def decode(self, unischema_field, encoded):
        import decimal

        if isinstance(self._scalar_type, ptypes.DecimalType) or isinstance(
            encoded, decimal.Decimal
        ):
            # Reference keeps Decimal as decimal.Decimal on decode (petastorm/codecs.py ~L110)
            return decimal.Decimal(encoded) if not isinstance(encoded, decimal.Decimal) else encoded
        np_dtype = np.dtype(unischema_field.numpy_dtype)
        if np_dtype.kind in ("U", "S", "O"):
            return encoded
        return np_dtype.type(encoded)

    def arrow_dtype(self, unischema_field=None):
        return self._scalar_type.arrow_type()

    def spark_dtype(self):
        return self._scalar_type.spark_type()

    def __setstate__(self, state):
        # Reference ScalarCodec pickles as {'_spark_type': <pyspark type>} (petastorm/codecs.py
        # ~L60); the compat unpickler maps pyspark type classes onto our tags already.
        if "_spark_type" in state and "_scalar_type" not in state:
            spark_type = state["_spark_type"]
            if isinstance(spark_type, ptypes.ScalarType):
                self._scalar_type = spark_type
            else:
                self._scalar_type = _tag_from_spark_type(spark_type)
        else:
            self.__dict__.update(state)

    def __repr__(self):
        return "ScalarCodec(%r)" % (self._scalar_type,)

    def __eq__(self, other):
        return type(self) is type(other) and self._scalar_type == other._scalar_type

    def __hash__(self):
        return hash((type(self).__name__, self._scalar_type))


class NdarrayCodec(DataframeColumnCodec):
    """Stores an ndarray as npy bytes in a binary column (reference: petastorm/codecs.py ~L130)."""

    def encode(self, unischema_field, value):
        expected = np.dtype(unischema_field.numpy_dtype)
        if not isinstance(value, np.ndarray):
            raise ValueError(
                "Expected numpy ndarray for field %r, got %r"
                % (unischema_field.name, type(value))
            )
        if value.dtype != expected:
            raise ValueError(
                "Field %r expected dtype %r, got %r"
                % (unischema_field.name, expected, value.dtype)
            )
        _check_shape(unischema_field, value)
        memfile = io.BytesIO()
        # allow_pickle=False so object-dtype arrays fail fast at write time instead of
        # becoming unreadable at decode time (decode also forbids pickle).
        np.save(memfile, value, allow_pickle=False)
        return bytearray(memfile.getvalue())

    def decode(self, unischema_field, encoded):
        memfile = io.BytesIO(encoded)
        return np.load(memfile, allow_pickle=False)

    def arrow_dtype(self, unischema_field=None):
        import pyarrow as pa

        return pa.binary()

    def spark_dtype(self):
        import pyspark.sql.types as T

        return T.BinaryType()


class CompressedNdarrayCodec(DataframeColumnCodec):
    """npy bytes + zlib (reference: petastorm/codecs.py ~L170)."""

    def encode(self, unischema_field, value):
        raw = NdarrayCodec().encode(unischema_field, value)
        return bytearray(zlib.compress(bytes(raw)))

    def decode(self, unischema_field, encoded):
        return NdarrayCodec().decode(unischema_field, zlib.decompress(encoded))

    def arrow_dtype(self, unischema_field=None):
        import pyarrow as pa

        return pa.binary()

    def spark_dtype(self):
        import pyspark.sql.types as T

        return T.BinaryType()


class CompressedImageCodec(DataframeColumnCodec):
    """PNG/JPEG image bytes (reference: petastorm/codecs.py ~L200, cv2 imencode/imdecode).

    TPU note: for ``jpeg`` payloads the loader can route decode through the two-stage path —
    host entropy decode to quantized DCT coefficients, then a Pallas dequant+IDCT+upsample+YCbCr
    kernel on device (petastorm_tpu/ops/jpeg.py). ``decode`` here is the portable host path.
    """

    def __init__(self, image_codec="png", quality=80):
        if image_codec not in ("png", "jpeg", "jpg"):
            raise ValueError("Unsupported image codec %r" % image_codec)
        self._image_codec = "jpeg" if image_codec == "jpg" else image_codec
        self._quality = int(quality)

    @property
    def image_codec(self):
        return self._image_codec

    @property
    def device_decodable(self):
        return self._image_codec == "jpeg"

    def encode(self, unischema_field, value):
        if not isinstance(value, np.ndarray):
            raise ValueError("Expected ndarray image for field %r" % unischema_field.name)
        if np.dtype(unischema_field.numpy_dtype) != value.dtype:
            raise ValueError(
                "Field %r expected dtype %r, got %r"
                % (unischema_field.name, unischema_field.numpy_dtype, value.dtype)
            )
        _check_shape(unischema_field, value)
        import cv2

        if self._image_codec == "png":
            ok, contents = cv2.imencode(".png", value)
        else:
            ok, contents = cv2.imencode(
                ".jpeg", value, [int(cv2.IMWRITE_JPEG_QUALITY), self._quality]
            )
        if not ok:
            raise ValueError("cv2.imencode failed for field %r" % unischema_field.name)
        return bytearray(contents.tobytes())

    def decode(self, unischema_field, encoded):
        import cv2

        from petastorm_tpu.errors import DecodeFieldError

        # np.frombuffer reads bytes/bytearray/memoryview alike — no intermediate copy
        img = cv2.imdecode(np.frombuffer(encoded, dtype=np.uint8), cv2.IMREAD_UNCHANGED)
        if img is None:
            raise DecodeFieldError(
                "cv2.imdecode failed for field %r (stream is corrupt or uses a JPEG "
                "family cv2 does not support, e.g. lossless)" % unischema_field.name)
        return img.astype(np.dtype(unischema_field.numpy_dtype), copy=False)

    def host_stage_decode(self, unischema_field, encoded):
        """JPEG bytes → quantized DCT coefficient planes (native C++ entropy decode,
        GIL-released — the reader pool's parallel half of the two-stage decode).

        Streams the two-stage path cannot handle (lossless/arithmetic, CMYK, corrupt-for-us)
        fall back to the full host decode per row; the loader stacks those alongside
        the device-decoded rows. Counts one image and this thread's CPU time in the
        process-wide stage-1 totals (``trace.tally``)."""
        if not self.device_decodable:
            raise NotImplementedError("on-device decode is only available for jpeg")
        t0 = time.thread_time_ns()
        try:
            return self._stage_one(unischema_field, encoded)
        finally:
            tally(stage1_images=1, stage1_ns=time.thread_time_ns() - t0)

    def _stage_one(self, unischema_field, encoded):
        from petastorm_tpu.errors import DecodeFieldError
        from petastorm_tpu.ops.jpeg import entropy_decode_jpeg_fast

        try:
            return entropy_decode_jpeg_fast(encoded)
        except ValueError as stage_err:
            try:
                return self.decode(unischema_field, encoded)
            except DecodeFieldError as host_err:
                # neither path can decode this stream (e.g. lossless or
                # arithmetic-coded JPEG): surface ONE error naming the field and
                # both failures instead of an opaque cv2 message from the pool
                raise DecodeFieldError(
                    "Field %r: stream is decodable by neither the two-stage device "
                    "path (%s) nor host cv2 (%s)"
                    % (unischema_field.name, stage_err, host_err)) from host_err

    def host_stage_decode_batch(self, unischema_field, values):
        """Sequence of encoded blobs (``None`` entries preserved) → list of staging
        payloads, one native call per row group when possible.

        A row group whose frame headers give one size takes the batched stage 1
        (petastorm_tpu/ops/jpeg.py ``entropy_decode_jpeg_batch``): every same-layout
        stream entropy-decodes into stacked buffers in one GIL-released native call;
        streams it cannot handle (lossless/arithmetic, corrupt, layout differs from
        the group) fall back to :meth:`host_stage_decode` individually, so the output
        mixes ``JpegPlanes`` and host-decoded ndarrays exactly like the per-row path.

        A row group of several sizes stages image by image, its work shared with the
        pool's other workers (``stage_images``), and an image no canvas of the
        mixed-size device decode holds (:func:`petastorm_tpu.ops.jpeg.canvas_holds`)
        decodes here with cv2 instead, so that the rare giant does not compile a
        program of its own size on the device."""
        if not self.device_decodable:
            raise NotImplementedError("on-device decode is only available for jpeg")
        idx = [i for i, v in enumerate(values) if v is not None]
        out = [None] * len(values)
        if not idx:
            return out
        blobs = [bytes(values[i]) for i in idx]
        from petastorm_tpu.ops.jpeg import (canvas_holds, entropy_decode_jpeg_batch,
                                            jpeg_size, stage_images)
        from petastorm_tpu.trace import profiler_span

        sizes = [jpeg_size(b) for b in blobs]
        if len(set(sizes) - {None}) > 1:
            def stage(job):
                blob, size = job
                if size is not None and not canvas_holds(size):
                    return self.decode(unischema_field, blob)
                return self.host_stage_decode(unischema_field, blob)

            with profiler_span("ptpu.pool.stage1", rows=len(blobs)):
                staged = stage_images(stage, list(zip(blobs, sizes)))
            for j, i in enumerate(idx):
                out[i] = staged[j]
            return out
        t0 = time.thread_time_ns()
        try:
            with profiler_span("ptpu.pool.stage1", rows=len(blobs)):
                planes = entropy_decode_jpeg_batch(blobs)
        except (ValueError, RuntimeError):
            planes = [None] * len(idx)
        # the rows it could not decode count where they are decoded again, one by one
        tally(stage1_images=sum(p is not None for p in planes),
              stage1_ns=time.thread_time_ns() - t0)
        for j, i in enumerate(idx):
            p = planes[j]
            out[i] = p if p is not None \
                else self.host_stage_decode(unischema_field, blobs[j])
        return out

    def device_decode_batch(self, unischema_field, staged, resize_to=None,
                            sharding=None, boxes=None):
        """Coefficient planes (one per row) → (n, ...) uint8 device array, one batched
        Pallas dispatch. Matches :meth:`decode`'s per-row contract: cv2 returns images
        in stored (BGR for color) channel order and 2-D for grayscale fields, so the
        RGB device output is flipped / channel-stripped accordingly. Rows that fell
        back to host decode in :meth:`host_stage_decode` arrive as ndarrays and are
        merged in at their original positions.

        ``resize_to=(h, w)`` enables mixed-size stores: the rows decode in canvas
        buckets and are resized on the device
        (:func:`petastorm_tpu.ops.jpeg.decode_jpeg_crop_batch`), each inside its
        ``boxes`` row (``y0, x0, h, w, flip``) when given, else whole. Host
        fallbacks (among them, in a row group of several sizes, the images no
        canvas holds) take ``cv2.resize`` INTER_LINEAR of the same box: the
        matching sampling.

        ``sharding``: optional batch-axis sharding; the coefficient slabs are placed
        across its devices before the stage-2 jit so decode runs SPMD (one batch
        shard per device) instead of serializing on the default device. The canvas
        decode runs on one device."""
        if not self.device_decodable:
            raise NotImplementedError("on-device decode is only available for jpeg")
        import jax.numpy as jnp

        from petastorm_tpu.ops.jpeg import (JpegPlanes, decode_jpeg_batch,
                                            decode_jpeg_crop_batch, to_stored_order)

        staged = list(staged)
        plane_idx = [i for i, s in enumerate(staged) if isinstance(s, JpegPlanes)]
        host_idx = [i for i in range(len(staged)) if i not in set(plane_idx)]
        shape = unischema_field.shape
        grayscale = shape is not None and len(shape) == 2
        if resize_to is not None:
            from petastorm_tpu.ops.crop import whole_boxes

            size = (int(resize_to[0]), int(resize_to[1]))
            if boxes is None:
                boxes = whole_boxes([(s.height, s.width) if isinstance(s, JpegPlanes)
                                     else s.shape[:2] for s in staged])

        parts = []
        order = []
        if plane_idx and resize_to is None:
            img = decode_jpeg_batch([staged[i] for i in plane_idx], sharding=sharding)
            parts.append(to_stored_order(img, grayscale))
            order.extend(plane_idx)
        elif plane_idx:
            parts.append(decode_jpeg_crop_batch(
                [staged[i] for i in plane_idx], boxes[plane_idx], size, grayscale))
            order.extend(plane_idx)
        if host_idx:
            from petastorm_tpu.obs.log import degradation
            from petastorm_tpu.trace import count

            count("decode_host_rows", len(host_idx))
            degradation("jpeg_host_fallback",
                        "%d of %d rows of field %r were decoded on host (stage 1 "
                        "could not take them, or no canvas holds them); the device "
                        "decode was skipped for those rows", len(host_idx), len(staged),
                        unischema_field.name)
            # host-decoded fallbacks are already in stored order; no flip
            fallbacks = [staged[i] for i in host_idx]
            if resize_to is not None:
                from petastorm_tpu.ops.crop import crop_resize_host

                fallbacks = [crop_resize_host(f, boxes[i], size)
                             for f, i in zip(fallbacks, host_idx)]
            parts.append(jnp.asarray(np.stack(fallbacks)))
            order.extend(host_idx)
        if len(parts) == 1:
            out = parts[0]
        else:
            out = jnp.concatenate(parts, axis=0)
        inverse = np.argsort(np.asarray(order))
        if not np.array_equal(inverse, np.arange(len(staged))):
            out = out[jnp.asarray(inverse)]
        return out

    def arrow_dtype(self, unischema_field=None):
        import pyarrow as pa

        return pa.binary()

    def spark_dtype(self):
        import pyspark.sql.types as T

        return T.BinaryType()

    def __setstate__(self, state):
        # Reference CompressedImageCodec stores the cv2 extension string ('.png'/'.jpeg',
        # petastorm/codecs.py ~L200); normalize on unpickle.
        codec = state.get("_image_codec", "png").lstrip(".")
        codec = "jpeg" if codec == "jpg" else codec
        if codec not in ("png", "jpeg"):
            raise ValueError("Unsupported image codec %r in pickled state" % codec)
        self._image_codec = codec
        self._quality = int(state.get("_quality", 80))

    def __repr__(self):
        return "CompressedImageCodec(%r, quality=%d)" % (self._image_codec, self._quality)


def _check_shape(unischema_field, value):
    shape = unischema_field.shape
    if shape is None:
        return
    if len(shape) != value.ndim:
        raise ValueError(
            "Field %r declares rank %d, got array rank %d"
            % (unischema_field.name, len(shape), value.ndim)
        )
    for declared, actual in zip(shape, value.shape):
        if declared is not None and declared != actual:
            raise ValueError(
                "Field %r declares shape %r, got %r"
                % (unischema_field.name, shape, value.shape)
            )


def _tag_from_spark_type(spark_type):
    """Map a pyspark.sql.types instance onto our ScalarType tag (pyspark optional)."""
    name = type(spark_type).__name__
    if name == "DecimalType":
        return ptypes.DecimalType(spark_type.precision, spark_type.scale)
    tag_cls = getattr(ptypes, name, None)
    if tag_cls is None or not issubclass(tag_cls, ptypes.ScalarType):
        raise ValueError("Unsupported scalar type %r" % (spark_type,))
    return tag_cls()
