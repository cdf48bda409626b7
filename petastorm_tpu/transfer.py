"""The transfer stage: one host batch → device arrays.

:class:`DeviceTransfer` runs, on the loader's transfer thread: inflate
(compressed-page pass-through) → decode (two-stage codec dispatch) → split
(device / host-only columns) → stage (pinned slab) → put (the slab in one
transfer where the batch allows, else column by column) → release (the wait,
then the slab and leases back) → layout (a packed batch's segment ids and
positions). :func:`column_sharding` is the one placement rule. The stages open
through the loader's ``stage`` factory, so their spans and sinks stay the
loader's.
"""
from __future__ import annotations

import logging
import math
import os

import numpy as np

from petastorm_tpu.io.lease import count_copy, detach_slab_views, take_leases
from petastorm_tpu.io.staging import watch_borrowed_inputs
from petastorm_tpu.parallel.mesh import batch_axis_shard_count
from petastorm_tpu.trace import phase

logger = logging.getLogger(__name__)

#: the host batch's key for a packed batch's chunk table (``pack=``): popped on
#: the transfer thread, shipped beside the tokens, read by jit_ptpu_pack_layout
_PACK_TABLE = "__pack_table__"


def column_sharding(sharding, name, arr=None):
    """Where column ``name`` goes under the loader's ``sharding``: a per-field
    dict's entry (None for a field it does not name), a ``NamedSharding``
    adapted to ``arr``'s rank (trailing axes replicated), any other sharding
    as it is; None without a sharding. Without ``arr`` the field's sharding is
    returned unadapted (the batch-axis layout a staged decode runs under)."""
    if isinstance(sharding, dict):
        return sharding.get(name)
    if arr is None or sharding is None:
        return sharding
    import jax.sharding as jsh

    if isinstance(sharding, jsh.NamedSharding):
        spec = list(sharding.spec)
        if len(spec) < arr.ndim:
            spec = spec + [None] * (arr.ndim - len(spec))
        elif len(spec) > arr.ndim:
            spec = spec[: arr.ndim]
        return jsh.NamedSharding(sharding.mesh, jsh.PartitionSpec(*spec))
    return sharding


def _replicated_like(sharding):
    """Where a packed batch's chunk table goes: every device of the tokens'
    ``sharding`` (a ``NamedSharding`` or one device)."""
    import jax.sharding as jsh

    if isinstance(sharding, jsh.NamedSharding):
        return jsh.NamedSharding(sharding.mesh, jsh.PartitionSpec())
    if len(sharding.device_set) == 1:
        return sharding
    raise ValueError("pack= lays out batches under a NamedSharding or on one device, "
                     "not under %s" % type(sharding).__name__)


def _row_size(staged):
    """A staged row's image ``(height, width)``: a JPEG's coefficient planes or a
    host-decoded array."""
    if hasattr(staged, "components"):
        return staged.height, staged.width
    return tuple(staged.shape[:2])


def _decode_sharding(s, local_rows):
    """Batch-axis sharding to SPMD-decode staged payloads under.

    Single-process: ``s`` itself (stage 2 consumes its mesh + batch axis;
    trailing axes are replicated per slab inside the decode). Multi-process: a
    global ``NamedSharding`` cannot place host data, so derive a process-LOCAL
    1-D mesh whose device order mirrors ``s``'s local batch-slice order —
    decode output shards then already sit where
    ``make_array_from_process_local_data`` wants them. None when the batch
    axis is unsharded or does not divide: the decode then runs on the default
    device (correct, just unscaled)."""
    import jax
    import jax.sharding as jsh

    if not isinstance(s, jsh.NamedSharding) or not len(s.spec) or s.spec[0] is None:
        return None
    if jax.process_count() == 1:
        return s
    axis = s.spec[0]
    s1 = jsh.NamedSharding(s.mesh, jsh.PartitionSpec(axis))
    global_rows = local_rows * jax.process_count()
    try:
        imap = s1.addressable_devices_indices_map((global_rows,))
    except ValueError:
        return None
    by_start = {}
    for dev, idx in imap.items():
        sl = idx[0]
        start = 0 if sl.start is None else int(sl.start)
        by_start.setdefault(start, dev)  # one device per distinct slice (replicas skip)
    devs = [by_start[k] for k in sorted(by_start)]
    if len(devs) <= 1 or local_rows % len(devs) != 0:
        return None
    mesh = jsh.Mesh(np.asarray(devs), ("_decode_batch",))
    return jsh.NamedSharding(mesh, jsh.PartitionSpec("_decode_batch"))


def _split_program(columns, word, shardings):
    """``jit_ptpu_slab_split``: a staged slab's used bytes, put as one array of
    ``word``s, cut into its ``columns`` (``(offset, shape, dtype)`` each) — a
    slice, a reshape and, last, a bitcast to the column's dtype. It does no
    arithmetic, so every value arrives bit for bit. ``shardings``, when given,
    places each output as the column's own put would have."""
    import jax
    from jax import lax

    def ptpu_slab_split(words):
        out = []
        for offset, shape, dtype in columns:
            start = offset // word.itemsize
            col = lax.slice(words, (start,), (start + math.prod(shape),))
            out.append(lax.bitcast_convert_type(col.reshape(shape), dtype))
        return tuple(out)

    kwargs = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(ptpu_slab_split, **kwargs)


def _is_device_dtype(arr):
    """Only numeric/bool fixed-shape columns can live on device; strings/objects stay host."""
    return isinstance(arr, np.ndarray) and arr.dtype.kind in "biufc"


class DeviceTransfer:
    """Host batch → ``(device arrays, host-only columns)``; one per loader.

    ``stats`` is the loader's ``PipelineStats`` and ``stage(name, batch=seq)``
    opens one of its stages; the rest are the loader's ``sharding``,
    ``staging=`` and resolved ``pack``, the reader's schema and device-decode
    fields, the validated ``device_decode_resize`` (a crop policy, or a dict of
    them by field) and the seed its crop boxes are drawn under. It owns the staging
    pool (:meth:`close` frees it), the layout program per row count, the slab
    split program per slab layout and the once-per-loader unsharded-decode
    warning."""

    def __init__(self, stats, stage, sharding=None, staging=None, pack=None,
                 schema=None, decode_fields=(), resize=None, seed=None):
        self.stats = stats
        self._stage = stage
        self.sharding = sharding
        self.pack = pack
        self.schema = schema
        self.decode_fields = decode_fields
        self.resize = resize
        self.seed = seed
        self._decoded = 0  # batches decode() took: their crop boxes' key
        self._staging_arg = staging
        self._staging = None
        self._staging_decided = False
        self._pack_programs = {}
        self._split_programs = {}
        self._warned_unsharded_decode = False

    def __call__(self, batch, seq, beat=None):
        """Transfer host batch number ``seq``; ``beat(state)``, when given, is
        called as the batch enters decode and H2D. Returns the device arrays
        and the host-only (string/object) columns."""
        leases = take_leases(batch)
        # the inflate's inputs are fresh copies of the pages, and it returns
        # only once its programs have run (it reads their ok flags)
        batch, inflated = self.inflate(batch, seq)
        if beat is not None:
            beat("decode")
        with self._stage("decode.dispatch", batch=seq), \
                watch_borrowed_inputs(bool(leases)) as borrowed:
            batch, decoded = self.decode(batch)
        if beat is not None:
            beat("h2d")
        with self._stage("h2d.transfer", batch=seq):
            table = batch.pop(_PACK_TABLE, None) if self.pack is not None else None
            device, host = self.split(batch, leases)
            device, slab = self.stage(device, leases, seq)
            arrays, table, waited = self.put(device, table, seq, slab)
            waited.extend(borrowed)
            arrays.update(decoded)
            arrays.update(inflated)
            self.stats.h2d_unwaited_outputs += len(decoded) + len(inflated)
            self.release(waited, slab, leases, seq)
            if table is not None:
                # after the wait: the layout reads no host buffer, and waiting on
                # it would wait on every program queued on the device before it
                with phase("pack.layout", batch=seq):
                    arrays.update(self.layout(arrays[self.pack[0]], table))
        return arrays, host

    def inflate(self, batch, seq):
        """The device inflate of compressed-page pass-through columns: the
        Pallas kernels (:mod:`petastorm_tpu.ops.pagedec_kernels`) on a non-CPU
        backend, the numpy reference otherwise (the decoded array then rides
        the normal staging and put). Returns ``(batch without the inflated
        columns, {name: device array})``.

        Pages and compressed/saved bytes land in the ``ptpu_pagedec_*``
        family. Columns that take the host fallback (CPU backend, sharded
        delivery, kernel bail) also count
        ``ptpu_pagedec_host_inflate_columns_total``: their H2D leg shipped the
        decoded array, so the saved-bytes number covered the wire only. The
        stage is ``decode.device_inflate`` (a span, and a chaos hook site of
        the same name)."""
        names = [name for name, v in batch.items()
                 if getattr(v, "is_passthrough", False)]
        if not names:
            return batch, {}
        import jax

        from petastorm_tpu import chaos as _chaos
        from petastorm_tpu.io.pagedec import pagedec_counters
        from petastorm_tpu.ops import pagedec_kernels as pk

        counters = pagedec_counters()
        with self._stage("decode.device_inflate", batch=seq) as st:
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.hit("decode.device_inflate")
            # sharded delivery takes the host path: the decoded array goes
            # through the same sharded put as any other column
            use_device = self.sharding is None and (
                jax.default_backend() != "cpu"
                or os.environ.get("PTPU_PAGEDEC_DEVICE", "") not in ("", "0"))
            decoded = {}
            for name in names:
                col = batch.pop(name)
                counters["pages"].inc(sum(
                    (p1 - p0) + (1 if c.dict_page is not None else 0)
                    for c, s, t in col.parts
                    for p0, p1, _base in (c.covering_pages(s, t),)))
                shipped = col.shipped_nbytes
                counters["bytes_compressed"].inc(shipped)
                counters["bytes_saved"].inc(max(0, col.raw_nbytes - shipped))
                arr = None
                if use_device:
                    try:
                        arr = pk.inflate_column(col)
                    except pk.DeviceInflateError:
                        arr = None  # host twin below validates + raises if corrupt
                if arr is None:
                    counters["host_inflate_columns"].inc()
                    batch[name] = col.materialize()
                else:
                    decoded[name] = arr
        counters["inflate_seconds"].observe(st.dt)
        return batch, decoded

    def decode(self, batch):
        """Finish two-stage codec decode on the device: staging-payload columns
        (e.g. the JPEG coefficient planes of ``decode_on_device=True`` readers)
        become device arrays, one batched codec dispatch per column, under the
        column's batch-axis sharding when one resolves. Under a crop policy that
        draws, the column's boxes (:meth:`boxes`, under the number of this call)
        join the batch as ``<name>_box`` and ship with its other columns.
        Returns ``(batch without the staged columns, {name: device array})``."""
        if not self.decode_fields:
            return batch, {}
        batch = dict(batch)
        seq, self._decoded = self._decoded, self._decoded + 1
        decoded = {}
        counted = False  # a batch counts once, however many fields fell back
        for name in self.decode_fields:
            arr = batch.pop(name, None)
            if arr is None:
                continue
            field = self.schema.fields[name]
            staged = list(arr)
            if any(s is None for s in staged):
                raise ValueError(
                    "Field %r has null rows; nullable columns are not supported with "
                    "decode_on_device (pad or filter nulls upstream)" % name
                )
            base = column_sharding(self.sharding, name)
            spmd = _decode_sharding(base, len(staged)) if base is not None else None
            kwargs = {} if spmd is None else {"sharding": spmd}
            policy = self.resize.get(name) if isinstance(self.resize, dict) \
                else self.resize
            # a batch already at the size, with no box to draw, decodes as
            # without the policy: one layout keeps its program and its sharding
            crop = policy is not None and (
                policy.draws or any(_row_size(s) != policy.size for s in staged))
            if crop:
                # the canvas decode runs on one device, whatever the sharding
                spmd = None
                kwargs = {"resize_to": policy.size}
                if policy.draws:
                    kwargs["boxes"] = batch[name + "_box"] = self.boxes(
                        staged, policy, seq)
            out = field.codec.device_decode_batch(field, staged, **kwargs)
            if self._unsharded(name, base, spmd, len(staged), crop) and not counted:
                counted = True
                self.stats.decode_unsharded_batches += 1
            decoded[name] = self._reshard(name, out)
        return batch, decoded

    def boxes(self, staged, policy, seq):
        """The crop boxes of decoded batch ``seq``'s staged rows under ``policy``
        (:func:`petastorm_tpu.ops.crop.draw_boxes`), from each row's header size,
        drawn under ``(seed, seq)``: the ``decode.crop`` phase."""
        from petastorm_tpu.ops import crop
        from petastorm_tpu.trace import count

        with phase("decode.crop", rows=len(staged)):
            boxes = crop.draw_boxes([_row_size(s) for s in staged], policy,
                                    crop.generator(self.seed or 0, seq))
        count("crop_boxes", len(boxes))
        return boxes

    def _unsharded(self, name, base, spmd, rows, crop=False):
        """True when the field's sharding cuts the batch axis across several
        devices but its decode ran on one (the batch does not divide, no
        process-local mesh derives, or a crop policy's canvas decode ran);
        warns once per loader. Correct output either way, but on a pod host one
        chip then decodes for all of them. (Sub-groups of mixed layouts can
        still fall back inside the codec without being counted here.)"""
        want = batch_axis_shard_count(base)
        got = batch_axis_shard_count(spmd)
        if want <= 1 or (got > 1 and rows % got == 0):
            return False
        if not self._warned_unsharded_decode:
            self._warned_unsharded_decode = True
            from petastorm_tpu.obs.log import degradation

            # once=False: this per-loader flag gates the log (obs.log's own
            # warn-once is per process, and two loaders each deserve a warning)
            degradation(
                "unsharded_decode",
                "Staged decode of field %r is running on a SINGLE device "
                "although its sharding splits the batch axis %d ways "
                "(batch rows=%d). Decode output is correct but unscaled; %s "
                "(Warned once; see PipelineStats.decode_unsharded_batches.)",
                name, want, rows,
                "the canvas decode of a device_decode_resize crop runs on one "
                "device." if crop else "make the per-process batch divisible by "
                "the batch-axis shard count.", once=False)
        return True

    def _reshard(self, name, out):
        """Lay a decoded column out like the batch (the ``decode.launch``
        phase)."""
        s = column_sharding(self.sharding, name, out)
        if s is None:
            return out
        import jax

        if jax.process_count() > 1:
            # `out` is device-resident: the process-local assembly places its
            # shards device-to-device, where np.asarray would pay the decoded
            # bytes' D2H + H2D that the two-stage split exists to avoid
            return jax.make_array_from_process_local_data(s, out)
        with phase("decode.launch"):
            return jax.device_put(out, s)

    def split(self, batch, leases):
        """The columns that go to the device, and the host-only rest (copied
        out of the leased slabs, which go back before the consumer reads
        them). A ragged column raises: it has no static shape."""
        device = {k: v for k, v in batch.items() if _is_device_dtype(v)}
        host = {k: v for k, v in batch.items() if k not in device}
        for name, arr in host.items():
            if isinstance(arr, np.ndarray) and arr.dtype == object and len(arr) \
                    and isinstance(arr[0], (np.ndarray, list, tuple)):
                raise ValueError(
                    "Field %r holds ragged tensors and cannot reach the device "
                    "with a static shape. Declare DataLoader(pad_shapes={%r: "
                    "(max_dims...)}) to zero-pad it (a %s__mask column marks the "
                    "valid region)." % (name, name, name)
                )
        if host:
            logger.debug("Fields kept host-side (non-device dtypes): %s", sorted(host))
            if leases:
                host = detach_slab_views(host)
        return device, host

    def stage(self, device, leases, seq):
        """``h2d.stage``: one copy of the device columns into a page-locked
        slab, which the put then reads. Returns ``(columns, slab lease or
        None)``. Without a pool, leased views are copied owned where
        ``device_put`` aliases host memory (the device arrays would otherwise
        be the memory the leases' release recycles)."""
        pool = self._ensure_staging(device) if device else None
        if pool is not None:
            with phase("h2d.stage", batch=seq):
                return pool.stage(device)
        if leases:
            from petastorm_tpu.io.staging import device_put_aliases_host

            if device_put_aliases_host():
                copied = 0
                for name, arr in list(device.items()):
                    if isinstance(arr, np.ndarray) and not arr.flags.writeable:
                        device[name] = arr.copy()
                        copied += arr.nbytes
                count_copy("h2d_owned_copy", copied)
        return device, None

    def _ensure_staging(self, device):
        """Resolve (once) and return the pinned H2D staging pool, or None.

        Decided from the first batch with device columns: ``staging=None``
        enables it on accelerator backends only; ``True``/an int force it —
        but any mode is refused when this backend's ``device_put`` aliases
        host memory (recycled slabs would corrupt delivered arrays), with a
        ``staging_aliasing`` degradation."""
        if self._staging_decided:
            return self._staging
        sizes = [v.nbytes for v in device.values() if isinstance(v, np.ndarray)]
        if not sizes:
            return None  # nothing stageable yet: decide on a later batch
        self._staging_decided = True
        arg = self._staging_arg
        if arg is False:
            return None
        from petastorm_tpu.io.staging import (PinnedStagingPool, _STAGE_ALIGN,
                                              device_put_aliases_host)

        if arg is None:
            import jax

            if jax.default_backend() == "cpu" or device_put_aliases_host():
                return None  # auto mode: pageable→pinned buys nothing on CPU
        elif device_put_aliases_host():
            from petastorm_tpu.obs.log import degradation

            degradation(
                "staging_aliasing",
                "DataLoader(staging=%r) refused: this backend's device_put "
                "ALIASES host numpy memory, so staging-slab reuse would "
                "corrupt delivered batches; transferring from pageable "
                "memory", arg)
            return None
        need = 0
        for nbytes in sizes:
            need = -(-need // _STAGE_ALIGN) * _STAGE_ALIGN + nbytes
        slab_bytes = int(arg) if not isinstance(arg, bool) and arg is not None \
            else need
        self._staging = PinnedStagingPool(max(slab_bytes, need), num_slabs=2)
        return self._staging

    def put(self, device, table, seq, slab=None):
        """``h2d.put``: the columns to their placement, and a packed batch's
        chunk table to every device of the tokens. A batch :meth:`_slab_words`
        admits goes as ONE transfer of its staged slab, cut into its columns
        on the device by :func:`_split_program` (``h2d_slab_puts``); any other
        goes column by column to :func:`column_sharding`'s placement (a field
        without one to the default device). Returns ``(arrays, table,
        waited)``: ``waited`` lists the transfers that read the host columns
        (the slab's word array, or the columns), never the split program's
        outputs."""
        import jax

        one = self._slab_words(device, slab)
        if one is not None:
            nbytes, transfers = one[0].nbytes, 1
        else:
            nbytes = sum(getattr(v, "nbytes", 0) for v in device.values())
            transfers = len(device)
        if table is not None:
            nbytes += table.nbytes
            transfers += 1
        with phase("h2d.put", batch=seq, bytes=nbytes, arrays=transfers):
            if one is not None:
                arrays, waited = self._put_slab(slab.columns, *one)
                self.stats.h2d_slab_puts += 1
            else:
                arrays = self._put_columns(device)
                waited = list(arrays.values())
            if table is not None:
                table = jax.device_put(
                    table, _replicated_like(arrays[self.pack[0]].sharding))
        return arrays, table, waited

    def _slab_words(self, device, slab):
        """``(words, shardings)`` where the staged batch can go as one
        transfer, else None. It can where ``slab`` holds at least two columns,
        all of one itemsize and of integer or float dtypes that JAX keeps as
        they are (with x64 off an ``int64`` is narrowed on the host), all bound
        for one device (no sharding, or a one-device sharding for every
        column, in one process). ``words`` is the slab's used bytes viewed as
        unsigned words of that itemsize (no copy); ``shardings`` the columns'
        placements, None without a sharding."""
        if slab is None or len(slab.columns) < 2:
            return None
        import jax

        dtypes = {dtype for _name, _offset, _shape, dtype in slab.columns}
        itemsizes = {dtype.itemsize for dtype in dtypes}
        if len(itemsizes) != 1:
            return None
        word = np.dtype("uint%d" % (8 * itemsizes.pop()))
        if any(dtype.kind not in "iuf" or jax.dtypes.canonicalize_dtype(dtype) != dtype
               for dtype in dtypes | {word}):
            return None
        shardings = None
        if self.sharding is not None:
            if jax.process_count() > 1:
                return None
            shardings = tuple(column_sharding(self.sharding, name, device[name])
                              for name, _offset, _shape, _dtype in slab.columns)
            if any(s is None for s in shardings) or \
                    len(set().union(*(s.device_set for s in shardings))) != 1:
                return None
        return slab.prefix.view(word), shardings

    def _put_slab(self, columns, words, shardings):
        """One ``device_put`` of ``words`` and the cached split program on it.
        Returns ``({name: column}, [the word array])``."""
        import jax

        layout = tuple((offset, shape, dtype) for _name, offset, shape, dtype in columns)
        key = (layout, words.dtype, shardings)
        fn = self._split_programs.get(key)
        if fn is None:
            fn = self._split_programs[key] = _split_program(layout, words.dtype, shardings)
        target = None if shardings is None else next(iter(shardings[0].device_set))
        on_device = jax.device_put(words, target)
        return dict(zip((c[0] for c in columns), fn(on_device))), [on_device]

    def _put_columns(self, device):
        """Each column on its own ``device_put`` (or process-local assembly)."""
        import jax

        if self.sharding is None:
            return jax.device_put(device)
        arrays = {}
        for name, arr in device.items():
            s = column_sharding(self.sharding, name, arr)
            if s is None:
                arrays[name] = jax.device_put(arr)
            elif jax.process_count() > 1:
                arrays[name] = jax.make_array_from_process_local_data(s, arr)
            else:
                arrays[name] = jax.device_put(arr, s)
        return arrays

    def release(self, waited, slab, leases, seq):
        """``h2d.wait``, then the slab and the leases back to their rings.

        ``device_put`` is async: ``waited`` holds the transfers that may still
        read a buffer about to be recycled (the slab's word array or the
        columns' puts from the slab or leased views, a program's input put
        from borrowed memory), and no program's output — that wait would wait on every program queued on the
        device before it (the running step)."""
        if slab is None and not leases:
            return
        if waited:
            import jax

            with phase("h2d.wait", batch=seq, arrays=len(waited)):
                jax.block_until_ready(waited)
        if slab is not None:
            slab.release()
        for lease in leases:
            lease.release()

    def layout(self, tokens, table):
        """Launch ``jit_ptpu_pack_layout`` on the device tokens' chunk table: the
        segment ids and positions, laid out like the tokens."""
        field, length = self.pack
        rows = tokens.shape[0]
        fn = self._pack_programs.get(rows)
        if fn is None:
            from petastorm_tpu.ops.packing import layout_program

            fn = self._pack_programs[rows] = layout_program(
                rows, length, tokens.sharding if self.sharding is not None else None)
        seg, pos = fn(table)
        return {field + "_segment_ids": seg, field + "_positions": pos}

    def layout_host(self, batch):
        """The host twin of :meth:`layout`, for ``to_device=False``."""
        from petastorm_tpu.ops.packing import layout_numpy

        field, length = self.pack
        seg, pos = layout_numpy(batch.pop(_PACK_TABLE), len(batch[field]), length)
        batch[field + "_segment_ids"] = seg
        batch[field + "_positions"] = pos
        return batch

    def close(self):
        """Free the staging pool."""
        if self._staging is not None:
            self._staging.close()
            self._staging = None
