"""The transfer stage (``petastorm_tpu.transfer``): the placement rule; a host
batch driven through ``DeviceTransfer`` with no loader thread, landing its stage
and phase totals in the ``PipelineStats`` fields the benchmark's metrics read;
the staged slab's one-transfer put and the batches it declines; and the
loader's lifetime with a transfer inside it."""
import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from petastorm_tpu.io.staging import PinnedStagingPool
from petastorm_tpu.loader import PipelineStats, _Stage
from petastorm_tpu.transfer import DeviceTransfer, column_sharding


def _dp():
    return NamedSharding(Mesh(np.array(jax.devices()[:8]), ("dp",)), PartitionSpec("dp"))


@pytest.mark.parametrize("case", ["dict_with_field", "dict_without_field", "named_up",
                                  "named_down", "named_unadapted", "single_device",
                                  "none"])
def test_column_sharding(case):
    dp = _dp()
    one = SingleDeviceSharding(jax.devices()[0])
    rank3 = np.zeros((8, 2, 3), np.float32)
    if case == "dict_with_field":  # a dict's entry is used as given
        assert column_sharding({"img": dp, "id": one}, "img", rank3) is dp
    elif case == "dict_without_field":
        assert column_sharding({"img": dp}, "label", rank3) is None
    elif case == "named_up":  # trailing axes replicated
        s = column_sharding(dp, "img", rank3)
        assert s.mesh == dp.mesh and s.spec == PartitionSpec("dp", None, None)
    elif case == "named_down":
        wide = NamedSharding(dp.mesh, PartitionSpec("dp", None))
        s = column_sharding(wide, "id", np.zeros(8))
        assert s.mesh == dp.mesh and s.spec == PartitionSpec("dp")
    elif case == "named_unadapted":  # no array: the decode's batch-axis base
        assert column_sharding(dp, "img") is dp
    elif case == "single_device":
        assert column_sharding(one, "img", rank3) is one
    else:
        assert column_sharding(None, "img", rank3) is None


class _SlowCodec:
    """A device codec whose decode takes 20 ms: stacks the staged rows."""

    def __init__(self):
        self.shardings = []

    def device_decode_batch(self, field, staged, sharding=None, resize_to=None):
        self.shardings.append(sharding)
        time.sleep(0.02)
        return jnp.asarray(np.stack(staged))


@pytest.mark.parametrize("sharded", [False, True])
def test_device_transfer_fills_the_stage_totals(sharded):
    stats = PipelineStats()
    sinks = types.SimpleNamespace(stats=stats, _trace=None, _obs=None,
                                  _health_scope=None, _prov_rec=None,
                                  tenant_context=None)
    codec = _SlowCodec()
    schema = types.SimpleNamespace(fields={"img": types.SimpleNamespace(codec=codec)})
    sharding = _dp() if sharded else None
    transfer = DeviceTransfer(stats, functools.partial(_Stage, sinks),
                              sharding=sharding, schema=schema,
                              decode_fields=("img",))
    img = np.empty(8, dtype=object)
    img[:] = [np.full((2, 2), i, np.uint8) for i in range(8)]
    ids = np.arange(8, dtype=np.int64)
    x = np.ones((8, 3), np.float32)
    names = np.array(["r%d" % i for i in range(8)], dtype=object)
    beats = []
    arrays, host = transfer({"img": img, "id": ids, "x": x, "name": names}, 0,
                            beats.append)
    assert beats == ["decode", "h2d"]
    assert sorted(arrays) == ["id", "img", "x"] and list(host) == ["name"]
    np.testing.assert_array_equal(np.asarray(arrays["img"]), np.stack(list(img)))
    np.testing.assert_array_equal(np.asarray(arrays["x"]), x)
    assert codec.shardings == [sharding]
    if sharded:
        assert arrays["x"].sharding.spec == PartitionSpec("dp", None)
        assert arrays["img"].sharding.spec == PartitionSpec("dp", None, None)
        assert stats.decode_launch_s > 0  # the reshard
    # the stages' totals hold their phases' and the codec's time
    assert stats.decode_s >= 0.02 + stats.decode_launch_s
    assert stats.h2d_s >= stats.h2d_put_s > 0
    assert stats.h2d_bytes == ids.nbytes + x.nbytes
    assert stats.h2d_unwaited_outputs == 1
    assert stats.decode_unsharded_batches == 0


def test_loader_is_freed_without_the_cycle_collector():
    """The transfer's stage factory reaches the loader through a proxy, so no
    cycle runs through the transfer: a loader goes with its last reference."""
    import gc
    import weakref

    from petastorm_tpu.loader import DataLoader

    class _Reader:
        is_batched_reader = True

        def __iter__(self):
            yield {"x": np.arange(4)}

        def stop(self):
            pass

        def join(self):
            pass

    gc.disable()
    try:
        loader = DataLoader(_Reader(), 2, to_device=False)
        with loader:
            assert [len(b["x"]) for b in loader] == [2, 2]
        ref = weakref.ref(loader)
        del loader
        assert ref() is None
    finally:
        gc.enable()


def _awkward_bits(rows):
    """float32 bit patterns an arithmetic pass would not keep: quiet and
    signalling NaNs with payloads, -0.0, subnormals, infinities."""
    bits = np.array([0x7FC00001, 0x7F800001, 0xFFC12345, 0x80000000, 0x00000001,
                     0x807FFFFF, 0x7F800000, 0x3F800000], np.uint32)
    return np.resize(bits, rows).view(np.float32)


def _columns(rows, case="criteo"):
    """A batch's device columns: float32 (dense), int32 (categorical) and a 2-D
    float32; the decline cases swap one in that the one-transfer put refuses."""
    cols = {"label": np.resize(np.array([2**31 - 1, -2**31, 0, -1], np.int32), rows),
            "dense": _awkward_bits(rows),
            "pair": np.stack([_awkward_bits(rows)[::-1]] * 3, axis=1),
            "cat": np.arange(rows, dtype=np.int32) * -7919}
    if case == "mixed_itemsize":
        cols["cat"] = cols["cat"].astype(np.int16)
    elif case == "int64":  # with x64 off device_put narrows it on the host
        cols["label"] = cols["label"].astype(np.int64)
        cols["cat"] = cols["cat"].astype(np.int64)
        del cols["dense"], cols["pair"]
    elif case == "bool":
        cols = {"__valid__": np.ones(rows, bool), "flag": np.zeros(rows, np.uint8)}
    elif case == "single":
        cols = {"dense": cols["dense"]}
    return cols


def _transfer(sharding=None):
    stats = PipelineStats()
    sinks = types.SimpleNamespace(stats=stats, _trace=None, _obs=None,
                                  _health_scope=None, _prov_rec=None,
                                  tenant_context=None)
    return DeviceTransfer(stats, functools.partial(_Stage, sinks), sharding=sharding)


def _put_staged(transfer, cols):
    """Stage ``cols`` the way the transfer stage does and put them; the arrays
    are read back before the slab goes back to the ring."""
    pool = PinnedStagingPool(1 << 20, num_slabs=1)
    try:
        staged, slab = pool.stage(cols)
        assert slab is not None
        with transfer._stage("h2d.transfer", batch=0):
            arrays, _table, waited = transfer.put(staged, None, 0, slab)
        host = {k: np.asarray(v) for k, v in arrays.items()}
        slab.release()
        return arrays, host, waited, slab
    finally:
        pool.close()


@pytest.mark.parametrize("rows", [8, 13])
def test_slab_put_delivers_every_column_bit_for_bit(rows):
    """One transfer of the staged slab, cut apart on the device: every column,
    a 2-D one and NaN payloads among them, comes back bit-equal; the put ships
    the slab's used bytes and waits only on the word array."""
    cols = _columns(rows)
    transfer = _transfer()
    arrays, host, waited, slab = _put_staged(transfer, cols)
    assert list(arrays) == list(cols)
    for name, col in cols.items():
        assert host[name].dtype == col.dtype and host[name].shape == col.shape
        np.testing.assert_array_equal(host[name].view(np.uint32), col.view(np.uint32))
    stats = transfer.stats
    assert stats.h2d_slab_puts == 1
    # 13 rows leave alignment gaps: the used prefix ships, gaps and all
    assert stats.h2d_bytes == slab.prefix.nbytes >= sum(c.nbytes for c in cols.values())
    assert len(waited) == 1 and waited[0].dtype == np.uint32
    assert waited[0].shape == (slab.prefix.nbytes // 4,)
    assert len(transfer._split_programs) == 1
    _put_staged(transfer, _columns(rows))  # the same layout: the program is reused
    assert len(transfer._split_programs) == 1 and stats.h2d_slab_puts == 2


def test_slab_layout_matches_the_staged_views():
    """``PinnedStagingPool.stage`` reports each column where its view sits."""
    cols = _columns(13)
    pool = PinnedStagingPool(1 << 20, num_slabs=1)
    try:
        staged, slab = pool.stage(cols)
        base = slab.prefix.__array_interface__["data"][0]
        for name, offset, shape, dtype in slab.columns:
            view = staged[name]
            assert view.__array_interface__["data"][0] - base == offset
            assert offset % 256 == 0 and (view.shape, view.dtype) == (shape, dtype)
        assert slab.prefix.nbytes == offset + staged[name].nbytes
        assert not slab.prefix.flags.writeable
        slab.release()
    finally:
        pool.close()


@pytest.mark.parametrize("case", ["one_device_sharding", "per_field_one_device"])
def test_slab_put_keeps_a_one_device_placement(case):
    dev = jax.devices()[1]
    cols = _columns(8)
    if case == "one_device_sharding":
        sharding = SingleDeviceSharding(dev)
    else:
        sharding = {name: SingleDeviceSharding(dev) for name in cols}
    transfer = _transfer(sharding)
    arrays, host, _waited, _slab = _put_staged(transfer, cols)
    assert transfer.stats.h2d_slab_puts == 1
    for name, col in cols.items():
        assert arrays[name].devices() == {dev}
        np.testing.assert_array_equal(host[name].view(np.uint32), col.view(np.uint32))


@pytest.mark.parametrize("case", ["mixed_itemsize", "int64", "bool", "single",
                                  "sharded", "dict_missing_field"])
def test_slab_put_declines_what_it_cannot_cut(case):
    """A batch the one-transfer put does not admit goes column by column, as
    before: the same values and dtypes as a plain ``device_put``, one transfer
    a column, and ``h2d_slab_puts`` stays 0."""
    cols = _columns(8, case)
    sharding = None
    if case == "sharded":
        sharding = _dp()
    elif case == "dict_missing_field":  # a field with no entry: the default device
        sharding = {name: SingleDeviceSharding(jax.devices()[0]) for name in cols}
        del sharding["cat"]
    transfer = _transfer(sharding)
    arrays, host, waited, _slab = _put_staged(transfer, cols)
    assert transfer.stats.h2d_slab_puts == 0 and not transfer._split_programs
    assert sorted(map(id, waited)) == sorted(map(id, arrays.values()))
    assert len(arrays) == len(cols)
    for name, col in cols.items():
        want = np.asarray(jax.device_put(col))
        assert host[name].dtype == want.dtype
        np.testing.assert_array_equal(host[name], want)
    if case == "sharded":
        assert arrays["pair"].sharding.spec == PartitionSpec("dp", None)


def test_release_waits_on_the_word_array_not_the_columns(monkeypatch):
    """Through the whole stage with a forced pool: ``release`` is handed the
    one word array put from the slab, never the split program's outputs."""
    import petastorm_tpu.io.staging as staging_mod

    monkeypatch.setattr(staging_mod, "_alias_probe", False)
    stats = PipelineStats()
    sinks = types.SimpleNamespace(stats=stats, _trace=None, _obs=None,
                                  _health_scope=None, _prov_rec=None,
                                  tenant_context=None)
    transfer = DeviceTransfer(stats, functools.partial(_Stage, sinks), staging=True)
    handed = []
    release = transfer.release

    def spy(waited, slab, leases, seq):
        handed.append((list(waited), slab))
        release(waited, slab, leases, seq)

    monkeypatch.setattr(transfer, "release", spy)
    try:
        for seq in range(3):
            cols = _columns(8)
            arrays, host = transfer(dict(cols), seq)
            for name, col in cols.items():
                np.testing.assert_array_equal(np.asarray(arrays[name]).view(np.uint32),
                                              col.view(np.uint32))
            (waited, slab), = handed[-1:]
            assert slab is not None and len(waited) == 1
            assert waited[0].dtype == np.uint32 and waited[0].ndim == 1
            assert not any(w is a for w in waited for a in arrays.values())
    finally:
        transfer.close()
    assert stats.h2d_slab_puts == 3 and stats.h2d_stage_s > 0
