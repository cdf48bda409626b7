"""The transfer stage (``petastorm_tpu.transfer``): the placement rule; a host
batch driven through ``DeviceTransfer`` with no loader thread, landing its stage
and phase totals in the ``PipelineStats`` fields the benchmark's metrics read;
and the loader's lifetime with a transfer inside it."""
import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

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
