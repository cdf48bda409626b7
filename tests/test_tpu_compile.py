"""The main path's device programs compile for a described TPU v5e chip.

Nothing runs here: the TPU compiler installed with jaxlib compiles each program at
its real size for one chip of a described ``v5e:2x2`` topology, which catches what
interpret mode cannot (tiling, scoped VMEM, memory). The topology is described in a
fixture — never at import — so every pytest-xdist worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""

import pytest


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu(monkeypatch):
    """Compile as the chip would: the persistent cache off (its entries cannot be
    read back without a chip), and code that picks Pallas interpret mode from
    ``jax.default_backend()`` told it is on a TPU."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return fn.lower(*specs).compile()


def test_idct_kernel_compiles_at_batch_128(tpu, one_chip):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops.jpeg import _idct_scaled

    n = 128 * 28 * 28  # luma blocks of a 128-image 224x224 batch
    compiled = _compile(jax.jit(_idct_scaled), _spec((n, 64), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_normalize_kernel_compiles(tpu, one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import image

    monkeypatch.setattr(image, "_use_interpret", lambda: False)
    fn = jax.jit(image.normalize_images.__wrapped__, static_argnames=("out_dtype",))
    compiled = _compile(fn, _spec((128, 224, 224, 3), jnp.uint8, one_chip),
                        _spec((3,), jnp.float32, one_chip),
                        _spec((3,), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_pages,cap", [
    (1, 1 << 20),  # one page at Parquet's default 1 MiB page size
    # a window of pages padded to a dictionary page just over pyarrow's 1 MiB
    # dictionary limit (a float column's measured 1,050,500 bytes -> 2 MiB)
    (4, 1 << 21),
])
def test_snappy_inflate_compiles_at_1mib_pages(tpu, one_chip, n_pages, cap):
    """Data-parallel XLA (no Pallas kernel): it must fit one chip's memory."""
    import jax.numpy as jnp

    from petastorm_tpu.ops import pagedec_kernels as pk

    fn = pk._snappy_pages_fn(n_pages, cap, cap)
    compiled = _compile(fn, _spec((n_pages, cap), jnp.uint8, one_chip),
                        _spec((n_pages, 2), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < n_pages * cap * 512, mem


@pytest.mark.parametrize("dtype,num_values,bit_width", [
    ("<i4", 16384, 11),  # an RLE-dictionary page: 11-bit indices
    ("<i4", 20000, None),  # a PLAIN page at pyarrow's 20,000-row page cap
])
def test_data_page_program_compiles(tpu, one_chip, dtype, num_values, bit_width):
    """The loader's per-page program (RLE expansion, dictionary gather, slice)
    over a window of 1 MiB-bucketed pages, the dictionary page's upper bound.
    XLA, no Pallas kernel: the Pallas extraction dropped bit 9 of 11-bit values
    on a v5e (PR 21)."""
    import jax.numpy as jnp

    from petastorm_tpu.ops import pagedec_kernels as pk

    out_cap = 1 << 20
    fn = pk._data_page_fn(out_cap, dtype, num_values, bit_width)
    scalar = _spec((), jnp.int32, one_chip)
    compiled = _compile(fn, _spec((4, out_cap), jnp.uint8, one_chip),
                        scalar, scalar, scalar, scalar)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_jpeg_stage2_compiles_420_224_batch_128(tpu, one_chip):
    import jax.numpy as jnp

    from petastorm_tpu.ops import jpeg

    layout = (224, 224, ((2, 2, 28, 28), (1, 1, 14, 14), (1, 1, 14, 14)))
    jpeg._batched_stage2.cache_clear()  # a CPU-traced variant must not be reused
    fn = jpeg._batched_stage2(layout)
    coeffs = tuple(_spec((128, by * bx, 64), jnp.int16, one_chip)
                   for _h, _v, by, bx in layout[2])
    qtabs = tuple(_spec((128, 64), jnp.int32, one_chip) for _ in layout[2])
    compiled = _compile(fn, coeffs, qtabs)
    assert "tpu_custom_call" in compiled.as_text()
    jpeg._batched_stage2.cache_clear()


def test_jpeg_stage2_compiles_spmd_over_dp4(tpu, topo):
    """Sharded delivery decodes one batch shard per chip: the stage-2 program
    must partition over a dp=4 mesh, Pallas IDCT included (shard_map)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.ops import jpeg

    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    layout = (224, 224, ((2, 2, 28, 28), (1, 1, 14, 14), (1, 1, 14, 14)))
    jpeg._batched_stage2.cache_clear()
    fn = jpeg._batched_stage2(layout, spmd=NamedSharding(mesh, P("dp")))
    coeffs = tuple(_spec((128, by * bx, 64), jnp.int16,
                         NamedSharding(mesh, P("dp", None, None)))
                   for _h, _v, by, bx in layout[2])
    qtabs = tuple(_spec((128, 64), jnp.int32, NamedSharding(mesh, P("dp", None)))
                  for _ in layout[2])
    compiled = _compile(fn, coeffs, qtabs)
    assert "tpu_custom_call" in compiled.as_text()
    jpeg._batched_stage2.cache_clear()


def test_jpeg_crop_resize_compiles_512_canvas_batch_128(tpu, one_chip):
    """The original-size ImageNet cell's main bucket: 128 4:2:0 images in 512x512
    canvases, split-packed coefficients as 32-bit words (a flat array and its
    overflow a component), cropped and resized to 224x224 by HIGHEST matmuls,
    within a few GB of one chip's 16."""
    import jax.numpy as jnp

    from petastorm_tpu.ops import jpeg

    sampling = ((2, 2), (1, 1), (1, 1))
    variant = (None, (False,) * 3, ((24, 40),) * 3)
    jpeg._crop_program.cache_clear()
    fn = jpeg._crop_program(sampling, (512, 512), 128, variant, (224, 224), False)
    words = (24 * 3 // 2 // 4, (40 - 24) // 4, (64 - 40) // 2 // 4)
    caps = (430080, 110592, 110592)
    flat = tuple(tuple(_spec((c, w), jnp.uint32, one_chip) for w in words) for c in caps)
    over = tuple(tuple(_spec((c // 2, w), jnp.uint32, one_chip) for w in words)
                 for c in caps)
    table = _spec((128, jpeg._TABLE_HEAD + 67 * 3), jnp.int32, one_chip)
    acc = _spec((128, 224, 224, 3), jnp.uint8, one_chip)
    compiled = _compile(fn, flat, over, table, acc)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
    jpeg._crop_program.cache_clear()


@pytest.mark.parametrize("rows,pair", [(16384, False), (1000, True)])
def test_slab_split_compiles_for_a_criteo_batch(tpu, one_chip, rows, pair):
    """The staged slab's split program at the Criteo cell's size (a b16384
    label, 13 float32 dense and 26 int32 categorical columns, one uint32 word
    array) and at a partial batch with an alignment gap and a 2-D column: the
    outputs keep each column's shape and dtype, with no scratch beyond them."""
    import jax.numpy as jnp
    import numpy as np

    from petastorm_tpu.transfer import _split_program

    dtypes = [np.dtype(np.int32)] + [np.dtype(np.float32)] * 13 + \
        [np.dtype(np.int32)] * 26
    shapes = [(rows,)] * len(dtypes)
    if pair:
        shapes[1] = (rows, 3)
    layout, end = [], 0
    for shape, dtype in zip(shapes, dtypes):
        offset = -(-end // 256) * 256
        layout.append((offset, shape, dtype))
        end = offset + int(np.prod(shape)) * 4
    fn = _split_program(tuple(layout), np.dtype(np.uint32), None)
    compiled = _compile(fn, _spec((end // 4,), jnp.uint32, one_chip))
    outs = compiled.out_info
    assert [(o.shape, np.dtype(o.dtype)) for o in outs] == list(zip(shapes, dtypes))
    assert compiled.memory_analysis().temp_size_in_bytes <= end
