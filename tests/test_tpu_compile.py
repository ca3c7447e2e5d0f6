"""Every RMI Pallas kernel compiles for a TPU v5e at the widths the chip
smoke run uses.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described and not attached.  Interpret-mode parity (the
other kernel suites) cannot show what Mosaic refuses — gathers it has
no lowering for, blocks that break the (8, 128) tiling rule, more VMEM
than a kernel may use — so these compiles guard it.  Keep every such
test in this one file: describing the topology loads the TPU library,
and only the worker that runs this file may do that.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import rmi_lookup

N = 1 << 20          # keys: the kernel-phase index of chip_smoke.py
M = N // 64          # leaves, as the service sizes them
B = 4096             # queries per call
D = 8192             # delta entries (power-of-two pad)
S = 4                # shards
PAGE, PAGES = 256, 16
HIDDEN = (16, 16)    # the widest stage-0 MLP the kernels take
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e chip's sharding, with the persistent compile cache off:
    a compile for a described chip is written to it but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return tuple(jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
                 for shape, dt in specs)


def _stage0(sharding, hidden, lead=()):
    dims = (1, *hidden, 1)
    specs = []
    for a, b in zip(dims[:-1], dims[1:]):
        specs += [(lead + (a, b), F32), (lead + (b,), F32)]
    return _shapes(sharding, *specs)


def _compiled_kernel(fn, args, static) -> bool:
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    return "tpu_custom_call" in text


@pytest.mark.parametrize("hidden", [(), HIDDEN])
def test_lookup_compiles(one_chip, hidden):
    args = (_shapes(one_chip, ((B,), F32))
            + (_stage0(one_chip, hidden),)
            + _shapes(one_chip, *[((M,), F32)] * 4, ((N,), F32)))
    assert _compiled_kernel(
        rmi_lookup.rmi_lookup_pallas, args,
        dict(hidden=hidden, n=N, num_leaves=M, max_window=256))


@pytest.mark.parametrize("hidden", [(), HIDDEN])
def test_merged_lookup_compiles(one_chip, hidden):
    args = (_shapes(one_chip, ((B,), F32))
            + (_stage0(one_chip, hidden),)
            + _shapes(one_chip, *[((M,), F32)] * 4, ((N,), F32),
                      ((D,), F32), ((D + 1,), I32)))
    assert _compiled_kernel(
        rmi_lookup.rmi_merged_lookup_pallas, args,
        dict(hidden=hidden, n=N, num_leaves=M, max_window=256))


@pytest.mark.parametrize("batch", [B, 2])  # 2: a range_lookup's bounds
def test_sharded_merged_lookup_compiles(one_chip, batch):
    ns, ms = N // S, N // S // 48
    args = (_shapes(one_chip, ((S, batch), F32))
            + (_stage0(one_chip, (), (S,)),)
            + _shapes(one_chip, *[((S, ms), F32)] * 4, ((S, ns), F32),
                      ((S, D), F32), ((S, D + 1), I32), ((S,), I32),
                      ((S,), I32), ((S,), F32)))
    assert _compiled_kernel(
        rmi_lookup.rmi_sharded_merged_lookup_pallas, args,
        dict(hidden=(), max_window=256))


def test_scan_range_compiles(one_chip):
    args = _shapes(one_chip, ((2,), F32), ((N,), F32), ((N,), I32),
                   ((D,), F32), ((D,), I32), ((D,), I32), ((D,), I32),
                   ((D,), I32))
    assert _compiled_kernel(
        rmi_lookup.rmi_scan_range_pallas, args,
        dict(page_size=PAGE, max_pages=PAGES))


def test_scan_page_compiles(one_chip):
    args = _shapes(one_chip, ((PAGES,), I32), ((N,), F32), ((N,), I32),
                   ((D,), F32), ((D,), I32), ((D,), I32), ((1,), I32))
    assert _compiled_kernel(
        rmi_lookup.rmi_scan_page_pallas, args, dict(page_size=PAGE))


@pytest.mark.parametrize("op", ["range", "page"])
def test_fused_scan_op_program_compiles(one_chip, op):
    """The fused scan ops' one program — the kernel with the argument
    casts and the live mask's bool cast around it — compiles whole."""
    from repro.kernels import ops

    if op == "range":
        fn, static = ops._scan_range_jit, dict(max_pages=PAGES)
        args = _shapes(one_chip, ((2,), F32), ((N,), F32), ((N,), I32),
                       ((D,), F32), ((D,), I32), ((D,), I32), ((D,), I32),
                       ((D,), I32))
    else:
        fn, static = ops._scan_page_jit, {}
        args = _shapes(one_chip, ((PAGES,), I32), ((N,), F32),
                       ((N,), I32), ((D,), F32), ((D,), I32), ((D,), I32),
                       ((1,), I32))
    assert _compiled_kernel(
        fn, args, dict(page_size=PAGE, use_kernel=True, **static))


@pytest.mark.parametrize("staged", ["empty", "non-empty"])
def test_cells_scan_program_compiles_at_their_size(one_chip, staged):
    """The scan program the benchmark's cells run (strategy ``binary``:
    the XLA body) over 200M keys, at the staged-insert pad of an empty
    delta and at the one pad every non-empty delta of a default service
    scans at, with no tombstones (the cells delete nothing).  Its
    arguments are the base keys and values and arrays of the delta's
    size: nothing else sized by the key count."""
    from repro.index_service.plane import EMPTY_STAGED_PAD
    from repro.index_service.service import ServiceConfig, _staged_scan_pad
    from repro.kernels import ops

    n = 200_000_000
    d = (EMPTY_STAGED_PAD if staged == "empty"
         else _staged_scan_pad(ServiceConfig()))
    t = EMPTY_STAGED_PAD    # tombstone pad
    args = _shapes(one_chip, ((2,), F32), ((n,), F32), ((n,), I32),
                   ((d,), F32), ((d,), I32), ((d,), I32), ((t,), I32),
                   ((t,), I32))
    compiled = ops._scan_range_jit.lower(
        *args, page_size=PAGE, max_pages=2, use_kernel=False,
        interpret=False).compile()
    # 8 B a key, and under a MiB more (the chip's tiling pads the
    # delta-sized arrays): no operand of 4 B a key besides the base's
    held = compiled.memory_analysis().argument_size_in_bytes
    assert 8 * n <= held < 8 * n + (1 << 20)


def test_sharded_scan_page_compiles(one_chip):
    ns = N // S
    args = _shapes(one_chip, ((S, ns), F32), ((S, ns), I32),
                   ((S, D), F32), ((S, D), I32), ((S, D), I32),
                   ((S, D), I32), ((S,), I32), ((S,), I32), ((S,), I32))
    assert _compiled_kernel(
        rmi_lookup.rmi_sharded_scan_page_pallas, args,
        dict(page_size=PAGE, max_pages=PAGES))


def test_index_past_vmem_is_refused(one_chip):
    """Four times the kernel-phase index no longer fits the scoped VMEM
    the whole-array slabs live in: the compiler, not the chip, says
    so."""
    n, m = 4 * N, 4 * N // 64
    args = (_shapes(one_chip, ((B,), F32))
            + (_stage0(one_chip, ()),)
            + _shapes(one_chip, *[((m,), F32)] * 4, ((n,), F32),
                      ((D,), F32), ((D + 1,), I32)))
    with pytest.raises(Exception, match="vmem|RESOURCE_EXHAUSTED"):
        _compiled_kernel(rmi_lookup.rmi_merged_lookup_pallas, args,
                         dict(hidden=(), n=n, num_leaves=m, max_window=256))
