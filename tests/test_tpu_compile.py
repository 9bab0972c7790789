"""Compile the main path for a described TPU v5e: no chip needed, nothing runs.

The TPU compiler refuses what interpret mode accepts (block layouts that do
not match XLA's tiling, shape casts Mosaic has no layout for, more VMEM than
a kernel may use). These tests compile the four Pallas kernels at the
sift-like and deep-like page widths (4 KB pages, R=64, M=16, Q=256) and the
beam-search step at real widths over a small page count, all with the
Mosaic path (`interpret=False`), for one chip of a described `v5e:2x2`.
The beam-search test also checks that the chip's program looks up the ADC
table without a gather of its f32 elements, while the CPU's keeps that
gather. Two more compile the streaming deployment's shapes (the
`deep-freshdiskann` benchmark configuration): a flush's candidate search
and the beam search at k + overfetch over the reserved capacity.

The topology is described inside a fixture: the TPU library may be loaded
by one process at a time, so no module of the suite touches it at import.
The last test checks that chip_smoke.py, the run on a real chip, refuses a
host without one.
"""
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import get_preset
from repro.core.pages import records_per_page
from repro.core.search_kernel import _search_batch
from repro.core.vamana import _insert_candidates
from repro.kernels.fused_search import fused_page_rank, page_adc
from repro.kernels.page_scan import page_scan
from repro.kernels.pq_adc import pq_adc

R, M, Q = 64, 16, 256          # Vamana degree, PQ subspaces, query block
PAGES = 512                    # small page count; widths are the real ones
SCHEDULE = 64                  # pages in one kernel call's schedule
BATCH = 16                     # ServerConfig.max_batch: one served batch

# (dataset, records per 4 KB page, d) — stored widths as build_layout packs
PAGE_SHAPES = [
    ("sift-like", records_per_page(4096, 128, 1, R)[0], 128),
    ("deep-like", records_per_page(4096, 96, 4, R)[0], 96),
]


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back from the persistent
        # cache without a chip; keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_args(kernel, n_p, d, spec):
    pages = spec((PAGES, n_p, d), jnp.float32)
    codes = spec((PAGES, n_p, M), jnp.uint8)
    ids = spec((SCHEDULE,), jnp.int32)
    q = spec((Q, d), jnp.float32)
    luts = spec((Q, M, 256), jnp.float32)
    return {
        "page_scan": (lambda p, i, x: page_scan(p, i, x, interpret=False),
                      (pages, ids, q)),
        "page_adc": (lambda c, i, t: page_adc(c, i, t, interpret=False),
                     (codes, ids, luts)),
        "pq_adc": (lambda c, t: pq_adc(c, t, interpret=False),
                   (spec((PAGES * n_p, M), jnp.uint8),
                    spec((M, 256), jnp.float32))),
        "fused_page_rank": (
            lambda p, c, i, x, t: fused_page_rank(p, c, i, x, t,
                                                  interpret=False),
            (pages, codes, ids, q, luts)),
    }[kernel]


@pytest.mark.parametrize("name,n_p,d", PAGE_SHAPES)
@pytest.mark.parametrize("kernel", ["page_scan", "page_adc", "pq_adc",
                                    "fused_page_rank"])
def test_kernel_compiles_for_v5e(one_chip, kernel, name, n_p, d):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_args(kernel, n_p, d, spec)
    compiled = _compile(fn, *args)
    # the Mosaic kernel is in the program, not an interpreted loop
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("preset", ["baseline", "octopusann"])
def test_search_batch_compiles_for_v5e(one_chip, preset):
    """One served batch of the beam search at the sift-like widths, with the
    preset's static flags and the visited-page bitmap serving collects."""
    cfg = get_preset(preset)
    name, n_p, d = PAGE_SHAPES[0]
    n = PAGES * n_p
    n_entries = cfg.memgraph_entries if cfg.memgraph_frac > 0 else 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((PAGES, n_p), jnp.int32),            # page_vids
            spec((PAGES, n_p, d), jnp.float32),       # page_vecs
            spec((PAGES, n_p, R), jnp.int32),         # page_nbrs
            spec((n,), jnp.int32),                    # vid2page
            spec((n,), jnp.int32),                    # vid2slot
            spec((M, 256, d // M), jnp.float32),      # pq_centroids
            spec((n, M), jnp.uint8),                  # pq_codes
            spec((n,), jnp.bool_),                    # cached
            spec((BATCH, d), jnp.float32),            # queries
            spec((BATCH, n_entries), jnp.int32),      # entries
            spec((BATCH, n_entries), jnp.bool_))      # entry_valid

    def step(*a):
        return _search_batch(
            *a, k=cfg.k, L=cfg.L, width=cfg.beam_width,
            max_iters=cfg.max_iters, n_p=n_p, page_search=cfg.page_search,
            dynamic_width=cfg.dynamic_width, dw_min=cfg.dw_min,
            dw_max=cfg.dw_max, pipeline=cfg.pipeline,
            spec=cfg.pipeline_spec, track_visited=True)

    compiled = _compile(step, *args)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # the whole step fits one v5e's 16 GB of HBM with room to spare
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 ** 30
    # the ADC table lookup is a select over the centroids on the chip: no
    # gather of f32 table elements; the uint8 row gather of codes stays
    gathers = _pq_lookup_gathers(compiled.as_text())
    assert "u8" in gathers and "f32" not in gathers
    # the CPU lowering of the same step keeps its gather of table elements
    cpu_args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    assert "f32" in _pq_lookup_gathers(_compile(step, *cpu_args).as_text())


# the streaming deployment: 24,576 base rows, capacity for 2^14 inserts,
# pages of 6 deep-like records, flushes of 64 rows (MutableIndex._reserve's
# sizing)
STREAM_N, STREAM_CAP, STREAM_FLUSH = 24576, 24576 + 2 ** 14, 64
STREAM_NP = PAGE_SHAPES[1][1]
STREAM_PAGES = (-(-STREAM_N // STREAM_NP)
                + (-(-(STREAM_CAP - STREAM_N) // STREAM_FLUSH) + 1)
                * -(-STREAM_FLUSH // STREAM_NP))


def test_insert_candidates_compile_for_v5e(one_chip):
    """A flush's candidate search over the reserved capacity: d=96, R=64,
    a batch of flush_threshold rows, insert_L=32, width 2."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d = PAGE_SHAPES[1][2]
    compiled = _compile(
        lambda X, G, e, q: _insert_candidates(X, G, e, q, L=32, width=2),
        spec((STREAM_CAP, d), jnp.float32), spec((STREAM_CAP, R), jnp.int32),
        spec((), jnp.int32), spec((STREAM_FLUSH, d), jnp.float32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 ** 30


def test_overfetch_search_batch_compiles_for_v5e(one_chip):
    """The merged path's one kernel shape: baseline at k + overfetch (16)
    over the reserved capacity and page space of the streaming
    deployment."""
    cfg = get_preset("baseline", L=32)
    n, P, n_p, d = STREAM_CAP, STREAM_PAGES, STREAM_NP, PAGE_SHAPES[1][2]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((P, n_p), jnp.int32), spec((P, n_p, d), jnp.float32),
            spec((P, n_p, R), jnp.int32), spec((n,), jnp.int32),
            spec((n,), jnp.int32), spec((M, 256, d // M), jnp.float32),
            spec((n, M), jnp.uint8), spec((n,), jnp.bool_),
            spec((BATCH, d), jnp.float32), spec((BATCH, 1), jnp.int32),
            spec((BATCH, 1), jnp.bool_))

    def step(*a):
        return _search_batch(
            *a, k=cfg.k + 16, L=cfg.L, width=cfg.beam_width,
            max_iters=cfg.max_iters, n_p=n_p, page_search=cfg.page_search,
            dynamic_width=cfg.dynamic_width, dw_min=cfg.dw_min,
            dw_max=cfg.dw_max, pipeline=cfg.pipeline,
            spec=cfg.pipeline_spec, track_visited=True)

    compiled = _compile(step, *args)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 ** 30
    assert "u8" in _pq_lookup_gathers(compiled.as_text())


HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?\S+ = (\w+)\[.*?'
                      r'metadata=\{[^}]*?op_name="([^"]*)"')


def _pq_lookup_gathers(hlo_text):
    """Element types of the instructions that come from a `gather` under
    the `pq_lookup` scope, fused computations included."""
    found = set()
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if m and "pq_lookup" in m.group(2) \
                and m.group(2).rsplit("/", 1)[-1] == "gather":
            found.add(m.group(1))
    return found


def test_chip_smoke_refuses_a_host_without_a_tpu():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit) as refused:
        chip_smoke.check_device(rehearse=False)
    assert "'cpu'" in str(refused.value.code)
    # only an explicit rehearsal gets past the check
    assert chip_smoke.check_device(rehearse=True).platform == "cpu"
