"""Every Pallas kernel of the extraction path compiles for a TPU v5e.

Interpret-mode parity says nothing about Mosaic's block-shape rules,
lowerings or on-chip memory limits; these tests hand each kernel entry
point, at the sizes the pipeline really launches, to the TPU compiler
for a described (not attached) v5e chip.  A refusal here is a refusal
the first run on the chip would have hit.

The topology is described inside a module-scoped fixture, never at
import, so every test worker collects the same tests and only the one
that runs this file loads the TPU compiler.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import executor, plan
from repro.data import synthetic
from repro.kernels import compact as ck
from repro.kernels import diameter as dk
from repro.kernels import firstorder as fok
from repro.kernels import glcm as gk
from repro.kernels import marching_cubes as mck
from repro.kernels import ops
from repro.runtime import autotune as at

# Table 2's largest ROI (236,588 vertices) buckets to M = 2^18
M_SIZES = (4096, 1 << 18)
# the padded-volume bucket of Table 2's largest image, 322 x 126 x 219
MC_SHAPE = plan.shape_bucket(dict(synthetic.TABLE2_CASES)["00001-1"])
FAMILY_SHAPE = (8, 128, 128, 128)  # B = 8 cases of a 128^3 bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel
    return compiled


F32 = jnp.float32


@pytest.mark.parametrize("M", M_SIZES)
@pytest.mark.parametrize("block", at.DEFAULT_BLOCKS)
@pytest.mark.parametrize("variant", at.DEFAULT_VARIANTS)
def test_diameter_autotune_candidates_compile(one_chip, variant, block, M):
    fn = functools.partial(dk.max_diameters_sq_pallas, block=block,
                           variant=variant)
    _compile(one_chip, fn, ((M, 3), F32), ((M,), F32))


@pytest.mark.parametrize("block", at.DEFAULT_BLOCKS)
@pytest.mark.parametrize("variant", ("naive", "fused", "tri"))
def test_diameter_full_grid_variants_compile(one_chip, variant, block):
    fn = functools.partial(dk.max_diameters_sq_pallas, block=block,
                           variant=variant)
    _compile(one_chip, fn, ((4096, 3), F32), ((4096,), F32))


@pytest.mark.parametrize("block", at.DEFAULT_MC_BLOCKS, ids=str)
def test_mc_bricks_compile_at_table2_bucket(one_chip, block):
    fn = functools.partial(mck.mc_volume_area_pallas, block=block,
                           chunk=at.DEFAULT_MC_CONFIG.chunk)
    _compile(one_chip, fn, (MC_SHAPE, F32), ((), F32), ((3,), F32))


def test_mc_tiled_partials_compile(one_chip):
    cfg = at.DEFAULT_MC_CONFIG
    slab = (MC_SHAPE[0], MC_SHAPE[1], 4 * cfg.block[2] + 1)
    _compile(one_chip, _tiled_partials(cfg.block, MC_SHAPE),
             (slab, F32), ((), F32), ((3,), F32), ((), F32))


def _tiled_partials(block, full_shape):
    def fn(slab, iso, spacing, z0):
        return mck.mc_brick_partials_pallas(
            slab, iso, spacing, full_shape=full_shape, z_cell_offset=z0,
            block=block, chunk=at.DEFAULT_MC_CONFIG.chunk)
    return fn


MC_WORK_CASES = (
    [("in-core", b, MC_SHAPE) for b in at.DEFAULT_MC_BLOCKS]
    + [("batch4", at.DEFAULT_MC_CONFIG.block, MC_SHAPE),
       ("in-core", at.DEFAULT_MC_CONFIG.block, (256, 256, 256))]
    + [("tiled", b, (MC_SHAPE[0], MC_SHAPE[1], 8 * b[2] + 1))
       for b in at.DEFAULT_MC_BLOCKS]
)


@pytest.mark.parametrize("kind,block,shape", MC_WORK_CASES, ids=str)
def test_mc_work_bound_covers_compiled_temporaries(one_chip, kind, block,
                                                   shape):
    # the window and tile budgets count mck.work_bytes for one MC call;
    # the compiler's own temporaries must stay within it, and a batch
    # (cases mapped one at a time) must not multiply them
    chunk = at.DEFAULT_MC_CONFIG.chunk
    if kind == "tiled":
        compiled = _compile(one_chip, _tiled_partials(block, MC_SHAPE),
                            (shape, F32), ((), F32), ((3,), F32), ((), F32))
    elif kind == "batch4":
        fn = functools.partial(mck.mc_volume_area_batch_pallas, block=block,
                               chunk=chunk)
        compiled = _compile(one_chip, fn, ((4,) + shape, F32), ((), F32),
                            ((4, 3), F32))
    else:
        fn = functools.partial(mck.mc_volume_area_pallas, block=block,
                               chunk=chunk)
        compiled = _compile(one_chip, fn, (shape, F32), ((), F32),
                            ((3,), F32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp <= mck.work_bytes(shape, block) <= mck.work_bytes(shape)


@pytest.mark.parametrize("block", at.DEFAULT_COMPACT_BLOCKS)
def test_compaction_compiles_at_largest_bucket(one_chip, block):
    M, cap = 1 << 18, 1 << 16
    fn = functools.partial(ck.compact_batch_pallas, cap=cap, block=block)
    _compile(one_chip, fn, ((4, M, 3), F32), ((4, M), jnp.bool_))


def _compact_cap_text(one_chip, shape, cap, compact=None):
    """HLO text of pass 0's ``_compact_cap`` compiled at a mask bucket."""
    mask = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    fields = jax.eval_shape(
        lambda m: ops.vertex_fields(m, 0.5, (1.0, 1.0, 1.0)), mask)
    fields = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        fields)
    if compact is None:
        fn = executor._compact_cap
    else:
        fn = jax.jit(lambda f, cap: compact(f, cap)[:2], static_argnums=1)
    return fn.lower(fields, cap).compile().as_text()


SORT_OP = re.compile(r"\ssort\(")  # an HLO sort instruction


def test_sort_op_pattern_finds_an_argsort(one_chip):
    """The pattern below sees the stable argsort pass 0 once ran."""
    def argsort_compact(f, cap):
        act = jnp.concatenate([a.reshape(-1) for a in (f.ax, f.ay, f.az)])
        return act[jnp.argsort(~act, stable=True)[:cap]], act
    text = _compact_cap_text(one_chip, (12, 10, 8), 64, argsort_compact)
    assert SORT_OP.search(text)


@pytest.mark.parametrize("shape", [(320, 128, 224), MC_SHAPE],
                         ids=lambda s: "x".join(map(str, s)))
def test_pass0_compaction_compiles_without_a_sort(one_chip, shape):
    """Pass 0's vertex compaction at a kidney bucket and M = 2^18 holds no
    sort: the O(L log L) argsort over every edge slot stays gone."""
    text = _compact_cap_text(one_chip, shape, 1 << 18)
    assert not SORT_OP.search(text)


@pytest.mark.parametrize("block", at.DEFAULT_FIRSTORDER_BLOCKS)
def test_firstorder_compiles(one_chip, block):
    fn = functools.partial(fok.firstorder_packed_batch_pallas, block=block)
    _compile(one_chip, fn, (FAMILY_SHAPE, F32), (FAMILY_SHAPE, F32))


def _chunk_sums(text):
    """Operand shapes of the f32 sums over one chunk in a scan body."""
    types = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = f32\[([\d,]*)\]\{",
                            text, re.M))
    shapes = [tuple(int(d) for d in types[op].split(",")) for op in
              re.findall(r" reduce\(%([^,]+),.*/while/body/", text)
              if op in types]
    return [s for s in shapes if math.prod(s) == fok.CANON_CHUNK]


@pytest.mark.parametrize("fold", ["batch", "tiled"])
def test_firstorder_xla_folds_sum_chunks_as_the_kernel_does(one_chip, fold):
    """Every sum over one chunk in the XLA folds reads a (1, CANON_CHUNK)
    operand, the layout in which XLA's reduction order matches the Pallas
    kernel's on a v5e.  A sum over a flat (CANON_CHUNK,) operand takes
    another order there, and rounds the m2 lane apart from the kernel's
    (``kernels/firstorder`` module docstring)."""
    if fold == "batch":
        fn = fok.firstorder_packed_batch_ref
        shapes = [((4, 48, 48, 32), F32)] * 2
    else:
        fn = fok.fold_packed_chunks
        shapes = [((72, fok.CANON_CHUNK), F32)] * 2 + [((), F32)] * 2
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    sums = _chunk_sums(fn.lower(*args).compile().as_text())
    # count, sum, sum of squares, m2
    assert sums == [(1, fok.CANON_CHUNK)] * 4, sums


@pytest.mark.parametrize("block,n_bins", [
    *(pytest.param(b, gk.N_BINS, id=str(b)) for b in at.DEFAULT_GLCM_BLOCKS),
    pytest.param(at.DEFAULT_GLCM_CONFIG.block, 64, id="default-bins64"),
])
def test_glcm_compiles(one_chip, block, n_bins):
    fn = functools.partial(gk.glcm_matrix_batch_pallas, block=block,
                           n_bins=n_bins)
    _compile(one_chip, fn, (FAMILY_SHAPE, F32), (FAMILY_SHAPE, F32))
