"""Compile-only checks of every Pallas kernel for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed alongside JAX,
and ``get_topology_desc`` describes a v5e 2x2 host without one attached.
Each test lowers one kernel at a real width for one chip of it and asserts
that Mosaic accepted the kernel (``tpu_custom_call`` in the compiled HLO).
This catches what interpret mode cannot: block shapes that break the
(8, 128) tiling rule, unaligned slices, and VMEM overruns. The default
dense solver step is compiled whole at the dense cells' shapes, where
its HVP takes the branch lowered for the chip.

The topology is described inside a module-scoped fixture (never at import)
so that pytest-xdist workers all collect the same tests and only the worker
running this file loads the TPU library.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import DiscoConfig, disco
from repro.core.hvp import DenseOperator
from repro.core.losses import get_loss
from repro.glm_serve.scoring import slot_margins
from repro.kernels import ops
from repro.launch.mesh import make_mesh

# the package re-exports functions under these module names
dense = importlib.import_module("repro.kernels.glm_hvp")
sparse = importlib.import_module("repro.kernels.sparse_hvp")

D = 2048           # epsilon's d = 2,000 padded to the 512 dense tile
N = 400_384        # epsilon's n = 400,000 padded to the 512 dense tile
S = 128            # probe block of the s-step kernels (one lane width)


@pytest.fixture(scope="module")
def v5e():
    """The four chips of a described v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


DENSE = {
    "xt_u": (lambda X, u: dense.xt_u(X, u),
             lambda dt: [((D, N), dt), ((D,), jnp.float32)]),
    "x_cz": (lambda X, c, z: dense.x_cz(X, c, z),
             lambda dt: [((D, N), dt), ((N,), jnp.float32),
                         ((N,), jnp.float32)]),
    "xt_multi": (lambda X, U: dense.xt_multi(X, U),
                 lambda dt: [((D, N), dt), ((D, S), jnp.float32)]),
    "x_cz_multi": (lambda X, c, Z: dense.x_cz_multi(X, c, Z),
                   lambda dt: [((D, N), dt), ((N,), jnp.float32),
                               ((N, S), jnp.float32)]),
    "x_c_xt_u": (lambda X, c, u: dense.x_c_xt_u(X, c, u),
                 lambda dt: [((D, N), dt), ((N,), jnp.float32),
                             ((D,), jnp.float32)]),
    "x_c_xt_multi": (lambda X, c, U: dense.x_c_xt_multi(X, c, U),
                     lambda dt: [((D, N), dt), ((N,), jnp.float32),
                                 ((D, S), jnp.float32)]),
    # the solver's entry: epsilon's unpadded shape through the wrapper
    "ops.glm_hvp": (lambda X, c, u: ops.glm_hvp(X, c, u, 1e-4,
                                                 mode="native"),
                    lambda dt: [((2000, 400_000), dt),
                                ((400_000,), jnp.float32),
                                ((2000,), jnp.float32)]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(DENSE))
def test_dense_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, shapes = DENSE[name]
    _compile(fn, one_chip, *shapes(dtype))


NB, W, NCB, BR, BC = 64, 8, 48, 128, 128      # forward layout, 128x128 tiles
WT = 10                                       # transposed tile-row width

SPARSE = {
    "ell_mv": (lambda x, c, v, col: sparse.ell_mv(x, col, v, c),
               [((NB, W, BR, BC), jnp.float32), ((NCB * BC,), jnp.float32),
                ((NCB * BC,), jnp.float32), ((NB, W), jnp.int32)]),
    "ell_mm": (lambda x, c, V, col: sparse.ell_mm(x, col, V, c),
               [((NB, W, BR, BC), jnp.float32), ((NCB * BC,), jnp.float32),
                ((NCB * BC, S), jnp.float32), ((NB, W), jnp.int32)]),
    "ell_hvp": (lambda xT, c, u, col: sparse.ell_hvp(xT, col, u, c),
                [((NCB, WT, BC, BR), jnp.float32),
                 ((NCB * BC,), jnp.float32), ((NB * BR,), jnp.float32),
                 ((NCB, WT), jnp.int32)]),
    "ell_hvp_mm": (lambda xT, c, U, col: sparse.ell_hvp_mm(xT, col, U, c),
                   [((NCB, WT, BC, BR), jnp.float32),
                    ((NCB * BC,), jnp.float32),
                    ((NB * BR, S), jnp.float32), ((NCB, WT), jnp.int32)]),
    # the matvec at short 8 x 128 tiles, 40 of 7,813 column tiles a row
    "ell_matvec_8x128_tile": (
        lambda x, v, col: ops.ell_matvec(x, col, v, mode="native"),
        [((8, 40, 8, 128), jnp.float32), ((7813 * 128,), jnp.float32),
         ((8, 40), jnp.int32)]),
}


@pytest.mark.parametrize("name", list(SPARSE))
def test_ell_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = SPARSE[name]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_scoring_step_compiles_for_v5e(one_chip, dtype):
    """The scoring engine's step at ``ctr``'s shape: 64 requests of 64
    (id, value) slots gathered against d = 1,000,000 weights and the
    padding zero. No Pallas kernel: XLA's gather and row sum."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((64, 64), jnp.int32), ((64, 64), dtype),
                          ((1_000_001,), jnp.float32))]
    compiled = jax.jit(slot_margins).lower(*args).compile()
    assert "gather" in compiled.as_text()
    out = jax.eval_shape(slot_margins, *args)
    assert out.shape == (64,) and out.dtype == jnp.float32


@pytest.mark.parametrize("body", ["mv", "hvp", "hvp_two_pass"])
def test_streamed_step_compiles_on_four_chips(v5e, body):
    """One step of a streamed DiSCO-S pass on a 4-chip mesh: the chunk
    kernels run per shard inside ``shard_map`` (the compiler refuses to
    partition a Pallas call)."""
    mesh = make_mesh((4,), ("data",), devices=v5e)
    m, chunk, d = 4, 256, 1024
    nb, nbt = d // BR, chunk // BC
    tiles = {"dataT": ((m, nbt, W, BC, BR), jnp.float32),
             "colsT": ((m, nbt, W), jnp.int32),
             "data": ((m, nb, W, BR, BC), jnp.float32),
             "cols": ((m, nb, W), jnp.int32)}
    keys, fn, reduce, whole = {
        "mv": (("dataT", "colsT"), disco._chunk_mv, False, [((d,), P())]),
        "hvp": (("dataT", "colsT"), disco._chunk_hvp, True,
                [((m * 2 * chunk,), P("data")), ((d,), P())]),
        "hvp_two_pass": (("dataT", "colsT", "data", "cols"),
                         disco._chunk_hvp_two_pass, True,
                         [((m * 2 * chunk,), P("data")), ((d,), P())]),
    }[body]
    args = [jax.ShapeDtypeStruct(*tiles[k], sharding=NamedSharding(
        mesh, P("data"))) for k in keys]
    args += [jax.ShapeDtypeStruct(s, jnp.float32,
                                  sharding=NamedSharding(mesh, spec))
             for s, spec in whole]
    n_sliced = 1 if reduce else 0
    text = disco._on_shards.lower(
        np.int32(1), *args, body=fn, mesh=mesh, axis="data",
        n_stacked=len(keys), n_sliced=n_sliced, chunk=chunk, reduce=reduce,
        mode="native").compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_slot_passes_compile_for_v5e(one_chip, dtype):
    """Both slot passes at the real-sim cell's shape (20,992 x 36,224
    padded, a 1,431-row head slab, 57,984 and 66,176 chunks of 8 slots):
    the one-hot gather and the window sums, with no Pallas kernel and
    well inside one chip's memory."""
    from repro.data.sparse import SLOT_WIDTH, SlotLayout, SlotPair

    rows, cols, H = 20_992, 36_224, 1_431
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    lay = lambda c: SlotLayout(sds((c, SLOT_WIDTH), jnp.int32),
                               sds((c, SLOT_WIDTH), dtype),
                               sds((c,), jnp.int32))
    pair = SlotPair(lay(57_984), lay(66_176), sds((H, cols), dtype),
                    sds((H,), jnp.int32), (rows, cols))
    u, z = sds((rows,), jnp.float32), sds((cols,), jnp.float32)
    for fn, args, n_out in ((ops.slot_xt, (pair, u), cols),
                            (ops.slot_x, (pair, z, z), rows)):
        compiled = fn.lower(*args).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
        out = jax.eval_shape(fn, *args)
        assert out.shape == (n_out,) and out.dtype == jnp.float32


def _dense_samples_step(devices, d, n):
    """The default dense DiSCO-S Newton step (logistic, classic PCG) of a
    solver on ``devices`` over (d, n) f32 data sharded by samples,
    compiled. The solver is assembled around shapes: nothing is placed,
    and ``_build_step`` binds the shapes as the program's data."""
    m = len(devices)
    mesh = make_mesh((m,), ("data",), devices=devices)
    s = disco.DiscoSolver.__new__(disco.DiscoSolver)
    s.cfg = DiscoConfig(loss="logistic", lam=1e-3, partition="samples")
    s.loss, s.axis, s.mesh, s.m = get_loss("logistic"), "data", mesh, m
    s.d, s.n, s.tau, s._sparse = d, n, min(s.cfg.tau, n), False

    def sds(shape, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    s.X = s.X_hvp = sds((d, n), P(None, "data"))
    s.y = s.weights = sds((n,), P("data"))
    s.X_tau, s.y_tau = sds((d, s.tau), P()), sds((s.tau,), P())
    step = s._build_step()
    return step.func.lower(*step.args, sds((d,), P()),
                           sds((2,), P(), jnp.uint32)).compile()


@pytest.mark.parametrize("cell", ["epsilon", "ocr"])
def test_dense_step_reads_x_once_per_hvp(v5e, cell):
    """The default dense DiSCO-S step at the cell's shape: ``epsilon``
    (2,000 x 400,000 on one chip) and ``ocr`` (1,156 x 3,500,000 on the
    2x2 mesh). The PCG loop's one HVP is the one-pass kernel, the only
    custom call of the step; nothing X-sized is padded, copied or
    transposed, and the step's temporaries stay under 1% of X a chip."""
    devices, d, n = {"epsilon": (v5e[:1], 2000, 400_000),
                     "ocr": (v5e, 1156, 3_500_000)}[cell]
    compiled = _dense_samples_step(devices, d, n)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(rf"%dense_hvp_one_pass\S* = f32\[{d},128\]", text)
    n_loc = n // len(devices)
    assert not re.search(
        rf"= f32\[{d},{n_loc}\]\S* (copy|pad|transpose|fusion)\(", text)
    x_bytes = d * n_loc * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * x_bytes


@pytest.mark.parametrize("d,kernel", [(2000, True), (1156, True),
                                      (70_000, False)])
def test_dense_operator_dispatch_for_v5e(one_chip, d, kernel):
    """Lowered for the chip, the default dense operator's full product is
    the one-pass kernel where its panel fits, and XLA's two passes where
    one lane group of X outgrows the panel budget."""
    n = 400_000 if kernel else 4096
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((d, n), (n,), (d,))]
    text = jax.jit(lambda X, c, u: DenseOperator(X, c).apply(u)).lower(
        *args).compile().as_text()
    assert ("dense_hvp_one_pass" in text) == kernel
    assert ("tpu_custom_call" in text) == kernel
