"""The fused ADMM kernels compile natively for a TPU v5e chip.

Interpret mode, which every other kernel test uses on the CPU, cannot see
what the chip's compiler refuses: slices off the lane tiling, too much
fast memory, 64-bit index arithmetic. These tests hand the public `ops`
wrappers to the installed TPU compiler against a described (not
attached) v5e topology and check that each program holds the Mosaic
kernel (`tpu_custom_call`). Nothing runs, so nothing here says anything
about results or times.

The topology is described only inside a fixture: only one process may
load the TPU library at a time, and it keeps it until it exits. The
compiles run with x64 off, as on the chip (Mosaic refuses the 64-bit
index maps that the suite-wide x64 of `conftest.py` would trace), and
with the persistent compilation cache off, since a program compiled for
a described chip cannot be read back from it.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import Case
from repro.kernels import ops
from repro.methods import Reduction, driver, get_kernel

UPDATE = ops.coded_admm_update.__wrapped__
COMBINE = ops.coded_combine.__wrapped__


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Lower the `ops` wrappers as the chip does: native Pallas, f32."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _shapes(one_chip, op, J, n, mask, runs=()):
    def f32(*shape):
        return jax.ShapeDtypeStruct(
            (*runs, *shape), jnp.float32, sharding=one_chip
        )

    m = f32(J) if mask else None
    if op == "combine":
        return (f32(J, n), f32(J), m)
    return (f32(J, n), f32(J), f32(n), f32(n), f32(n), f32(), f32(), m)


def _assert_native(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


OPS = {"update": UPDATE, "combine": COMBINE}


@pytest.mark.parametrize("mask", [True, False], ids=["mask", "no_mask"])
@pytest.mark.parametrize("op", sorted(OPS))
def test_kernel_compiles_at_usps_width(native, one_chip, op, mask):
    """J = 3 ECNs over the usps parameter vector (p*d = 640)."""
    fn = functools.partial(OPS[op], block_n=ops.fit_block_n(640))
    _assert_native(fn, _shapes(one_chip, op, 3, 640, mask))


@pytest.mark.parametrize("op", sorted(OPS))
def test_kernel_pads_tiny_vector_to_one_lane_tile(native, one_chip, op):
    """The synthetic problem's 3-float parameter, padded to 128 lanes."""
    fn = functools.partial(OPS[op], block_n=ops.fit_block_n(3))
    _assert_native(fn, _shapes(one_chip, op, 6, 3, True))


@pytest.mark.parametrize("n", [3, 640])
def test_update_vmapped_over_runs(native, one_chip, n):
    """The batched sweep's form: J = 6, one kernel call per run, 16 runs."""
    fn = jax.vmap(functools.partial(UPDATE, block_n=ops.fit_block_n(n)))
    _assert_native(fn, _shapes(one_chip, "update", 6, n, True, runs=(16,)))


@pytest.mark.parametrize("op", sorted(OPS))
def test_kernel_compiles_at_large_n(native, one_chip, op):
    """J = 16 over a 2**20 vector in 16384-lane tiles (~1.3 MB of VMEM)."""
    fn = functools.partial(OPS[op], block_n=16_384)
    _assert_native(fn, _shapes(one_chip, op, 16, 2**20, True))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "vjp"])
def test_expert_gmm_compiles_at_moonlight_widths(native, one_chip, grad):
    """The grouped expert products of one agent's expert layer in the
    moonlight cell: 8,192 tokens x top 6 slots in a worst-case buffer,
    8 held experts of 2,048 -> 1,408 (bfloat16); with the VJP, the data
    gradient (weights transposed) and the weight gradient too."""
    lhs = jax.ShapeDtypeStruct((49152, 2048), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, 2048, 1408), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    fn = ops.expert_gmm
    if grad:
        fn = jax.value_and_grad(
            lambda a, b, g: jnp.sum(ops.expert_gmm(a, b, g).astype(jnp.float32)), (0, 1))
    text = jax.jit(fn).lower(lhs, rhs, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)
    names = ("expert_gmm", "expert_gmm_t", "expert_gmm_tgmm") if grad else ("expert_gmm",)
    assert all(f"%{name}." in text for name in names)


# An HLO value's name and dimensions; a gather's or a dynamic slice's
# operand and slice sizes.
_VALUE = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]")
_READ = re.compile(
    r"(?:gather|dynamic-slice)\(%([\w.-]+)[,)].*?slice_sizes=\{([\d,]*)\}"
)


def _pool_reads(text, rows):
    """Consecutive rows taken by each gather or dynamic slice of a pool of
    at least ``rows`` rows: along an axis of that many rows or more, or
    along a (tiles, 128) pair of axes that lays that many rows on lanes."""
    dims = {
        m[1]: [int(n) for n in m[2].split(",") if n]
        for m in _VALUE.finditer(text)
    }
    reads = []
    for m in _READ.finditer(text):
        shape, sizes = dims[m[1]], [int(n) for n in m[2].split(",")]
        for ax, n in enumerate(shape):
            if n >= rows and n != 128:
                reads.append(sizes[ax])
            elif n == 128 and ax and shape[ax - 1] * 128 >= rows:
                reads.append(sizes[ax - 1] * sizes[ax])
    return reads


def _f32(a, sharding):
    dt = {np.float64: jnp.float32, np.int64: jnp.int32}.get(
        np.asarray(a).dtype.type, np.asarray(a).dtype
    )
    return jax.ShapeDtypeStruct(np.shape(a), dt, sharding=sharding)


@pytest.mark.parametrize(
    "dataset, N, K, M",
    [("synthetic", 10, 6, 360), ("usps", 10, 3, 90)],
    ids=["fleet_synth_p3", "usps_pd640"],
)
def test_batched_step_fetches_windows_not_rows(
    native, one_chip, dataset, N, K, M
):
    """The batched csI-ADMM step with a streaming reduction, as the sweep
    engine dispatches it: 4 runs of mixed S (1 and 2, so MU is the S = 1
    bound) over 2 shared datasets, float32. At the fleet cell's shape
    (N = 10, b = 5,040, p = 3, K = 6, MU = 30) and at the USPS width
    (p*d = 640, K = 3), every read of the data pool takes a window of at
    least MU rows: none fetches a single row."""
    kernel = get_kernel("csI-ADMM")
    probs, nets, cfgs = [], [], []
    for seed in (0, 1):
        prob = allocate(DATASETS[dataset](seed), N, K)
        net = make_network(N, 0.5, seed=seed)
        for S in (1, 2):
            case = Case(
                method="csI-ADMM", dataset=dataset, N=N, K=K, M=M,
                scheme="cyclic", S=S, seed=seed, iters=8,
            )
            probs.append(prob)
            nets.append(net)
            cfgs.append(kernel.config(case))
    preps = [kernel.prepare(p, n, c, 8) for p, n, c in zip(probs, nets, cfgs)]
    statics = {
        **preps[0].statics, **driver._statics_bound(kernel, probs, cfgs, 8)
    }
    batch = driver._stack_runs(preps, True, 1)
    assert batch.index is not None  # the datasets ship as shared tables
    spec = Reduction(fields=("accuracy", "test_error"), budgets=(0.5,))
    fn = driver._batched(
        driver._compose_reduced(kernel, driver._statics_key(statics), spec),
        batch.shared,
        kernel.row_consts,
    )
    args = jax.tree.map(
        lambda a: _f32(a, one_chip), (batch.tables, *batch.per_run)
    )
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    MU = statics["MU"]
    reads = _pool_reads(text, N * probs[0].b)
    assert reads, "no read of the data pool found in the program"
    assert min(reads) >= MU, f"rows read {sorted(set(reads))} (MU = {MU})"
