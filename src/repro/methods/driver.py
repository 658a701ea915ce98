"""Execution backends derived from a MethodKernel (DESIGN.md §8, §9).

``run_serial`` executes one run as ``lax.scan(kernel.step)``;
``run_batch`` executes R runs as ``vmap`` of the *same* composed scan —
the batched engine is a pure performance transform of the serial path
because both call literally the same step function. ``run_sharded`` lays
the batched runs axis of that same vmapped scan out over a
`jax.sharding.Mesh` of every visible device (``shard_map`` over a 1-D
runs mesh, NamedSharding-placed inputs, buffer donation on accelerator
backends, automatic chunking when a grid exceeds the per-device memory
budget), falling back structurally to the single-device vmap when only
one device is visible (DESIGN.md §9). A fourth backend, the TPU mesh runtime
(`repro.distributed.consensus`, DESIGN.md §3), shares the algorithmic
core but owns its sharding-aware state layout.

Jitted executables are cached per kernel, statics and (batched tiers)
const-table layout (`_Stacked`), on top of the persistent XLA
compilation cache enabled by `repro.experiments.sweep`.
"""

from __future__ import annotations

import os
from functools import lru_cache
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.admm import Trace
from repro.core.graph import Network
from repro.core.problems import LeastSquaresProblem
from repro.distributed.sharding import AxisLayout, batch_specs

from .base import MethodKernel, Prepared, TableRow
from .reductions import Reduction

__all__ = ["run_serial", "run_batch", "run_sharded"]


def _statics_key(statics: dict) -> tuple:
    return tuple(sorted(statics.items()))


def _compose(kernel: MethodKernel, statics_key: tuple):
    """setup -> init -> scan(step) -> final as ONE pure run function."""
    statics = dict(statics_key)

    def run(consts, steps):
        aux = kernel.setup(consts, statics)
        state = kernel.init(aux, statics)

        def body(s, inp):
            return kernel.step(s, inp, aux, statics)

        xs = steps if steps else None
        length = None if steps else statics["iters"]
        state, metrics = jax.lax.scan(body, state, xs, length=length)
        x, z = kernel.final(state, aux, statics)
        return x, z, metrics

    return run


def _compose_reduced(
    kernel: MethodKernel, statics_key: tuple, spec: Reduction
):
    """setup -> init -> scan(step + reduction fold) -> finalize (§12).

    Same step function as `_compose`, but the per-iteration metrics feed
    a fixed-size `Reduction` carry instead of being stacked as scan
    outputs, and the cumulative sim_time/comm_cost clock rides along as
    the LAST per-step input (increments appended by `_clock_steps`, so
    kernels' positional ``inp`` indices are untouched by the ``[:-1]``
    slice). Output is the flat summary dict — O(spec), not O(iters).
    """
    statics = dict(statics_key)

    def run(consts, steps):
        aux = kernel.setup(consts, statics)
        state = kernel.init(aux, statics)
        red0 = spec.init_carry(steps[-1].dtype)

        def body(carry, inp):
            s, red = carry
            s, metrics = kernel.step(s, inp[:-1], aux, statics)
            return (s, spec.update_carry(red, metrics, inp[-1])), None

        (state, red), _ = jax.lax.scan(body, (state, red0), steps)
        out = spec.finalize_carry(red)
        if spec.final_x:
            out["final_x"], out["final_z"] = kernel.final(
                state, aux, statics
            )
        return out

    return run


def _clock_steps(prep: Prepared) -> np.ndarray:
    """(iters, 2) per-step [d_sim_time, d_comm] increments of the host
    clocks, ordered as `repro.methods.reductions.CLOCK_AXES`."""
    return np.stack(
        [
            np.diff(prep.sim_time, prepend=0.0),
            np.diff(np.asarray(prep.comm, dtype=np.float64), prepend=0.0),
        ],
        axis=1,
    )


def _batched(run, shared: Tuple[bool, ...], rows: Tuple[int, ...] = ()):
    """vmap of a composed run over a `_Stacked` group's arguments.

    Each run's consts tuple is rebuilt on the device: at the ``shared``
    positions its row ``index`` of the table, elsewhere its own stacked
    const. The rows are taken once, before the scan, except at the
    positions in ``rows`` (the kernel's `MethodKernel.row_consts`): there
    the run gets a `TableRow`, the whole table and its index, and reads
    the table where it needs it. A group that shares nothing takes the
    plain vmap: (consts, steps), no tables, no index."""
    if not any(shared):
        return jax.vmap(run)
    if not all(shared[pos] for pos in rows):
        rows = ()  # a kernel gets its row_consts all in one form

    def fn(tables, index, consts, steps):
        t, c = iter(tables), iter(consts)
        full, axes = [], []
        for pos, s in enumerate(shared):
            if not s:
                full.append(next(c))
                axes.append(0)
            elif pos in rows:
                full.append(TableRow(next(t), index))
                axes.append(TableRow(None, 0))
            else:
                full.append(next(t)[index])
                axes.append(0)
        return jax.vmap(run, in_axes=(tuple(axes), 0))(tuple(full), steps)

    return fn


@lru_cache(maxsize=None)
def _serial_fn(kernel: MethodKernel, statics_key: tuple):
    return jax.jit(_compose(kernel, statics_key))


@lru_cache(maxsize=None)
def _batch_fn(
    kernel: MethodKernel, statics_key: tuple, shared: Tuple[bool, ...]
):
    return jax.jit(
        _batched(_compose(kernel, statics_key), shared, kernel.row_consts)
    )


@lru_cache(maxsize=None)
def _serial_reduced_fn(
    kernel: MethodKernel, statics_key: tuple, spec: Reduction
):
    return jax.jit(_compose_reduced(kernel, statics_key, spec))


@lru_cache(maxsize=None)
def _batch_reduced_fn(
    kernel: MethodKernel,
    statics_key: tuple,
    spec: Reduction,
    shared: Tuple[bool, ...],
):
    return jax.jit(
        _batched(
            _compose_reduced(kernel, statics_key, spec), shared,
            kernel.row_consts,
        )
    )


def _to_trace(prep: Prepared, x, z, metrics) -> Trace:
    acc, test_err, z_err = metrics
    return Trace(
        accuracy=np.asarray(acc),
        test_error=np.asarray(test_err),
        comm_cost=prep.comm,
        sim_time=prep.sim_time,
        z_err=np.asarray(z_err),
        final_x=np.asarray(x),
        final_z=np.asarray(z),
    )


def run_serial(
    kernel: MethodKernel,
    problem: LeastSquaresProblem,
    net: Network,
    cfg,
    iters: int,
    reductions: Optional[Reduction] = None,
):
    """One run: jitted ``lax.scan`` of the kernel's step function.

    Returns a full `Trace`, or — with ``reductions`` — the run's flat
    summary dict of numpy arrays (DESIGN.md §12).
    """
    prep = kernel.prepare(problem, net, cfg, iters)
    statics = {**prep.statics, **prep.max_statics}
    consts = tuple(jnp.asarray(c) for c in prep.consts)
    if reductions is not None:
        fn = _serial_reduced_fn(kernel, _statics_key(statics), reductions)
        steps = tuple(jnp.asarray(s) for s in prep.steps) + (
            jnp.asarray(_clock_steps(prep)),
        )
        return {k: np.asarray(v) for k, v in fn(consts, steps).items()}
    fn = _serial_fn(kernel, _statics_key(statics))
    x, z, metrics = fn(
        consts, tuple(jnp.asarray(s) for s in prep.steps)
    )
    return _to_trace(prep, x, z, metrics)


class _Stacked(NamedTuple):
    """A dispatch group's host inputs, the runs on the leading axis.

    A const that several runs hold as the same host object (the grid
    points of one seed share its `LeastSquaresProblem`) ships once, as a
    row of a table; ``index`` gives each run its row, and `_batched`
    takes the rows on the device. ``shared`` marks the positions of
    `Prepared.consts` that are tables: a static of the executable. Where
    no position is shared there are no tables and no index.
    """

    shared: Tuple[bool, ...]
    tables: Tuple[np.ndarray, ...]  # (U, ...) each: the shared positions
    index: Optional[np.ndarray]  # (R,) int32: each run's row of the tables
    consts: Tuple[np.ndarray, ...]  # (R, ...) each: the other positions
    steps: Tuple[np.ndarray, ...]  # (R, iters, ...) each

    @property
    def per_run(self) -> tuple:
        """The arguments with a runs axis: what chunks slice and pad."""
        own = (self.consts, self.steps)
        return own if self.index is None else (self.index, *own)

    @property
    def args(self) -> tuple:
        """The batched executables' arguments, in their order."""
        if self.index is None:
            return self.per_run
        return (self.tables, *self.per_run)


def _stack_consts(per_run: Sequence[Sequence], copies: int = 1):
    """(shared, tables, index, consts) of the runs' const tuples.

    A position is shared where the runs hold fewer distinct objects than
    there are runs, by identity: free, and exact. One index serves every
    shared position: a row per distinct combination of their objects.
    Each table ships ``copies`` times (once per device of the sharded
    tier), against one row per run padded to a multiple of ``copies``.
    Where the tables ship no fewer rows than that, every position stacks
    per run, with no table and no index."""
    R, n = len(per_run), len(per_run[0])
    shared = tuple(len({id(r[i]) for r in per_run}) < R for i in range(n))
    rows: Dict[tuple, int] = {}
    index = np.array(
        [
            rows.setdefault(
                tuple(id(c) for c, s in zip(r, shared) if s), len(rows)
            )
            for r in per_run
        ],
        dtype=np.int32,
    )
    if len(rows) * copies >= -(-R // copies) * copies or not any(shared):
        return (False,) * n, (), None, _stack(per_run)
    # Rows are numbered in order of first appearance.
    firsts = [per_run[i] for i in np.unique(index, return_index=True)[1]]
    tables = _stack([[c for c, s in zip(r, shared) if s] for r in firsts])
    consts = _stack([[c for c, s in zip(r, shared) if not s] for r in per_run])
    return shared, tables, index, consts


def _stack_batch(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    clock: bool = False,
    copies: int = 1,
) -> Tuple[List[Prepared], dict, _Stacked]:
    """Prepare R runs and stack them on a leading runs axis (host-side).

    All runs must share the kernel's static signature; ``max_statics``
    (e.g. the masked window bound MU) are reconciled with ``max`` so runs
    whose *runtime* value differs (mixed straggler tolerance S in a fig5
    grid) still share the trace. Raises ValueError on mixed statics —
    `repro.experiments.sweep.run_sweep` groups by signature first. With
    ``clock`` the stacked `_clock_steps` ride along as the last step input
    (the streaming tier's layout, `_compose_reduced`). Consts that runs
    share by identity become tables shipped ``copies`` times
    (`_stack_consts`).
    """
    R = len(problems)
    if not (len(nets) == len(cfgs) == R):
        raise ValueError("problems, nets, cfgs must have equal length")
    sigs = {
        kernel.static_signature(p, c, iters)
        for p, c in zip(problems, cfgs)
    }
    if len(sigs) != 1:
        raise ValueError(
            f"batch mixes {len(sigs)} static signatures; group runs by "
            f"{kernel.name} static_signature() first"
        )

    with jax.profiler.TraceAnnotation("repro.sweep.prepare", runs=R):
        preps = [
            kernel.prepare(p, n, c, iters)
            for p, n, c in zip(problems, nets, cfgs)
        ]
    statics = dict(preps[0].statics)
    if any(pr.statics != statics for pr in preps[1:]):
        raise ValueError("equal signatures produced unequal statics")
    for key in preps[0].max_statics:
        statics[key] = max(pr.max_statics[key] for pr in preps)

    with jax.profiler.TraceAnnotation("repro.sweep.stack"):
        shared, tables, index, consts = _stack_consts(
            [pr.consts for pr in preps], copies
        )
        steps = _stack([pr.steps for pr in preps])
        if clock:
            steps += (np.stack([_clock_steps(pr) for pr in preps]),)
    return preps, statics, _Stacked(shared, tables, index, consts, steps)


def _stack(per_run: Sequence[Sequence]) -> Tuple[np.ndarray, ...]:
    """Stack each position of the runs' input tuples on a runs axis."""
    return tuple(
        np.stack([np.asarray(r[i]) for r in per_run])
        for i in range(len(per_run[0]))
    )


def _nbytes(tree) -> int:
    """Host bytes handed to the device: the transfer span's ``bytes``."""
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _transfer_span(per_run, tables=(), sharing: bool = False):
    """The transfer span of a dispatch's host inputs: ``runs``, the rows
    of ``per_run``; ``tables``, the rows of ``tables`` shipped (each run
    ships its own data where its group is not ``sharing``; a later chunk
    of one that is ships none); ``bytes``, the host bytes of both."""
    runs = len(jax.tree.leaves(per_run)[0])
    n = len(tables[0]) if tables else 0 if sharing else runs
    return jax.profiler.TraceAnnotation(
        "repro.sweep.transfer", runs=runs, tables=n,
        bytes=_nbytes((tables, per_run)),
    )


def _unstack_traces(preps: List[Prepared], x, z, metrics) -> List[Trace]:
    acc, test_err, z_err = metrics
    out = [np.asarray(o) for o in (x, z, acc, test_err, z_err)]
    return [
        _to_trace(pr, out[0][r], out[1][r], (out[2][r], out[3][r], out[4][r]))
        for r, pr in enumerate(preps)
    ]


def run_batch(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    reductions: Optional[Reduction] = None,
):
    """R runs as ONE vmapped scan — one jit trace, one device dispatch.

    Returns per-run `Trace`s, or — with ``reductions`` — one dict of
    numpy arrays with a leading runs axis (DESIGN.md §12).
    """
    preps, statics, batch = _stack_batch(
        kernel, problems, nets, cfgs, iters, clock=reductions is not None
    )
    with _transfer_span(batch.per_run, batch.tables):
        args = jax.tree.map(jnp.asarray, batch.args)
    key = _statics_key(statics)
    with jax.profiler.TraceAnnotation("repro.sweep.execute"):
        if reductions is not None:
            fn = _batch_reduced_fn(kernel, key, reductions, batch.shared)
            return {k: np.asarray(v) for k, v in fn(*args).items()}
        x, z, metrics = _batch_fn(kernel, key, batch.shared)(*args)
        return _unstack_traces(preps, x, z, metrics)


# --------------------------------------------------------------------------
# Mesh-sharded batch execution (DESIGN.md §9)
# --------------------------------------------------------------------------

# Per-device working-set budget for one sharded dispatch, in MiB. The
# chunking rule is deliberately coarse (inputs + outputs + one 2x slack
# factor for XLA temporaries); it only needs to keep a huge grid from
# OOMing a device, not to model the allocator.
_MEM_BUDGET_ENV = "REPRO_SHARD_MEM_MB"
_DEFAULT_MEM_MB = 4096


def _runs_mesh() -> Mesh:
    """1-D device mesh over the runs axis (trailing size-1 model axis so
    `repro.distributed.sharding.AxisLayout` spec inference applies)."""
    devs = np.array(jax.devices()).reshape(-1, 1)
    return Mesh(devs, ("runs", "model"))


@lru_cache(maxsize=None)
def _sharded_fn(
    kernel: MethodKernel,
    statics_key: tuple,
    D: int,
    shared: Tuple[bool, ...],
    n_steps: int,
    donate: bool,
):
    """jit(shard_map(vmap(compose))) over the runs axis of a 1-D mesh.

    shard_map (not bare NamedSharding propagation) because the step's
    Pallas `coded_admm_update` has no SPMD partitioning rule: under
    GSPMD, XLA walls the op off and reshards its operands every scan
    iteration (measured ~50x slower); under shard_map each device runs
    the whole vmapped scan on its local R/D runs and the Pallas call
    never sees a partitioned operand. check_vma=False for the same
    reason (pallas_call has no replication rule). Nothing in the scan
    crosses the runs axis, so per-run math — and the outputs — are
    bitwise identical to the single-device vmap.

    Takes a `_Stacked` group's arguments: the tables (if any) replicated
    on every device, the index and the other inputs split on the runs
    axis. Every chunk reuses the tables, so only the per-run inputs are
    donated.
    """
    mesh = _runs_mesh()
    assert mesh.devices.shape[0] == D  # cache key consistency
    runs = P("runs")
    spec = (
        tuple(runs for s in shared if not s),
        tuple(runs for _ in range(n_steps)),
    )
    if any(shared):
        spec = (tuple(P() for s in shared if s), runs, *spec)
    out_spec = (runs, runs, (runs, runs, runs))
    fn = jax.shard_map(
        _batched(_compose(kernel, statics_key), shared, kernel.row_consts),
        mesh=mesh,
        in_specs=spec,
        out_specs=out_spec,
        check_vma=False,
    )
    first = int(any(shared))  # the tables, argument 0, are not donated
    return jax.jit(
        fn, donate_argnums=tuple(range(first, len(spec))) if donate else ()
    )


@lru_cache(maxsize=None)
def _sharded_reduced_fn(
    kernel: MethodKernel,
    statics_key: tuple,
    spec: Reduction,
    D: int,
    n_consts: int,
    n_steps: int,
    donate: bool,
):
    """jit(shard_map(vmap(compose_reduced))) — the streaming sharded tier.

    Same mesh/shard_map rationale as `_sharded_fn`; the single bare
    ``P("runs")`` out_spec applies as a prefix to every leaf of the
    summary dict (each leaf has a leading vmapped runs axis)."""
    mesh = _runs_mesh()
    assert mesh.devices.shape[0] == D
    in_spec = (
        tuple(P("runs") for _ in range(n_consts)),
        tuple(P("runs") for _ in range(n_steps + 1)),  # +1: clock steps
    )
    fn = jax.shard_map(
        jax.vmap(_compose_reduced(kernel, statics_key, spec)),
        mesh=mesh,
        in_specs=in_spec,
        out_specs=P("runs"),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


def _bytes_per_run(batch: _Stacked, statics: dict) -> Tuple[int, int]:
    """Estimated device footprint: (bytes per run, bytes per device).

    A run holds its stacked inputs, its rows of the tables as taken on
    the device, and its scan outputs; every device holds the tables once.
    """
    R = len(jax.tree.leaves(batch.per_run)[0])
    rows = sum(t.nbytes // len(t) for t in batch.tables)
    in_bytes = _nbytes(batch.per_run) // R + rows
    iters = int(statics.get("iters", 1))
    # x/z outputs mirror the largest const (the data block); metrics are
    # 3 float traces of length iters.
    out_bytes = 3 * iters * 8 + rows + _nbytes(batch.consts) // R
    return max(in_bytes + out_bytes, 1), _nbytes(batch.tables)


def _chunk_runs(
    R_pad: int, D: int, per_run_bytes: int, shared_bytes: int = 0
) -> int:
    """Largest run count per dispatch within the per-device budget,
    a multiple of the device count D (so every chunk shards evenly).
    ``shared_bytes`` sit on every device whatever the chunk."""
    budget = int(os.environ.get(_MEM_BUDGET_ENV, _DEFAULT_MEM_MB)) * 2**20
    # 2x slack for temporaries
    fit = (max(budget - shared_bytes, 0) * D) // (2 * per_run_bytes)
    chunk = max(D, (fit // D) * D)
    return min(chunk, R_pad)


def _run_reduced_chunked(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    spec: Reduction,
) -> Dict[str, np.ndarray]:
    """Streaming sharded execution with LAZY per-chunk prepare (§12).

    The eager path prepares and stacks all R runs before dispatching —
    host memory O(R x iters) even though the device outputs are O(R).
    Here runs are prepared only when their chunk dispatches, so peak host
    memory is O(chunk x iters) + O(R x spec): the chunk size shrinks as
    per-run schedules grow (`_chunk_runs` on the prepared bytes of run
    0), which is what keeps fleet-scale RSS flat in ``iters``
    (EXPERIMENTS.md 'Fleet scale'). Requires the kernel's
    `max_statics_bound` to be exact enough that every chunk reconciles
    under ONE set of jit statics — one trace, one executable, chunk
    count dispatches.
    """
    D = len(jax.devices())
    sigs = {
        kernel.static_signature(p, c, iters)
        for p, c in zip(problems, cfgs)
    }
    if len(sigs) != 1:
        raise ValueError(
            f"batch mixes {len(sigs)} static signatures; group runs by "
            f"{kernel.name} static_signature() first"
        )
    bound: Dict[str, int] = {}
    for p, c in zip(problems, cfgs):
        for key, val in kernel.max_statics_bound(p, c, iters).items():
            bound[key] = max(bound.get(key, 0), int(val))

    # One probe prepare: fixes the shared statics and sizes the chunks.
    with jax.profiler.TraceAnnotation("repro.sweep.prepare", runs=1):
        prep0 = kernel.prepare(problems[0], nets[0], cfgs[0], iters)
    if set(prep0.max_statics) != set(bound):
        raise ValueError(
            f"{kernel.name}.max_statics_bound() keys {sorted(bound)} != "
            f"prepared max_statics keys {sorted(prep0.max_statics)}; "
            "implement the bound hook for chunked streaming execution"
        )
    statics = {**prep0.statics, **bound}
    per_run = (
        sum(np.asarray(a).nbytes for a in prep0.consts + prep0.steps)
        + _clock_steps(prep0).nbytes
    )
    R = len(problems)
    mesh = _runs_mesh()
    layout = AxisLayout(mesh, data=("runs",), model="model")
    donate = jax.default_backend() in ("tpu", "gpu")
    fn = _sharded_reduced_fn(
        kernel, _statics_key(statics), spec, D,
        len(prep0.consts), len(prep0.steps), donate,
    )
    del prep0  # the probe's schedules are re-prepared with its chunk

    chunk = _chunk_runs(-(-R // D) * D, D, max(per_run, 1))
    outs: List[Dict[str, np.ndarray]] = []
    for lo in range(0, R, chunk):
        hi = min(lo + chunk, R)
        with jax.profiler.TraceAnnotation("repro.sweep.prepare", runs=hi - lo):
            preps = [
                kernel.prepare(p, n, c, iters)
                for p, n, c in zip(
                    problems[lo:hi], nets[lo:hi], cfgs[lo:hi]
                )
            ]
        for pr in preps:
            if pr.statics != _shared_statics(statics, pr):
                raise ValueError(
                    "equal signatures produced unequal statics"
                )
            for key, val in pr.max_statics.items():
                if int(val) > statics[key]:
                    raise ValueError(
                        f"{kernel.name}.max_statics_bound() under-bounds "
                        f"{key}: prepared {val} > bound {statics[key]}"
                    )
        n = hi - lo
        with jax.profiler.TraceAnnotation("repro.sweep.stack"):
            csl = _pad_runs(_stack([pr.consts for pr in preps]), n, D)
            ssl = _pad_runs(
                _stack([pr.steps for pr in preps])
                + (np.stack([_clock_steps(pr) for pr in preps]),),
                n, D,
            )
        del preps
        _, (put_c, put_s) = _put_sharded((csl, ssl), mesh, layout)
        del csl, ssl  # the chunk's host copies die before the next one
        with jax.profiler.TraceAnnotation("repro.sweep.execute"):
            out = fn(put_c, put_s)
            outs.append({k: np.asarray(v)[:n] for k, v in out.items()})
    return {
        k: np.concatenate([o[k] for o in outs]) for k in outs[0]
    }


def _pad_runs(tree, n: int, D: int):
    """Pad the n-run axis of every array in ``tree`` to a multiple of D by
    repeating the last run (its outputs are sliced off after the
    dispatch)."""
    pad = -(-n // D) * D - n
    if not pad:
        return tree
    return jax.tree.map(
        lambda a: np.concatenate([a, np.repeat(a[-1:], pad, axis=0)]), tree
    )


def _put_sharded(
    per_run, mesh: Mesh, layout: AxisLayout,
    tables: Tuple[np.ndarray, ...] = (), sharing: bool = False,
):
    """Place stacked host runs (padding included) on the runs mesh and
    ``tables`` replicated on every device, in a transfer span
    (`_transfer_span`). Returns (tables, per_run) placed."""
    everywhere = NamedSharding(mesh, P())
    with _transfer_span(per_run, tables, sharing):
        return (
            tuple(jax.device_put(t, everywhere) for t in tables),
            jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(mesh, batch_specs(a, layout))
                ),
                per_run,
            ),
        )


def _shared_statics(statics: dict, prep: Prepared) -> dict:
    """The statics a chunked run must agree on: everything but the
    max-reconciled keys (whose runtime values legitimately differ)."""
    return {
        k: v for k, v in statics.items() if k not in prep.max_statics
    }


def run_sharded(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    reductions: Optional[Reduction] = None,
):
    """R runs vmapped AND laid out over a device mesh on the runs axis.

    The computation is literally `run_batch`'s vmapped scan, wrapped in
    `shard_map` over a 1-D `Mesh` of all visible devices: each device
    executes the scan on its local R/D runs (see `_sharded_fn` for why
    shard_map rather than GSPMD propagation). Inputs are pre-placed with
    `NamedSharding`s inferred by `repro.distributed.sharding.batch_specs`
    so entry into the jitted shard_map moves no data. R is padded to a
    device-count multiple by repeating the last run (padded outputs are
    dropped), grids above the `REPRO_SHARD_MEM_MB` per-device budget are
    split into device-aligned chunks, and input buffers are donated on
    accelerator backends (XLA does not implement donation on CPU).
    Bitwise equal to `run_batch` because no op crosses the runs axis;
    with a single visible device it degrades to exactly `run_batch`.

    With ``reductions`` set, execution routes to `_run_reduced_chunked`:
    the same mesh layout, but runs are prepared lazily per chunk and the
    scan emits fixed-size streaming summaries instead of a full `Trace`
    (DESIGN.md §12) — the return value is one dict of (R, ...) numpy
    arrays. The bitwise claim above is for the Trace path; the in-scan
    fold fuses with the kernel math, so streaming summaries agree with
    `run_batch` to last-ulp tolerance rather than bit-for-bit (XLA
    fusion choices move with the per-device vmap batch size).
    """
    D = len(jax.devices())
    if D == 1 or len(problems) == 1:
        # Structural fallback: one device means nothing to lay out; one
        # run means padding would make every device compute a duplicate
        # of the same scan for no wall-clock gain.
        return run_batch(
            kernel, problems, nets, cfgs, iters, reductions=reductions
        )
    if reductions is not None:
        return _run_reduced_chunked(
            kernel, problems, nets, cfgs, iters, reductions
        )

    # Tables are replicated on the D devices, so they ship D times.
    preps, statics, batch = _stack_batch(
        kernel, problems, nets, cfgs, iters, copies=D
    )
    R = len(preps)
    mesh = _runs_mesh()
    layout = AxisLayout(mesh, data=("runs",), model="model")
    donate = jax.default_backend() in ("tpu", "gpu")
    fn = _sharded_fn(
        kernel, _statics_key(statics), D, batch.shared, len(batch.steps),
        donate,
    )

    chunk = _chunk_runs(-(-R // D) * D, D, *_bytes_per_run(batch, statics))
    sharing = batch.index is not None
    placed = None  # the tables on every device, from the first chunk on
    outs: List[Tuple] = []
    for lo in range(0, R, chunk):
        n = min(chunk, R - lo)
        with jax.profiler.TraceAnnotation("repro.sweep.stack"):
            part = _pad_runs(
                jax.tree.map(lambda a: a[lo : lo + n], batch.per_run), n, D
            )
        ship = batch.tables if placed is None else ()  # later chunks: none
        put_t, put_r = _put_sharded(part, mesh, layout, ship, sharing)
        if placed is None:
            placed = (put_t,) if sharing else ()
        with jax.profiler.TraceAnnotation("repro.sweep.execute"):
            x, z, (acc, te, ze) = fn(*placed, *put_r)
            outs.append(
                tuple(np.asarray(o)[:n] for o in (x, z, acc, te, ze))
            )
    cat = [np.concatenate([o[i] for o in outs]) for i in range(5)]
    return _unstack_traces(preps, cat[0], cat[1], (cat[2], cat[3], cat[4]))
