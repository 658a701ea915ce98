"""Execution backends derived from a MethodKernel (DESIGN.md §8, §9).

``run_serial`` executes one run as ``lax.scan(kernel.step)``;
``run_batch`` executes R runs as ``vmap`` of the *same* composed scan —
the batched engine is a pure performance transform of the serial path
because both call literally the same step function. ``run_sharded`` lays
the runs axis of that same vmapped scan out over a `jax.sharding.Mesh`
of every visible device (``shard_map`` over a 1-D runs mesh), falling
back structurally to the single-device vmap when only one device is
visible (DESIGN.md §9). Both run one pipeline, `_run_chunks`: prepare,
stack, place and execute, chunk by chunk within a per-device memory
budget. A fourth backend, the TPU mesh runtime
(`repro.distributed.consensus`, DESIGN.md §3), shares the algorithmic
core but owns its sharding-aware state layout.

Jitted executables are cached per kernel, statics and (batched tiers)
const-table layout and device count, on top of the persistent XLA
compilation cache enabled by `repro.experiments.sweep`.
"""

from __future__ import annotations

import dataclasses
import itertools
from functools import lru_cache
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.admm import Trace
from repro.core.graph import Network
from repro.core.problems import LeastSquaresProblem

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
    kernel: MethodKernel,
    statics_key: tuple,
    shared: Tuple[bool, ...],
    D: int,
    donate: bool,
):
    return _build(_compose(kernel, statics_key), kernel, shared, D, donate)


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
    D: int,
    donate: bool,
):
    return _build(
        _compose_reduced(kernel, statics_key, spec), kernel, shared, D,
        donate,
    )


def _build(run, kernel: MethodKernel, shared, D: int, donate: bool):
    """The runs-axis executable of a composed run: jit of its vmap over a
    `_Stacked` chunk's arguments (`_batched`), on one device for D == 1.

    For D > 1, jit(shard_map(...)) over the runs axis of the 1-D mesh —
    shard_map, not bare NamedSharding propagation, because the step's
    Pallas `coded_admm_update` has no SPMD partitioning rule: under
    GSPMD, XLA walls the op off and reshards its operands every scan
    iteration (measured ~50x slower); under shard_map each device runs
    the whole vmapped scan on its local R/D runs and the Pallas call
    never sees a partitioned operand. check_vma=False for the same
    reason (pallas_call has no replication rule). Nothing in the scan
    crosses the runs axis, so per-run math is the single-device vmap's.
    The tables (if any) are replicated on every device, the index and
    the other inputs split on the runs axis; chunks reuse the tables, so
    only the per-run inputs are donated.
    """
    fn = _batched(run, shared, kernel.row_consts)
    if D == 1:
        return jax.jit(fn)
    runs = P("runs")
    specs = (P(), runs, runs, runs) if any(shared) else (runs, runs)
    fn = jax.shard_map(
        fn, mesh=_runs_mesh(), in_specs=specs, out_specs=runs,
        check_vma=False,
    )
    first = int(any(shared))  # the tables, argument 0, are not donated
    return jax.jit(
        fn, donate_argnums=tuple(range(first, len(specs))) if donate else ()
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
    """A chunk's host inputs, the runs on the leading axis.

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
        """The arguments with a runs axis: what a chunk pads."""
        own = (self.consts, self.steps)
        return own if self.index is None else (self.index, *own)


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


def _stack(per_run: Sequence[Sequence]) -> Tuple[np.ndarray, ...]:
    """Stack each position of the runs' input tuples on a runs axis."""
    return tuple(
        np.stack([np.asarray(r[i]) for r in per_run])
        for i in range(len(per_run[0]))
    )


# --------------------------------------------------------------------------
# The batched pipeline: prepare -> stack -> place -> execute, per chunk
# --------------------------------------------------------------------------

# Per-device working-set budget of one dispatch, in bytes. The chunking
# rule is deliberately coarse (a run's prepared inputs and metric traces,
# with 2x slack for XLA temporaries); it only needs to keep a huge grid
# from running a device out of memory, not to model the allocator.
_MEM_BUDGET = 4096 * 2**20


def _runs_mesh() -> Mesh:
    """1-D mesh of every visible device over the runs axis."""
    return Mesh(np.array(jax.devices()), ("runs",))


def _statics_bound(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    cfgs: Sequence,
    iters: int,
) -> Dict[str, int]:
    """The group's `MethodKernel.max_statics_bound`: the max over its runs,
    known before any run is prepared. Raises ValueError on mixed static
    signatures — `repro.experiments.sweep.run_sweep` groups by signature
    first."""
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
    return bound


def _check_statics(
    kernel: MethodKernel, preps: Sequence[Prepared], base: dict, bound: dict
) -> None:
    """Every run shares the first run's statics, and the bound covers its
    ``max_statics`` (same keys, no value above the bound)."""
    for pr in preps:
        if set(pr.max_statics) != set(bound):
            raise ValueError(
                f"{kernel.name}.max_statics_bound() keys {sorted(bound)} != "
                f"prepared max_statics keys {sorted(pr.max_statics)}; "
                "implement the bound hook"
            )
        for key, val in pr.max_statics.items():
            if int(val) > bound[key]:
                raise ValueError(
                    f"{kernel.name}.max_statics_bound() under-bounds "
                    f"{key}: prepared {val} > bound {bound[key]}"
                )
        if pr.statics != base:
            raise ValueError("equal signatures produced unequal statics")


def _stack_runs(
    preps: Sequence[Prepared], clock: bool, copies: int
) -> _Stacked:
    """Stack prepared runs on a leading runs axis (host-side). Consts that
    runs share by identity become tables shipped ``copies`` times
    (`_stack_consts`); with ``clock`` the `_clock_steps` ride along as the
    last step input (the streaming layout, `_compose_reduced`)."""
    shared, tables, index, consts = _stack_consts(
        [pr.consts for pr in preps], copies
    )
    steps = _stack([pr.steps for pr in preps])
    if clock:
        steps += (np.stack([_clock_steps(pr) for pr in preps]),)
    return _Stacked(shared, tables, index, consts, steps)


def _run_bytes(prep: Prepared, clock: bool) -> int:
    """A run's device footprint for chunking: its prepared inputs as if it
    shipped all its own data (it takes its rows of any table on the
    device), plus its three metric traces where no reduction folds them.
    Tables ship only where they hold fewer rows than a device's runs, so
    they fit in the same count."""
    inputs = sum(np.asarray(a).nbytes for a in prep.consts + prep.steps)
    if clock:
        return inputs + _clock_steps(prep).nbytes
    return inputs + 3 * 8 * len(prep.sim_time)


def _chunk_runs(R: int, D: int, run_bytes: int) -> int:
    """Runs per dispatch: as many as the per-device budget holds with 2x
    slack for temporaries, a multiple of the device count D (so every
    chunk shards evenly), and no more than R padded to a multiple of D."""
    fit = _MEM_BUDGET * D // (2 * max(run_bytes, 1))
    return min(max(D, fit // D * D), -(-R // D) * D)


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


def _transfer_span(per_run, tables, sharing: bool):
    """The transfer span of a dispatch's host inputs: ``runs``, the rows
    of ``per_run``; ``tables``, the rows of ``tables`` shipped (each run
    ships its own data where its chunk is not ``sharing``; a chunk whose
    tables the devices already hold ships none); ``bytes``, the host
    bytes of both."""
    runs = len(jax.tree.leaves(per_run)[0])
    n = len(tables[0]) if tables else 0 if sharing else runs
    return jax.profiler.TraceAnnotation(
        "repro.sweep.transfer", runs=runs, tables=n,
        bytes=sum(a.nbytes for a in jax.tree.leaves((tables, per_run))),
    )


def _place(tables, per_run, mesh: Optional[Mesh]):
    """Host arrays onto the device(s): with a ``mesh``, the tables on
    every device and the per-run inputs split on its runs axis, so entry
    into the sharded executable moves no data."""
    if mesh is None:
        return jax.tree.map(jnp.asarray, (tables, per_run))
    every, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("runs"))
    return (
        jax.tree.map(lambda a: jax.device_put(a, every), tables),
        jax.tree.map(lambda a: jax.device_put(a, split), per_run),
    )


def _rows(preps: Sequence[Prepared], batch: _Stacked) -> tuple:
    """What a chunk's tables hold: its shared positions, the ids of the
    host objects at them row by row, and the objects (alive, so that no id
    is reused while the devices hold their rows)."""
    firsts = np.unique(batch.index, return_index=True)[1]
    objs = [
        c for i in firsts for c, s in zip(preps[i].consts, batch.shared) if s
    ]
    return batch.shared, tuple(map(id, objs)), objs


def _run_chunks(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    reductions: Optional[Reduction],
    D: int,
):
    """R runs through the one batched pipeline on D devices (§8, §9).

    The jit statics come from the group's `max_statics_bound` before any
    run is prepared, so every chunk runs on one executable. The first
    run, prepared as the first of the first chunk, sizes the chunks
    (`_chunk_runs`). Per chunk: prepare its runs, stack them (the last
    chunk padded to a multiple of D by repeating its last run), place
    them, execute, and bring the outputs back. Runs are prepared only
    when their chunk dispatches, so host memory is O(chunk x iters) +
    O(R x outputs). A chunk ships its tables unless the devices hold the
    same rows already (`_rows`): the same host objects, by identity.
    """
    R = len(problems)
    if not (len(nets) == len(cfgs) == R):
        raise ValueError("problems, nets, cfgs must have equal length")
    bound = _statics_bound(kernel, problems, cfgs, iters)
    clock = reductions is not None
    mesh = _runs_mesh() if D > 1 else None
    donate = D > 1 and jax.default_backend() in ("tpu", "gpu")
    prepared = (
        kernel.prepare(p, n, c, iters) for p, n, c in zip(problems, nets, cfgs)
    )
    chunk = base = key = held = None  # held: (`_rows`, placed tables)
    outs, kept, done = [], [], 0
    while done < R:
        with jax.profiler.TraceAnnotation("repro.sweep.prepare") as span:
            preps = [next(prepared)]
            if chunk is None:
                base = preps[0].statics
                key = _statics_key({**base, **bound})
                chunk = _chunk_runs(R, D, _run_bytes(preps[0], clock))
            preps += itertools.islice(prepared, chunk - 1)
            span.set_metadata(runs=len(preps))
            _check_statics(kernel, preps, base, bound)
        n = len(preps)
        done += n
        with jax.profiler.TraceAnnotation("repro.sweep.stack"):
            batch = _stack_runs(preps, clock, D)
            per_run = _pad_runs(batch.per_run, n, D)
        shared, sharing, ship = batch.shared, batch.index is not None, ()
        if sharing:
            rows = _rows(preps, batch)
            if held is None or held[0][:2] != rows[:2]:
                ship = batch.tables
        if not clock:  # a Trace keeps only the host clocks of its run
            kept += [
                dataclasses.replace(pr, consts=(), steps=()) for pr in preps
            ]
        del preps, batch  # the chunk's host copies die before the next
        with _transfer_span(per_run, ship, sharing):
            put_t, put_r = _place(ship, per_run, mesh)
        del per_run
        if ship:
            held = (rows, put_t)
        args = (held[1], *put_r) if sharing else put_r
        if clock:
            fn = _batch_reduced_fn(kernel, key, reductions, shared, D, donate)
        else:
            fn = _batch_fn(kernel, key, shared, D, donate)
        with jax.profiler.TraceAnnotation("repro.sweep.execute"):
            outs.append(jax.tree.map(lambda a: np.asarray(a)[:n], fn(*args)))
    out = jax.tree.map(lambda *a: np.concatenate(a), *outs)
    if clock:
        return out
    x, z, (acc, test_err, z_err) = out
    return [
        _to_trace(pr, x[r], z[r], (acc[r], test_err[r], z_err[r]))
        for r, pr in enumerate(kept)
    ]


def run_batch(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    reductions: Optional[Reduction] = None,
):
    """R runs as a vmapped scan on one device: one jit trace, and one
    dispatch per chunk of the memory budget (one for most grids).

    Returns per-run `Trace`s, or — with ``reductions`` — one dict of
    numpy arrays with a leading runs axis (DESIGN.md §12).
    """
    return _run_chunks(kernel, problems, nets, cfgs, iters, reductions, 1)


def run_sharded(
    kernel: MethodKernel,
    problems: Sequence[LeastSquaresProblem],
    nets: Sequence[Network],
    cfgs: Sequence,
    iters: int,
    reductions: Optional[Reduction] = None,
):
    """R runs vmapped AND laid out over a device mesh on the runs axis.

    The same pipeline as `run_batch`, on a 1-D `Mesh` of all visible
    devices: each chunk is padded to a device-count multiple by repeating
    its last run (padded outputs are dropped), placed split on the runs
    axis (tables on every device), and executed as the vmapped scan under
    `shard_map` (see `_build` for why shard_map rather than GSPMD), with
    input buffers donated on accelerator backends (XLA does not implement
    donation on CPU). Bitwise equal to `run_batch` for Traces because no
    op crosses the runs axis. Streaming summaries agree with `run_batch`
    to last-ulp tolerance rather than bit-for-bit: the in-scan fold fuses
    with the kernel math, and XLA's fusion choices move with the
    per-device vmap batch size (DESIGN.md §12).
    """
    # Structural fallback: one run would make every device compute a
    # duplicate of the same scan for no wall-clock gain.
    D = len(jax.devices()) if len(problems) > 1 else 1
    return _run_chunks(kernel, problems, nets, cfgs, iters, reductions, D)
