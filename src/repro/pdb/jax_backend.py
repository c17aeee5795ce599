"""JAX device backend of the ParameterDB: the delta-staleness ring buffer.

On SPMD hardware there is no intra-program asynchrony, so the paper's
admissible-delay semantics is mapped onto *steps*: the gradient at step
``alpha`` is evaluated at the parameters of step ``alpha - delta`` and
applied to the parameters of step ``alpha``.  A ring buffer holds the last
``delta + 1`` parameter versions; per-partition-group delays (the Sec-7.1
per-chunk version arrays) let different parts of the model read different
staleness levels.

``delta = 0`` is bit-identical to synchronous training (asserted in
tests/test_staleness_jax.py and the pdb conformance suite) — the Sec-4
sequential-correctness guarantee.  ``delta = inf`` has no finite buffer;
the engine caps at the configured delta, which is the bounded-staleness
regime of SSP/parameter-server work the paper positions itself against.

:class:`TrainEngine` wraps both the plain synchronous path (delta=0, no
ring-buffer overhead) and the delayed path behind one step interface, with
the same Op-history / staleness telemetry as the other backends: each
training step is the single logical SPMD worker executing its Def-3 program
over the partition groups (read every group at its configured delay, write
every group), validated against a :class:`repro.pdb.policies.DeltaPolicy`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from .telemetry import Telemetry

PyTree = Any


@dataclasses.dataclass
class DelayedState:
    params: PyTree          # current theta[alpha]
    hist: PyTree            # stacked (delta+1, ...) ring buffer of versions
    ptr: jnp.ndarray        # ring position of theta[alpha]
    opt_state: PyTree
    step: jnp.ndarray

    def tree_flatten(self):
        return ((self.params, self.hist, self.ptr, self.opt_state, self.step),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    DelayedState,
    lambda s: s.tree_flatten(),
    lambda aux, ch: DelayedState.tree_unflatten(aux, ch))


# ---------------------------------------------------------------------------
# Packed ring layout (the Pallas fast path)
#
# Leaves are grouped by (admissible delay, dtype), flattened, concatenated,
# padded to whole (8, 128) tiles and stored as one (size, R, 128) buffer per
# group: the ring axis then lies outside the two dims the TPU tiles, so a
# version is a contiguous row.  A stale read is ONE row DMA per group
# (kernels/ring_gather.py, row index via scalar prefetch) instead of one
# dynamic-slice DMA per leaf; a write is one row update per group.  Packing round-trips bit-exactly, so
# the delta=0 sequential-correctness guarantee is untouched (asserted in
# tests/test_staleness_jax.py).
# ---------------------------------------------------------------------------

_LANE = 128
_TILE = 8 * _LANE


class _PackGroup(NamedTuple):
    key: str                              # "d<delay>_<dtype>"
    delay: int
    dtype: Any
    idxs: tuple[int, ...]                 # flat-leaf indices in this group
    shapes: tuple[tuple[int, ...], ...]
    sizes: tuple[int, ...]
    pad: int                              # zero-pad to whole tiles


def _pack_plan(params: PyTree, delta: int,
               delay_for: Callable[[tuple], int] | None
               ) -> tuple[list[_PackGroup], Any, int]:
    leaves = jax.tree_util.tree_leaves_with_path(params)
    treedef = jax.tree_util.tree_structure(params)
    by_key: dict[tuple, list] = {}
    for i, (path, leaf) in enumerate(leaves):
        d = delta if delay_for is None else min(delay_for(path), delta)
        dt = jnp.asarray(leaf).dtype
        by_key.setdefault((d, dt.name), []).append((i, tuple(leaf.shape), dt))
    plan = []
    for (d, dtname) in sorted(by_key):
        members = by_key[(d, dtname)]
        sizes = tuple(int(np.prod(s)) for _, s, _ in members)
        plan.append(_PackGroup(
            key=f"d{d}_{dtname}", delay=d, dtype=members[0][2],
            idxs=tuple(m[0] for m in members),
            shapes=tuple(m[1] for m in members),
            sizes=sizes, pad=(-sum(sizes)) % _TILE))
    return plan, treedef, len(leaves)


def _pack_rows(plan: list[_PackGroup], leaves: list) -> dict:
    rows = {}
    for g in plan:
        parts = [jnp.ravel(leaves[i]).astype(g.dtype) for i in g.idxs]
        row = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if g.pad:
            row = jnp.pad(row, (0, g.pad))
        rows[g.key] = row.reshape(-1, _LANE)
    return rows


def _unpack_rows(plan: list[_PackGroup], rows: dict, treedef: Any,
                 n_leaves: int) -> PyTree:
    out: list = [None] * n_leaves
    for g in plan:
        row, off = rows[g.key].reshape(-1), 0
        for i, shape, sz in zip(g.idxs, g.shapes, g.sizes):
            out[i] = jax.lax.slice_in_dim(row, off, off + sz).reshape(shape)
            off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def _resolve_packed(packed: bool | None) -> bool:
    """Default layout follows the kernel dispatch: the Pallas impls
    (``REPRO_KERNEL_IMPL=pallas|interpret``) use the packed ring."""
    return kops.kernel_impl() != "ref" if packed is None else packed


def init_delayed_state(params: PyTree, opt_init: Callable[[PyTree], PyTree],
                       delta: int, packed: bool | None = None,
                       delay_for: Callable[[tuple], int] | None = None
                       ) -> DelayedState:
    """Ring buffer starts filled with theta[0] (the paper's convention that
    reads clipped below iteration 1 see the initial values).  ``packed``
    selects the grouped (size, N) layout (see module notes); it must match
    the ``make_delayed_step`` that consumes the state."""
    size = delta + 1
    if _resolve_packed(packed):
        plan, _, _ = _pack_plan(params, delta, delay_for)
        rows = _pack_rows(plan, jax.tree_util.tree_leaves(params))
        hist = {k: jnp.broadcast_to(r[None], (size,) + r.shape)
                for k, r in rows.items()}
    else:
        hist = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (size,) + x.shape), params)
    return DelayedState(params=params, hist=hist,
                        ptr=jnp.zeros((), jnp.int32),
                        opt_state=opt_init(params),
                        step=jnp.zeros((), jnp.int32))


def _keep_if(ok: jnp.ndarray, new: PyTree, old: PyTree) -> PyTree:
    """``new`` where ``ok`` else ``old``, leaf by leaf.  The engine's steps
    donate their input state, so a non-finite step must hand the prior
    values back itself: its caller can no longer read the donated input."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def make_delayed_step(
    grad_fn: Callable[[PyTree, Any], tuple[jnp.ndarray, PyTree]],
    opt_update: Callable[[PyTree, PyTree, PyTree], tuple[PyTree, PyTree]],
    delta: int,
    delay_for: Callable[[tuple], int] | None = None,
    packed: bool | None = None,
) -> Callable[[DelayedState, Any], tuple[DelayedState, dict]]:
    """Build a jit-able delayed-gradient step.  A step whose loss is not
    finite returns its input state unchanged (and does not advance ``ptr``
    or ``step``), so the step may be jitted with its state donated.

    grad_fn(params, batch) -> (loss, grads)
    opt_update(grads, opt_state, params) -> (new_params, new_opt_state)
    delay_for(path) -> per-leaf delay in [0, delta]; default: uniform delta.
    packed: use the grouped ring layout + fused gather (default: follows
        ``REPRO_KERNEL_IMPL``).  The returned step exposes its stale-read
        as ``step.read_stale`` (parity tests / benchmarks).
    """
    size = delta + 1
    use_packed = _resolve_packed(packed)
    plan_cache: dict = {}

    def _plan_for(params: PyTree):
        # static, derived once — one engine, one tree structure
        if "plan" not in plan_cache:
            plan_cache["plan"] = _pack_plan(params, delta, delay_for)
        return plan_cache["plan"]

    def read_stale(state: DelayedState) -> PyTree:
        if use_packed:
            # state.params mirrors the (unpacked) tree the plan needs
            plan, treedef, n_leaves = _plan_for(state.params)
            rows = {}
            for g in plan:
                idx = jnp.mod(state.ptr - g.delay, size)
                rows[g.key] = kops.ring_gather(state.hist[g.key], idx)
            return _unpack_rows(plan, rows, treedef, n_leaves)

        def pick(path, hist_leaf):
            d = delta if delay_for is None else min(delay_for(path), delta)
            idx = jnp.mod(state.ptr - d, size)
            return jax.lax.dynamic_index_in_dim(hist_leaf, idx, axis=0,
                                                keepdims=False)
        return jax.tree_util.tree_map_with_path(pick, state.hist)

    def step(state: DelayedState, batch: Any) -> tuple[DelayedState, dict]:
        stale_params = read_stale(state)
        loss, grads = grad_fn(stale_params, batch)
        new_params, new_opt = opt_update(grads, state.opt_state, state.params)
        ok = jnp.all(jnp.isfinite(loss))
        new_params, new_opt = _keep_if(ok, (new_params, new_opt),
                                       (state.params, state.opt_state))
        # a skipped step rewrites theta[alpha] into its own slot (hist[ptr]
        # already holds it bit-exactly), so the ring is untouched
        new_ptr = jnp.where(ok, jnp.mod(state.ptr + 1, size), state.ptr)
        if use_packed:
            plan, _, _ = plan_cache["plan"]
            new_rows = _pack_rows(plan, jax.tree_util.tree_leaves(new_params))
            new_hist = {
                g.key: jax.lax.dynamic_update_index_in_dim(
                    state.hist[g.key], new_rows[g.key], new_ptr, axis=0)
                for g in plan}
        else:
            new_hist = jax.tree.map(
                lambda h, p: jax.lax.dynamic_update_index_in_dim(
                    h, p.astype(h.dtype), new_ptr, axis=0),
                state.hist, new_params)
        new_state = DelayedState(params=new_params, hist=new_hist,
                                 ptr=new_ptr, opt_state=new_opt,
                                 step=state.step + ok.astype(jnp.int32))
        return new_state, {"loss": loss, "staleness": jnp.asarray(delta)}

    step.read_stale = read_stale
    return step


# ---------------------------------------------------------------------------
# Unified train engine (the one JAX entry point for launch/train.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainEngine:
    """One step interface over both JAX execution paths.

    ``step(state, batch)`` runs the jitted update and records the step's
    Def-3 ops (one logical worker, one chunk per partition group) into the
    shared telemetry.  The recorded version of each read mirrors the ring
    buffer's indexing (reads clipped below step 1 see the initial values),
    so warmup staleness ramps 0..delay exactly as on device.

    A non-finite step returns its input state's values unchanged (the
    input itself is donated).  Drivers that may *skip* such a step (e.g.
    the fault layer) should call ``step_fn`` directly and then
    ``record_step()`` only for accepted steps, so the Op history matches
    the actual parameter evolution.
    """

    init_state: Callable[[], Any]
    step_fn: Callable[[Any, Any], tuple[Any, dict]]   # jitted
    telemetry: Telemetry
    delta: int
    group_delays: tuple[int, ...]                     # delay per chunk/group

    def __post_init__(self):
        self._itr = 0

    def step(self, state: Any, batch: Any) -> tuple[Any, dict]:
        new_state, metrics = self.step_fn(state, batch)
        self.record_step()
        return new_state, metrics

    def record_step(self) -> None:
        """Log one committed step's ops into the shared telemetry."""
        self._itr += 1
        itr = self._itr
        for g, d in enumerate(self.group_delays):
            self.telemetry.on_read(0, g, itr, version=max(itr - 1 - d, 0))
        for g in range(len(self.group_delays)):
            self.telemetry.on_write(0, g, itr)

    @property
    def history(self):
        return self.telemetry.history


def make_engine(params: PyTree,
                grad_fn: Callable[[PyTree, Any], tuple[jnp.ndarray, PyTree]],
                opt: Any, sync: Any,
                record_history: bool = False) -> TrainEngine:
    """Build the unified engine from a grad function and a SyncConfig-like
    object (``delta``, ``group_delays``, ``delay_for``).

    delta == 0 and no group delays: plain synchronous dict state
    {"params", "opt"} (checkpoint-compatible with the historical layout);
    otherwise: :class:`DelayedState` ring buffer with per-group delays.

    ``step_fn`` donates its state argument, so the old and new states are
    never both live on the device; a state passed to it must not be read
    again.  The state ``init_state`` builds holds ``params`` itself (no
    second copy on the device), so the first step consumes them: build
    one state per engine.
    """
    delta = int(getattr(sync, "delta", 0))
    group_delays_cfg = tuple(getattr(sync, "group_delays", ()) or ())
    leaves = jax.tree_util.tree_leaves_with_path(params)
    if delta > 0 and group_delays_cfg:
        delay_fn = sync.delay_for
        delays = tuple(min(delay_fn(path), delta) for path, _ in leaves)
    else:
        delays = tuple(delta for _ in leaves)
    telemetry = Telemetry(record_history=record_history)

    if delta == 0:
        def sync_step(state, batch):
            loss, grads = grad_fn(state["params"], batch)
            new = opt.update(grads, state["opt"], state["params"])
            ok = jnp.all(jnp.isfinite(loss))
            new_params, new_opt = _keep_if(ok, new, (state["params"],
                                                     state["opt"]))
            return ({"params": new_params, "opt": new_opt},
                    {"loss": loss, "staleness": jnp.zeros((), jnp.int32)})

        return TrainEngine(
            init_state=lambda: {"params": params, "opt": opt.init(params)},
            step_fn=jax.jit(sync_step, donate_argnums=0),
            telemetry=telemetry, delta=0, group_delays=delays)

    delay_for = sync.delay_for if group_delays_cfg else None
    packed = _resolve_packed(getattr(sync, "packed_ring", None))
    raw = make_delayed_step(grad_fn, opt.update, delta, delay_for,
                            packed=packed)
    return TrainEngine(
        init_state=lambda: init_delayed_state(params, opt.init, delta,
                                              packed=packed,
                                              delay_for=delay_for),
        step_fn=jax.jit(raw, donate_argnums=0),
        telemetry=telemetry, delta=delta, group_delays=delays)
