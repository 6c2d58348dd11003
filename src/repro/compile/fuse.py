"""Fusion metadata and fused-callable construction for compiled plans.

A task body is *fusible* when the graph builder attaches a :class:`FuseSpec`
to the task (``g.add(..., fuse=FuseSpec(...))``): a pure kernel plus the keys
it reads and writes in the graph's shared ``fuse_state`` (a mapping-like
store — :class:`~repro.linalg.tiles.TileStore` for the factorizations, a
small adapter for the decode step).  ``Task.meta`` is excluded from the
structural :func:`~repro.replay.graph_key` digest, so fuse metadata never
perturbs recording/cache keys.

Consecutive fusible tasks from one worker's run list are lowered into a
single :class:`FusedSegment`: the per-task Python dispatch (context
creation, result bookkeeping, scheduler hand-off) collapses into one call
that gathers the segment's external inputs from the state, runs the kernel
sequence, and scatters the outputs back.  When every spec in the segment is
``jit_safe`` the whole sequence is additionally wrapped in one outer
``jax.jit`` — one XLA computation per segment shape (callables are cached
process-wide by segment *structure*, so same-shaped segments across rebuilt
graphs share compilations).

The dynamic executor batches too, without a recording: a worker that takes
a ready task carrying a ``jit_safe`` spec also takes the ready siblings
next to it in its deque that share its kernel, and launches them as one
jitted program (:func:`batched_program`): every call's reads in, every
call's writes out, with no dependency between the calls.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["BATCH_BYTES", "BATCH_MAX", "FuseSpec", "FusedSegment", "batch_counts",
           "batch_limit", "batched_program", "fuse_spec_of", "fused_cache_info"]


@dataclasses.dataclass(frozen=True)
class FuseSpec:
    """Declares a task body as a pure kernel over ``graph.fuse_state`` keys.

    ``fn(*[state[k] for k in reads])`` must return the new value for the
    single write key, or a tuple matching ``writes``.  ``result_key`` names
    which written key's value becomes ``results[tid]`` (``None`` → the task
    result is ``None``, matching store-mutating bodies).  ``fn`` must be a
    stable module-level callable — fused-callable caching keys on its
    identity.
    """

    fn: Callable[..., Any]
    reads: Tuple[Any, ...]
    writes: Tuple[Any, ...]
    result_key: Optional[Any] = None
    jit_safe: bool = True


def fuse_spec_of(task) -> Optional[FuseSpec]:
    """The task's :class:`FuseSpec`, or ``None`` for opaque bodies."""
    meta = getattr(task, "meta", None)
    if not meta:
        return None
    spec = meta.get("fuse")
    return spec if isinstance(spec, FuseSpec) else None


# process-wide cache of composed callables keyed by segment structure
# (kernel identities + read/write slot topology); jax.jit's own shape-based
# retracing layers underneath this.
_FUSED_CACHE: Dict[Tuple[Any, ...], Callable[..., Any]] = {}


def fused_cache_info() -> Dict[str, int]:
    return {"entries": len(_FUSED_CACHE)}


def _compose(norm: Tuple[Tuple[Callable, Tuple[int, ...], Tuple[int, ...], int], ...],
             ext_slots: Tuple[int, ...], out_slots: Tuple[int, ...]):
    def run(*ext_vals):
        vals: Dict[int, Any] = dict(zip(ext_slots, ext_vals))
        res: List[Any] = []
        for fn, reads, writes, result_slot in norm:
            out = fn(*(vals[s] for s in reads))
            if len(writes) == 1:
                vals[writes[0]] = out
            else:
                for s, v in zip(writes, out):
                    vals[s] = v
            res.append(vals[result_slot] if result_slot >= 0 else None)
        return tuple(vals[s] for s in out_slots), tuple(res)

    return run


class FusedSegment:
    """One run of consecutive fusible tasks lowered to a single callable.

    Graph-independent: holds state *keys* and tids only, so a segment
    compiled from one graph executes against any same-digest graph's
    ``fuse_state`` (same structure → same keys and kernels).
    """

    __slots__ = ("tids", "ext_keys", "out_keys", "jitted", "_run", "ext_deps")

    def __init__(self, items: Sequence[Tuple[int, FuseSpec]], *,
                 jit_fuse: bool = True,
                 dep_map: Optional[Dict[int, Sequence[int]]] = None):
        slot: Dict[Any, int] = {}

        def sid(key: Any) -> int:
            if key not in slot:
                slot[key] = len(slot)
            return slot[key]

        norm: List[Tuple[Callable, Tuple[int, ...], Tuple[int, ...], int]] = []
        ext: List[int] = []
        written: set = set()
        for _tid, spec in items:
            reads = []
            for k in spec.reads:
                s = sid(k)
                if s not in written and s not in ext:
                    ext.append(s)
                reads.append(s)
            writes = [sid(k) for k in spec.writes]
            written.update(writes)
            result_slot = sid(spec.result_key) if spec.result_key is not None else -1
            norm.append((spec.fn, tuple(reads), tuple(writes), result_slot))

        by_slot = {s: k for k, s in slot.items()}
        out_slots = tuple(sorted(written))
        self.tids = tuple(tid for tid, _ in items)
        self.ext_keys = tuple(by_slot[s] for s in ext)
        self.out_keys = tuple(by_slot[s] for s in out_slots)
        # external dependencies: predecessor tids outside the segment
        members = set(self.tids)
        deps: set = set()
        if dep_map:
            for tid in self.tids:
                deps.update(d for d in dep_map.get(tid, ()) if d not in members)
        self.ext_deps = frozenset(deps)

        structure = (tuple(norm), tuple(ext), out_slots)
        all_jit_safe = all(spec.jit_safe for _, spec in items)
        self.jitted = bool(jit_fuse and all_jit_safe)
        cache_key = (structure, self.jitted)
        run = _FUSED_CACHE.get(cache_key)
        if run is None:
            run = _compose(tuple(norm), tuple(ext), out_slots)
            if self.jitted:
                import jax

                run = jax.jit(run)
            _FUSED_CACHE[cache_key] = run
        self._run = run

    def __call__(self, state, results: Dict[int, Any]) -> None:
        outs, res = self._run(*(state[k] for k in self.ext_keys))
        for k, v in zip(self.out_keys, outs):
            state[k] = v
        for tid, rv in zip(self.tids, res):
            results[tid] = rv


# ---------------------------------------------------------------------------
# batched launch of ready sibling tasks (dynamic executor)
#: The most sibling tasks one batched launch carries.  A run of ready
#: siblings is cut into powers of two up to it (37 = 32 + 4 + 1), so a kernel
#: has at most 7 batched programs per operand shape (1, 2, 4, ..., 64), and a
#: launch passes at most 64 x (reads per call) arguments: 192 for the
#: three-operand tile update.
BATCH_MAX = 64
#: The most operand bytes one batched launch passes: 64 three-operand
#: updates of 192-wide float32 tiles.  Larger operands batch fewer calls
#: (a 1536-wide tile update none): their kernels keep the device busy for
#: longer than a launch costs the host, and a batched program over them
#: takes the TPU compiler tens of seconds.
BATCH_BYTES = 64 * 3 * 192 * 192 * 4
_BATCH_COUNTS = tuple(1 << i for i in range(BATCH_MAX.bit_length() - 1, -1, -1))

# (kernel, reads, writes, count) -> jitted program; jax.jit specializes it
# per operand shape underneath
_BATCHED: Dict[Tuple[Callable, int, int, int], Callable[..., Any]] = {}
# (kernel, reads, writes, operand signature) whose every count is compiled
_BATCHED_SHAPES: set = set()
_BATCHED_LOCK = threading.Lock()


def batch_limit(operands: Sequence[Any]) -> int:
    """How many calls over operands like ``operands`` (one call's reads)
    one batched launch may carry: :data:`BATCH_MAX`, fewer where their
    bytes would pass :data:`BATCH_BYTES`."""
    import jax

    nbytes = sum(getattr(x, "nbytes", 8) for x in jax.tree_util.tree_leaves(list(operands)))
    return max(1, min(BATCH_MAX, BATCH_BYTES // max(1, nbytes)))


def batch_counts(n: int) -> List[int]:
    """``n`` as powers of two of at most :data:`BATCH_MAX`, largest first."""
    out: List[int] = []
    for c in _BATCH_COUNTS:
        while n >= c:
            out.append(c)
            n -= c
    return out


def _batched(fn: Callable, n_reads: int, n_writes: int, count: int):
    key = (fn, n_reads, n_writes, count)
    prog = _BATCHED.get(key)
    if prog is None:
        import jax

        def run(*flat):
            outs: List[Any] = []
            for i in range(0, count * n_reads, n_reads):
                out = fn(*flat[i:i + n_reads])
                if n_writes == 1:
                    outs.append(out)
                else:
                    outs.extend(out)
            return tuple(outs)

        run.__name__ = f"batched_{getattr(fn, '__name__', 'kernel')}_{count}"
        prog = _BATCHED.setdefault(key, jax.jit(run))
    return prog


def _placed(x: Any) -> Tuple[Any, ...]:
    """Shape, dtype, weak type and committed placement of an operand: what
    selects a compiled program besides the traced function."""
    try:
        return (x.shape, x.dtype, x.weak_type, x.sharding if x.committed else None)
    except AttributeError:      # a NumPy array or a Python scalar
        import jax

        a = jax.typeof(x)
        return (a.shape, a.dtype, a.weak_type, None)


def batched_program(fn: Callable, n_reads: int, n_writes: int, count: int,
                    operands: Sequence[Any]) -> Callable[..., Any]:
    """The jitted program that runs ``count`` independent calls of ``fn``:
    it takes each call's ``n_reads`` operands in turn, by position, and
    returns the flat tuple of each call's ``n_writes`` results in turn.

    The program depends only on the kernel, the arity, ``count`` and the
    operand shapes, never on which tasks are grouped.  ``operands`` are the
    first call's; the first time ``fn`` batches over operands of their
    shape, dtype and placement, the program of every count up to their
    :func:`batch_limit` is compiled, so the compilations happen together,
    the first time a workload batches."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(list(operands))
    sig = (fn, n_reads, n_writes, tree, tuple(_placed(x) for x in leaves))
    if sig not in _BATCHED_SHAPES:
        with _BATCHED_LOCK:
            if sig not in _BATCHED_SHAPES:
                structs = jax.tree_util.tree_unflatten(tree, [
                    jax.ShapeDtypeStruct(shape, dtype, weak_type=weak,
                                         sharding=sharding)
                    for shape, dtype, weak, sharding in sig[-1]])
                limit = batch_limit(operands)
                for c in _BATCH_COUNTS:
                    if c <= limit:
                        _batched(fn, n_reads, n_writes, c).lower(*structs * c).compile()
                _BATCHED_SHAPES.add(sig)
    return _batched(fn, n_reads, n_writes, count)

