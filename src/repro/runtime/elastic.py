"""ElasticRuntime: scheduler allocations bound to JAX meshes.

This is where the paper's control plane meets the data plane.  A
training job is one job submitted through the
:class:`~repro.core.api.Instance` facade; it holds a resource
allocation (a subgraph of the hierarchical scheduler's resource graph)
for its whole life.  Elasticity events map as:

* **grow**   — a malleable grow request *through the job queue*
  (``JobHandle.grow``: MATCHGROW via the scheduler hierarchy, bursting
  through the External API if the local fleet is exhausted, with a
  typed GROW event flowing back), then re-bind the job to a larger
  mesh and re-shard the training state onto it;
* **shrink** — a malleable shrink request through the queue
  (``JobHandle.shrink``: bottom-up release with exact queue/scheduler
  accounting and a SHRINK event), re-bind to a smaller mesh;
* **failure** — subtractive transform ejecting the failed node, then a
  grow request for a replacement (spare pool first, then external),
  then restore from the last checkpoint if in-memory state was lost.

Because growth and shrink ride the queue, training jobs and batch jobs
share one lifecycle: the same events, the same accounting, the same
preemption story.  Parameters/optimizer move to the new mesh's
NamedShardings (topology-independent layout keyed by logical axes) by
:func:`reshard`, shard to shard between the chips, never through the
host.  The runtime keeps one binding per mesh size (mesh,
model, shardings, jitted step), built on the first bind to that size or
ahead by :meth:`ElasticRuntime.prepare`: a resize to a size already
seen builds nothing and, once that size has stepped, compiles nothing.

With a ``SpanCollector`` on the scheduler (``span_collector``), a grow
records ``elastic.grow`` (holding the queue's ``match_grow`` and the
rebind), a shrink ``elastic.shrink``, and each rebind ``elastic.bind``
with ``elastic.reshard`` (state on the new mesh, waited for) and, when a
size is new, ``elastic.step_build``.  The counters ``n_resizes``,
``n_step_builds``, ``reshard_bytes`` and ``reshard_fallback_bytes`` are
always on.
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..core.api import Instance, JobHandle
from ..core.jobspec import Jobspec
from ..core.metrics import NO_SPAN
from ..core.scheduler import SchedulerInstance
from ..models.config import ArchConfig, ShapeConfig
from ..models.model import Model, make_model
from ..optim.adamw import OptConfig
from ..parallel.sharding import Rules, ShardingCtx


@dataclass
class ElasticEvent:
    kind: str            # grow | shrink | eject | rebind | restore
    t: float
    chips_before: int
    chips_after: int
    detail: str = ""


@dataclass
class _Binding:
    """What one mesh size needs: the mesh, the model over it, the
    training state's shardings, and the jitted init and train steps."""
    mesh: Any
    model: Model
    param_sh: Any
    opt_sh: Any
    init_params: Any
    init_opt: Any
    train_step: Any


Bounds = Tuple[Tuple[int, int], ...]


def _bounds(index, shape) -> Bounds:
    """A shard's index (a tuple of slices) as (start, stop) per axis."""
    return tuple(s.indices(n)[:2] for s, n in zip(index, shape))


def _concat_grid(pieces, offsets, axis: int = 0):
    """Concatenate ``pieces`` that tile a block in a grid, each at its
    ``offsets`` in the block, one axis at a time."""
    if len(pieces) == 1:
        return pieces[0]
    starts = sorted({o[axis] for o in offsets})
    if len(starts) == 1:
        return _concat_grid(pieces, offsets, axis + 1)
    return jnp.concatenate(
        [_concat_grid([p for p, o in zip(pieces, offsets) if o[axis] == s],
                      [o for o in offsets if o[axis] == s], axis + 1)
         for s in starts], axis)


@functools.partial(jax.jit, static_argnums=1)
def _cut_pieces(shards, bounds):
    """Each of ``shards`` (on one device) cut to its (starts, limits)."""
    return [jax.lax.slice(x, *b) for x, b in zip(shards, bounds)]


@functools.partial(jax.jit, static_argnums=1)
def _join_blocks(groups, offsets):
    """Each group of pieces (on one device) joined into its block."""
    return [_concat_grid(g, o) for g, o in zip(groups, offsets)]


@dataclass(frozen=True)
class _Piece:
    """The part of one source shard that lies in one target block."""
    leaf: int
    src: Any                # the device of the source shard it is cut from
    dst: Any                # the device of the target block
    cut: Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]]  # None: whole
    offset: Tuple[int, ...]     # its start in the target block
    nbytes: int


class _Move:
    """How a list of arrays moves from their shardings to ``dst``.

    A leaf whose sharding is already equivalent, or that is not fully
    addressable, goes through ``jax.device_put`` (the latter counted in
    ``fallback_bytes``).  Every other leaf moves shard to shard: each
    target block is made of the overlapping source blocks (a copy on the
    block's own device preferred), cut on the source's device by one
    jitted program per device, the pieces on another chip copied in one
    batched ``device_put``, and a block of several pieces joined on its
    device by one jitted program per device.  Built once per layout and
    target; its programs compile on the first call on each device."""

    def __init__(self, avals, src, dst):
        self.avals, self.dst = avals, dst
        self.put: List[int] = []
        self.blocks: Dict[int, List[List[_Piece]]] = {}
        self.fallback_bytes = 0
        for i, ((shape, dtype), s, d) in enumerate(zip(avals, src, dst)):
            if s.is_equivalent_to(d, len(shape)):
                self.put.append(i)
            elif not (s.is_fully_addressable and d.is_fully_addressable) \
                    or (blocks := self._tile(i, shape, dtype, s, d)) is None:
                self.put.append(i)
                self.fallback_bytes += \
                    math.prod(shape) * jnp.dtype(dtype).itemsize
            else:
                self.blocks[i] = blocks
        pieces = [p for bs in self.blocks.values() for b in bs for p in b]
        self.moved = [p for p in pieces if p.src != p.dst]
        self.copied_bytes = sum(p.nbytes for p in self.moved)
        self.cuts: Dict[Any, List[_Piece]] = {}     # by source device
        for p in pieces:
            if p.cut is not None:
                self.cuts.setdefault(p.src, []).append(p)
        self.joins: Dict[Any, List[Tuple[int, int]]] = {}   # by device
        for i, bs in self.blocks.items():
            for k, b in enumerate(bs):
                if len(b) > 1:
                    self.joins.setdefault(b[0].dst, []).append((i, k))

    @staticmethod
    def _tile(i, shape, dtype, src, dst) -> Optional[List[List[_Piece]]]:
        """Per target device (in ``dst``'s order), the pieces of its
        block; None where they do not tile it (the move falls back)."""
        copies: Dict[Bounds, List[Any]] = {}
        for dev, idx in src.addressable_devices_indices_map(shape).items():
            copies.setdefault(_bounds(idx, shape), []).append(dev)
        itemsize = jnp.dtype(dtype).itemsize
        blocks = []
        for dev, idx in dst.addressable_devices_indices_map(shape).items():
            want = _bounds(idx, shape)
            block, size = [], 0
            for have, devs in copies.items():
                o = tuple((max(a, b), min(c, e)) for (a, c), (b, e)
                          in zip(want, have))
                if not all(lo < hi for lo, hi in o):
                    continue
                n = math.prod(hi - lo for lo, hi in o)
                size += n
                cut = None if o == have else (
                    tuple(lo - h for (lo, _), (h, _) in zip(o, have)),
                    tuple(hi - h for (_, hi), (h, _) in zip(o, have)))
                block.append(_Piece(
                    i, dev if dev in devs else devs[0], dev, cut,
                    tuple(lo - w for (lo, _), (w, _) in zip(o, want)),
                    n * itemsize))
            if not block or size != math.prod(hi - lo for lo, hi in want):
                return None
            blocks.append(block)
        return blocks

    def __call__(self, leaves: List[jax.Array]) -> List[jax.Array]:
        out: List[Any] = [None] * len(leaves)
        if self.put:
            for i, y in zip(self.put, jax.device_put(
                    [leaves[i] for i in self.put],
                    [self.dst[i] for i in self.put])):
                out[i] = y
        shard = {(i, s.device): s.data for i in self.blocks
                 for s in leaves[i].addressable_shards}
        got = {p: shard[p.leaf, p.src] for bs in self.blocks.values()
               for b in bs for p in b if p.cut is None}
        for ps in self.cuts.values():
            got.update(zip(ps, _cut_pieces(
                [shard[p.leaf, p.src] for p in ps],
                tuple(p.cut for p in ps))))
        if self.moved:
            got.update(zip(self.moved, jax.device_put(
                [got[p] for p in self.moved],
                [p.dst for p in self.moved])))
        joined = {}
        for ik in self.joins.values():
            bs = [self.blocks[i][k] for i, k in ik]
            joined.update(zip(ik, _join_blocks(
                [[got[p] for p in b] for b in bs],
                tuple(tuple(p.offset for p in b) for b in bs))))
        for i, bs in self.blocks.items():
            out[i] = jax.make_array_from_single_device_arrays(
                self.avals[i][0], self.dst[i],
                [joined[i, k] if len(b) > 1 else got[b[0]]
                 for k, b in enumerate(bs)])
        return out


def reshard(tree, shardings, moves: Dict):
    """``tree`` on ``shardings`` (a tree of the same structure), moved
    shard to shard between the devices where it can be (see
    :class:`_Move`).  ``moves`` caches each layout's move, so a move
    seen before compiles nothing.  Returns the tree, the bytes that
    changed devices, and the bytes handed to ``jax.device_put`` because
    the shard path did not apply."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dst = tuple(treedef.flatten_up_to(shardings))
    key = (tuple((x.shape, x.dtype) for x in leaves),
           tuple(x.sharding for x in leaves), dst)
    move = moves.get(key)
    if move is None:
        move = moves[key] = _Move(*key)
    return (treedef.unflatten(move(leaves)), move.copied_bytes,
            move.fallback_bytes)


class ElasticRuntime:
    """Bind a scheduler allocation to a mesh; survive resizes."""

    def __init__(self, scheduler: Union[SchedulerInstance, Instance],
                 cfg: ArchConfig,
                 shape: ShapeConfig, jobid: str = "train-job",
                 model_axis: int = 1, chip_type: str = "core",
                 rules: Optional[Rules] = None,
                 opt: Optional[OptConfig] = None):
        # everything control-plane goes through the Instance facade; a
        # bare SchedulerInstance (back-compat) is wrapped in one
        self.api = scheduler if isinstance(scheduler, Instance) \
            else Instance(scheduler)
        self.scheduler = self.api.scheduler
        self.handle: Optional[JobHandle] = None
        self.cfg = cfg
        self.shape = shape
        self.jobid = jobid
        self.model_axis = model_axis
        self.chip_type = chip_type
        self.rules = rules or Rules()
        self.opt = opt
        self.events: List[ElasticEvent] = []
        self.mesh = None
        self.model: Optional[Model] = None
        self._train_step = None
        self.params = None
        self.opt_state = None
        self._bindings: Dict[int, _Binding] = {}
        self.n_resizes = 0          # grows and shrinks that went through
        self.n_step_builds = 0      # bindings built (one per mesh size)
        self.reshard_bytes = 0      # training state moved between meshes
        self.reshard_fallback_bytes = 0     # of it, through device_put
        self._moves: Dict = {}      # reshard's moves, one per layout

    def _span(self, name: str, **attrs):
        col = self.scheduler.span_collector
        return NO_SPAN if col is None else col.span(name, **attrs)

    # ---------------------------------------------------------------- #
    def chips_allocated(self) -> int:
        alloc = self.scheduler.allocations.get(self.jobid)
        if alloc is None:
            return 0
        g = self.scheduler.graph
        return sum(1 for p in alloc.paths
                   if p in g and g.vertex(p).type == self.chip_type)

    def _usable_devices(self) -> int:
        """Devices this process may bind (min of allocation and local)."""
        chips = self.chips_allocated()
        avail = len(jax.devices())
        usable = min(chips, avail)
        # keep divisibility by the model axis and the batch
        usable -= usable % self.model_axis
        while usable > self.model_axis and \
                self.shape.global_batch % (usable // self.model_axis):
            usable -= self.model_axis
        return max(usable, self.model_axis)

    # ---------------------------------------------------------------- #
    def prepare(self, sizes) -> None:
        """Build the binding of each mesh size in ``sizes`` ahead of the
        resizes that will use it (a step compiles on its first call)."""
        for n in sizes:
            self._binding(n)

    def _binding(self, n: int) -> _Binding:
        b = self._bindings.get(n)
        if b is not None:
            return b
        from ..launch.mesh import make_mesh_for
        with self._span("elastic.step_build", devices=n):
            mesh = make_mesh_for(n, self.model_axis)
            model = make_model(self.cfg, ShardingCtx(self.rules, mesh),
                               self.opt)
            psh, osh = model.param_shardings(), model.opt_shardings()
            b = self._bindings[n] = _Binding(
                mesh, model, psh, osh,
                jax.jit(model.init_params, out_shardings=psh),
                jax.jit(model.init_opt, out_shardings=osh),
                jax.jit(model.train_step, out_shardings=(psh, osh, None),
                        donate_argnums=(0, 1)))
        self.n_step_builds += 1
        return b

    def bind(self, key: Optional[jax.Array] = None) -> None:
        """Bind to the mesh of the current allocation, re-sharding the
        existing state onto it (or initializing it with ``key``)."""
        n = self._usable_devices()
        before = 0 if self.mesh is None else self.mesh.size
        with self._span("elastic.bind", devices=n):
            b = self._binding(n)
            self.mesh, self.model, self._train_step = \
                b.mesh, b.model, b.train_step
            if self.params is None:
                self.init_state(key)
            else:
                # re-shard existing state onto the new mesh (elastic move)
                with self._span("elastic.reshard") as sp:
                    state = (self.params, self.opt_state)
                    moved = sum(x.nbytes for x in
                                jax.tree_util.tree_leaves(state))
                    state, copied, fallback = reshard(
                        state, (b.param_sh, b.opt_sh), self._moves)
                    self.params, self.opt_state = jax.block_until_ready(state)
                    if sp is not None:
                        sp.attrs.update(bytes=moved, copied_bytes=copied,
                                        fallback_bytes=fallback)
                self.reshard_bytes += moved
                self.reshard_fallback_bytes += fallback
        self.events.append(ElasticEvent(
            "rebind", time.time(), before, self.mesh.size,
            f"devices={self.mesh.size} model_axis={self.model_axis}"))

    def init_state(self, key: Optional[jax.Array] = None) -> None:
        """(Re)initialize parameters and optimizer state on the bound
        mesh from ``key`` (default ``jax.random.key(0)``)."""
        b = self._bindings[self.mesh.size]
        if key is None:
            key = jax.random.key(0)
        with self.mesh:
            self.params = b.init_params(key)
            self.opt_state = b.init_opt(self.params)

    # ---------------------------------------------------------------- #
    def allocate(self, chips: int) -> bool:
        """Submit the training job (strictly local MATCHALLOCATE for
        the initial placement; it runs until cancelled)."""
        from ..core.jobspec import ResourceReq
        js = Jobspec(resources=[ResourceReq(self.chip_type, chips)])
        self.handle = self.api.submit(js, jobid=self.jobid,
                                      alloc_id=self.jobid, grow=False,
                                      dispatch=True)
        from ..core.queue import JobState
        if self.handle.state is not JobState.RUNNING:
            self.handle.cancel()
            self.handle = None
            return False
        return True

    def grow(self, chips: int) -> bool:
        """Malleable grow through the queue: MATCHGROW more chips (with
        a GROW event flowing back), rebind, re-shard."""
        from ..core.jobspec import ResourceReq
        if self.handle is None:
            return False
        before = self.chips_allocated()
        js = Jobspec(resources=[ResourceReq(self.chip_type, chips)])
        with self._span("elastic.grow", chips=chips):
            if not self.handle.grow(js):
                return False
            self.events.append(ElasticEvent(
                "grow", time.time(), before, self.chips_allocated(),
                f"+{chips} {self.chip_type}"))
            self.bind()
        self.n_resizes += 1
        return True

    def shrink(self, chips: int) -> bool:
        """Malleable shrink through the queue: relinquish ``chips``
        chips (bottom-up release, SHRINK event, queue accounting and
        scheduler allocation kept in agreement)."""
        if self.handle is None:
            return False
        alloc = self.scheduler.allocations.get(self.jobid)
        if alloc is None:
            return False
        g = self.scheduler.graph
        victims = [p for p in alloc.paths
                   if p in g and g.vertex(p).type == self.chip_type]
        if len(victims) - chips < self.model_axis:
            return False
        before = self.chips_allocated()
        with self._span("elastic.shrink", chips=chips):
            if not self.handle.shrink(paths=victims[-chips:]):
                return False
            self.events.append(ElasticEvent(
                "shrink", time.time(), before, self.chips_allocated(),
                f"-{chips} {self.chip_type}"))
            self.bind()
        self.n_resizes += 1
        return True

    # ---------------------------------------------------------------- #
    def eject_and_replace(self, node_path: str,
                          replace: bool = True) -> bool:
        """Failure path: subtractive transform for the dead node, then a
        MATCHGROW for replacement resources."""
        from ..core.jobspec import ResourceReq
        from ..core.transform import remove_subgraph
        g = self.scheduler.graph
        if node_path not in g:
            return False
        lost = [p for p in g.subtree(node_path)
                if g.vertex(p).type == self.chip_type]
        before = self.chips_allocated()
        remove_subgraph(g, [node_path], jobid=self.jobid)
        alloc = self.scheduler.allocations.get(self.jobid)
        if alloc is not None:
            alloc.paths = [p for p in alloc.paths if p in g]
        if self.handle is not None:
            # the failure mutated the graph out from under the queue:
            # resync the job record so accounting stays exact
            self.handle.job.paths = [p for p in self.handle.job.paths
                                     if p in g]
        self.events.append(ElasticEvent(
            "eject", time.time(), before, self.chips_allocated(), node_path))
        ok = True
        if replace and lost:
            js = Jobspec(resources=[ResourceReq(self.chip_type, len(lost))])
            ok = bool(self.handle.grow(js)) if self.handle is not None \
                else bool(self.scheduler.match_grow(js, self.jobid))
        self.bind()
        return ok

    # ---------------------------------------------------------------- #
    def step(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        with self.mesh:
            sharded = jax.device_put(
                batch, self.model.input_shardings(self.shape))
            self.params, self.opt_state, metrics = self._train_step(
                self.params, self.opt_state, sharded)
        return metrics
