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
preemption story.  Parameters/optimizer move via ``jax.device_put`` with
the new mesh's NamedShardings (topology-independent layout keyed by
logical axes).  The runtime keeps one binding per mesh size (mesh,
model, shardings, jitted step), built on the first bind to that size or
ahead by :meth:`ElasticRuntime.prepare`: a resize to a size already
seen builds nothing and, once that size has stepped, compiles nothing.

With a ``SpanCollector`` on the scheduler (``span_collector``), a grow
records ``elastic.grow`` (holding the queue's ``match_grow`` and the
rebind), a shrink ``elastic.shrink``, and each rebind ``elastic.bind``
with ``elastic.reshard`` (state on the new mesh, waited for) and, when a
size is new, ``elastic.step_build``.  The counters ``n_resizes``,
``n_step_builds`` and ``reshard_bytes`` are always on.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

import jax

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
                with self._span("elastic.reshard"):
                    moved = sum(x.nbytes for x in jax.tree_util.tree_leaves(
                        (self.params, self.opt_state)))
                    self.params = jax.device_put(self.params, b.param_sh)
                    self.opt_state = jax.device_put(self.opt_state, b.opt_sh)
                    jax.block_until_ready((self.params, self.opt_state))
                self.reshard_bytes += moved
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
