"""Scheduling-plane cells: one site behind one ``Instance`` with exact
EASY backfill, driven in simulated time by site-job traffic
(``harness/traffic.py``: the Lublin-Feitelson model).

A job asks for its walltime and runs its runtime: the driver moves the
simulated clock to each event and ends a job there by cancelling it
(``Instance.cancel``), so jobs leave before their walltime, as jobs of
real sites do.  Traffic kinds (``traffic["mode"]``):

* ``backlog`` -- the pending queue is held at ``depth`` jobs: after each
  scheduling pass the jobs it started are replaced by new ones, and the
  clock moves from one job end to the next;
* ``open`` -- jobs arrive with exponential gaps that offer ``load`` of
  the site's node-seconds; the clock moves to the next arrival or job
  end, whichever comes first.

A scheduling pass is one ``Instance.step``: after jobs end, and after
jobs are submitted.  ``decision_ms`` is the window's wall time over its
passes: what one scheduling decision costs, the submits and ends that
call for it included.

After the window, what the program decided is compared with the plain
reference (``bench/reference/easy.py``) fed the same calls: every start
(job and simulated time), every allocation read back as vertex paths,
and the device's feasibility mask on the final live state.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Tuple

import numpy as np

from .cell import Check, Outcome, RunContext, use_program
from .instrument import patched, peak_memory_bytes
from .peaks import feasibility_bytes
from .traffic import SiteJob, arrival_gaps, site_jobs


def _program():
    use_program()
    import repro.core as core
    from repro.core import flatgraph, match, policy
    from repro.core.events import EventType
    from repro.kernels import feasibility
    return core, flatgraph, match, policy, feasibility, EventType


def site_vertices(site: dict) -> int:
    """Vertices of a flat site: the root, and per node the node, its
    sockets and their cores."""
    spn, cps = site["sockets_per_node"], site["cores_per_socket"]
    return 1 + site["nodes"] * (1 + spn * (1 + cps))


class ProgramSite:
    """The system under test: ``build_cluster`` + ``Instance`` with
    ``EasyBackfill()`` on a ``SimClock``; starts and frees are read from
    its event stream."""

    def __init__(self, site: dict):
        core, _, _, _, _, EventType = _program()
        self._start, self._free = EventType.START, EventType.FREE
        self.site = site
        self.graph = core.build_cluster(
            nodes=site["nodes"], sockets_per_node=site["sockets_per_node"],
            cores_per_socket=site["cores_per_socket"])
        if self.graph.num_vertices != site_vertices(site):
            raise SystemExit(f"site has {self.graph.num_vertices} vertices;"
                             f" the configuration {site_vertices(site)}")
        self.clock = core.SimClock()
        self.inst = core.Instance(graph=self.graph, name="site",
                                  clock=self.clock,
                                  policy=core.EasyBackfill())
        self._Jobspec = core.Jobspec
        self._specs: Dict[Tuple[int, int, int], object] = {}
        self._meta: Dict[str, SiteJob] = {}
        self._jobid: Dict[int, str] = {}
        self.started: List[Tuple[int, float]] = []
        self.events: List[Tuple[str, str]] = []
        self.allocs: Dict[str, Tuple[List[str], int, int, int]] = {}
        self.n_pending = 0
        self.inst.subscribe(self._on_event)

    def spec(self, nodes: int, spn: int, cps: int):
        s = self._specs.get((nodes, spn, cps))
        if s is None:
            s = self._specs[(nodes, spn, cps)] = self._Jobspec.hpc(
                nodes=nodes, sockets=spn * nodes, cores=spn * nodes * cps)
        return s

    def _on_event(self, ev) -> None:
        if ev.type is self._start:
            job = self.inst.queue.get(ev.jobid)
            j = self._meta[ev.jobid]
            self.started.append((j.index, job.start_time))
            self.allocs[ev.jobid] = (job.paths, j.nodes, j.sockets_per_node,
                                     j.cores_per_socket)
            self.events.append(("start", ev.jobid))
            self.n_pending -= 1
        elif ev.type is self._free:
            self.events.append(("free", ev.jobid))

    def submit(self, job: SiteJob) -> None:
        h = self.inst.submit(self.spec(job.nodes, job.sockets_per_node,
                                       job.cores_per_socket),
                             walltime=job.walltime)
        self._meta[h.jobid] = job
        self._jobid[job.index] = h.jobid
        self.n_pending += 1

    def set_clock(self, t: float) -> None:
        self.clock.set(t)

    def end(self, index: int) -> None:
        self.inst.cancel(self._jobid[index])

    def step(self) -> int:
        return self.inst.step()

    def now(self) -> float:
        return self.clock.now()


class ControlSite:
    """The control put in the program's place: the reference's EASY over
    socket slots with first-fit placement (``SharedNodes``), which lets
    two serial jobs share a node."""

    def __init__(self, site: dict):
        from reference.easy import SharedNodes
        self.ref = SharedNodes(site)
        self.started = self.ref.started
        self.events = self.ref.events
        self.allocs = self.ref.allocs
        self.graph = None

    @property
    def n_pending(self) -> int:
        return len(self.ref.pending)

    def submit(self, job: SiteJob) -> None:
        self.ref.submit_job(job.index, job.nodes, job.sockets_per_node,
                            job.cores_per_socket, job.walltime)

    def set_clock(self, t: float) -> None:
        self.ref.set_clock(t)

    def end(self, index: int) -> None:
        self.ref.end(index)

    def step(self) -> int:
        return self.ref.step()

    def now(self) -> float:
        return self.ref.now


class Driver:
    """Feeds one site its traffic, ends its jobs when their runtime is
    up, and logs every call for the replay."""

    def __init__(self, ctx: RunContext, system):
        self.ctx = ctx
        self.system = system
        t = ctx.cell.traffic
        self.traffic = t
        site = ctx.cell.config["site"]
        self.jobs = site_jobs(t, site, ctx.seed)
        self.gaps = arrival_gaps(t, site, ctx.seed) \
            if t["mode"] == "open" else None
        self.next_arrival = next(self.gaps) if self.gaps else None
        self.log: List[tuple] = []
        self.all_jobs: Dict[int, SiteJob] = {}
        self.ends: List[Tuple[float, int]] = []     # (runtime end, index)
        self.n_seen = 0                             # starts put on ends
        self.fresh = False            # submitted since the last pass

    def _submit(self) -> None:
        job = next(self.jobs)
        self.all_jobs[job.index] = job
        self.system.submit(job)
        self.log.append(("submit", job.index))
        self.fresh = True

    def _clock(self, t: float) -> None:
        self.system.set_clock(t)
        self.log.append(("clock", t))

    def _end_due(self) -> None:
        now = self.system.now()
        while self.ends and self.ends[0][0] <= now:
            _, idx = heapq.heappop(self.ends)
            self.system.end(idx)
            self.log.append(("end", idx))

    def _pass(self) -> None:
        with self.ctx.spans.span("pass"):
            self.system.step()
        self.log.append(("step",))
        self.fresh = False
        started = self.system.started
        for idx, t0 in started[self.n_seen:]:
            heapq.heappush(self.ends, (t0 + self.all_jobs[idx].runtime, idx))
        self.n_seen = len(started)

    def one_round(self) -> None:
        """One pass, after the event that calls for it: the unit the
        window loop repeats."""
        if self.traffic["mode"] == "backlog":
            if not self.fresh and self.ends:
                self._clock(self.ends[0][0])
                self._end_due()
            self._pass()
            while self.system.n_pending < self.traffic["depth"]:
                self._submit()
        elif self.ends and self.ends[0][0] <= self.next_arrival:
            self._clock(self.ends[0][0])
            self._end_due()
            self._pass()
        else:
            self._clock(self.next_arrival)
            self._submit()
            self._pass()
            self.next_arrival += next(self.gaps)


def _traffic_specs(system, site: dict) -> list:
    """One jobspec per request shape the traffic submits: a serial job's
    one core, and whole nodes (the scan's rows do not depend on the
    node count)."""
    return [system.spec(1, 1, 1),
            system.spec(1, site["sockets_per_node"], site["cores_per_socket"])]


def _warm_device_shapes(system, site: dict) -> None:
    """Compile the feasibility kernel for the traffic's request rows
    (all its shapes at once: the scan pads its distinct rows to 8) and
    the aggregate sweep for the site's level shapes."""
    _, flatgraph, _, _, _, _ = _program()
    flat = system.graph.flat()
    flat.feasible_roots_batch([r for s in _traffic_specs(system, site)
                               for r in s.resources])
    flatgraph.aggregate_sweep(flat.own_counts(), flat.parent[:flat.n],
                              flat._levels)


def _device_mask_mismatch(system, site: dict, nodes_busy) -> int:
    """The program's feasibility scan (the device kernel on a TPU) on
    the final live state, for every shape of the traffic, against the
    free nodes the allocations leave: a node-rooted request can root
    exactly at a free node."""
    reqs = [s.resources[0] for s in _traffic_specs(system, site)]
    flat = system.graph.flat()
    mask = flat.feasible_roots_batch(reqs)
    free = {f"node{i}" for i in range(site["nodes"])} - \
        {f"node{i}" for i in nodes_busy}
    bad = 0
    for row in mask:
        got = {flat.path[i].rsplit("/", 1)[-1] for i in np.nonzero(row)[0]}
        bad += len(got ^ free)
    return bad


def replay(log: List[tuple], jobs: Dict[int, SiteJob], nodes: int):
    """The calls a ``Driver`` logged, in order, through the plain
    reference."""
    from reference.easy import EasyByCounts
    ref = EasyByCounts(nodes)
    for call in log:
        if call[0] == "submit":
            j = jobs[call[1]]
            ref.submit(j.index, j.nodes, j.walltime)
        elif call[0] == "clock":
            ref.set_clock(call[1])
        elif call[0] == "end":
            ref.end(call[1])
        else:
            ref.step()
    return ref


def run(ctx: RunContext, system=None) -> Outcome:
    """``system`` replaces the program (the control, and the tests'
    faults); by default the program's own site."""
    cell = ctx.cell
    site, traffic = cell.config["site"], cell.traffic
    from reference.easy import replay_allocations

    _, flatgraph, match, policy, feasibility, _ = _program()
    spans = ctx.spans
    kernel_bytes: List[int] = []

    def on_kernel(args, kwargs, out):
        kernel_bytes.append(feasibility_bytes([a.shape for a in args]))

    with patched(flatgraph.FlatGraph, "feasible_roots_batch",
                 lambda f: spans.wrap("scan_call", f)), \
            patched(policy.EasyBackfill, "backfill",
                    lambda f: spans.wrap("policy_pass", f)), \
            patched(match.Matcher, "match",
                    lambda f: spans.wrap("match", f)), \
            patched(flatgraph, "aggregate_sweep",
                    lambda f: spans.wrap("sweep_call", f)), \
            patched(feasibility, "_feasible_pallas",
                    lambda f: spans.wrap("scan_kernel", f, on_kernel)):
        system = system or ProgramSite(site)
        drv = Driver(ctx, system)
        if system.graph is not None:
            _warm_device_shapes(system, site)
        if traffic["mode"] == "backlog":
            for _ in range(traffic["depth"]):
                drv._submit()
        for _ in range(traffic["warmup_rounds"]):
            drv.one_round()
        n_before = len(system.started)
        with ctx.window() as t0:
            kernel_bytes.clear()
            while ctx.elapsed(t0) < ctx.seconds:
                drv.one_round()
        started_window = len(system.started) - n_before
        memory = peak_memory_bytes(ctx.devices)

        # -- checks: the same calls through the plain reference -------- #
        ref = replay(drv.log, drv.all_jobs, site["nodes"])
        got, want = dict(system.started), dict(ref.started)
        start_mismatch = sum(1 for i in set(got) | set(want)
                             if got.get(i) != want.get(i))
        window_jobs = [i for i, _ in system.started[n_before:]]
        failed = sum(1 for i in window_jobs if got.get(i) != want.get(i))
        faults, busy = replay_allocations(system.events, system.allocs,
                                          site)
        checks = {"start_mismatch": Check(start_mismatch,
                                          cell.limits["start_mismatch"]),
                  "alloc_faults": Check(faults, cell.limits["alloc_faults"])}
        if system.graph is not None:
            checks["device_mask_mismatch"] = Check(
                _device_mask_mismatch(system, site, busy),
                cell.limits["device_mask_mismatch"])

    ctx.extra["scan_kernel_bytes"] = kernel_bytes
    passes = spans.get("pass").durations
    lines = [f"passes in the window: {len(passes)}; jobs started: "
             f"{started_window}; simulated time {system.now():.1f} s; "
             f"pending {system.n_pending}",
             f"feasibility kernel calls in the window: {len(kernel_bytes)}; "
             f"sweep calls: {spans.get('sweep_call').calls}; matches: "
             f"{spans.get('match').calls}"]
    metrics = {}
    if passes:
        metrics["decision_ms"] = 1e3 * ctx.window_s / len(passes)
    return Outcome(
        metrics=metrics, attempted=started_window, failed=failed,
        checks=checks, memory_peak_bytes=memory, lines=lines)
