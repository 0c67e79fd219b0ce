"""Plain reference for the scheduling cells: EASY backfill over node
counts, and a check of allocations read back as vertex paths.

It imports nothing of the program.  It holds the semantics that the
``quartz`` configuration states, for a site whose nodes are identical
and allocated whole (a node a job matches is claimed by that job alone,
whatever share of its sockets and cores the job asks):

* jobs start in submission order while the free nodes cover the head;
* the blocked head gets a reservation at its shadow time, the earliest
  walltime end of a running job by which the free nodes plus the nodes
  released by then cover it;
* a later job, in order, starts now if it fits the free nodes and
  either ends (by its walltime) by the shadow time, or leaves the
  head's reservation where it was: the free nodes left after it, plus
  those released by the shadow time, still cover the head (EASY's
  spare-capacity rule);
* after each such start the shadow time is worked out again;
* a job leaves when the driver ends it (its runtime is up), or at its
  start plus its walltime; ``step`` releases the jobs due and then
  schedules.

With the node as the binding count (a job's sockets and cores all lie
on its own nodes, and a whole free node holds any job's per-node
share), these are exactly the per-type count rules of the program's
EASY; a structural failure of a match cannot happen.

:class:`SharedNodes` is the control: the same EASY over socket slots,
placing jobs first-fit so that two serial jobs share a node.  That
breaks the guarantee the configuration states first (a matched node is
claimed by one job alone): the tempting step of packing a site by
sockets.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

EPS = 1e-12            # the comparison band of a reservation moving later


class EasyByCounts:
    def __init__(self, nodes: int):
        self.nodes = nodes
        self.free = nodes
        self.now = 0.0
        self.pending: List[Tuple[int, int, float]] = []   # (index, n, wt)
        self.running: List[Tuple[float, int, int]] = []   # (end, n, index)
        self.started: List[Tuple[int, float]] = []
        self._version = 0
        self._sched_version = -1

    # -- the calls a driver makes --------------------------------------- #
    def submit(self, index: int, nodes: int, walltime: float) -> None:
        self.pending.append((index, nodes, walltime))
        self._version += 1

    def set_clock(self, t: float) -> None:
        self.now = t

    def end(self, index: int) -> None:
        """The job's runtime is up: it leaves before its walltime."""
        for r in self.running:
            if r[2] == index:
                self._leave(r)
                return

    def step(self) -> int:
        for r in [r for r in self.running if r[0] <= self.now]:
            self._leave(r)
        return self._schedule()

    # -- internals ------------------------------------------------------- #
    def _leave(self, r: Tuple[float, int, int]) -> None:
        self.running.remove(r)
        self.free += r[1]
        self._version += 1

    def _start(self, job: Tuple[int, int, float]) -> bool:
        idx, n, wt = job
        self.free -= n
        self.running.append((self.now + wt, n, idx))
        self.pending.remove(job)
        self.started.append((idx, self.now))
        self._version += 1
        return True

    def _cover(self, deficit: int) -> Optional[float]:
        """Earliest running end by which releases cover ``deficit``."""
        if deficit <= 0:
            return self.now
        got = 0
        for end, n, _ in sorted(self.running):
            got += n
            if got >= deficit:
                return end
        return None

    def _schedule(self) -> int:
        if self._version == self._sched_version:
            return 0
        started = 0
        while self.pending:
            head = self.pending[0]
            if head[1] <= self.free and self._start(head):
                started += 1
                continue
            started += self._backfill(head)
            break
        self._sched_version = self._version
        return started

    def _backfill(self, head) -> int:
        started = 0
        shadow = self._cover(head[1] - self.free)
        for job in list(self.pending[1:]):
            _, n, wt = job
            if n > self.free:
                continue
            if shadow is not None and self.now + wt > shadow:
                after = self._cover(head[1] - (self.free - n))
                if after is None or after > shadow + EPS:
                    continue
            if not self._start(job):
                continue
            started += 1
            shadow = self._cover(head[1] - self.free)
        return started


class SharedNodes(EasyByCounts):
    """The control: EASY over socket slots (one per socket of a node),
    each job placed first-fit on the lowest nodes with room for its
    sockets, so that serial jobs share nodes.  It reports its placements
    as vertex paths, and starts and ends as events, as the program's
    site does."""

    def __init__(self, site: dict, cluster: str = "cluster0"):
        self.spn = site["sockets_per_node"]
        super().__init__(self.spn * site["nodes"])
        self.site = site
        self.root = "/" + cluster
        self.slots = [self.spn] * site["nodes"]     # free sockets
        self.shape: Dict[int, Tuple[int, int, int]] = {}
        self.placed: Dict[int, List[Tuple[int, int]]] = {}
        self.events: List[Tuple[str, str]] = []
        self.allocs: Dict[str, Tuple[List[str], int, int, int]] = {}

    def submit_job(self, index: int, nodes: int, spn: int, cps: int,
                   walltime: float) -> None:
        self.shape[index] = (nodes, spn, cps)
        self.submit(index, nodes * spn, walltime)

    def _start(self, job) -> bool:
        idx = job[0]
        n, spn, cps = self.shape[idx]
        nodes = [i for i, f in enumerate(self.slots) if f >= spn][:n]
        if len(nodes) < n:
            return False
        paths: List[str] = []
        took = []
        for i in nodes:
            first = self.spn - self.slots[i]            # next free socket
            self.slots[i] -= spn
            took.append((i, spn))
            node = f"{self.root}/node{i}"
            paths.append(node)
            for sk in range(first, first + spn):
                sock = f"{node}/socket{sk}"
                paths.append(sock)
                paths += [f"{sock}/core{k}" for k in range(cps)]
        self.placed[idx] = took
        self.allocs[str(idx)] = (paths, n, spn, cps)
        self.events.append(("start", str(idx)))
        return super()._start(job)

    def _leave(self, r) -> None:
        for i, h in self.placed.pop(r[2]):
            self.slots[i] += h
        self.events.append(("free", str(r[2])))
        super()._leave(r)


# ---------------------------------------------------------------------- #
# allocations read back as vertex paths
# ---------------------------------------------------------------------- #
def _parse(path: str) -> Tuple:
    """``/<cluster>/node<i>[/socket<j>[/core<k>]]`` -> (i, j, k) prefix."""
    parts = path.strip("/").split("/")[1:]
    out = []
    for part, word in zip(parts, ("node", "socket", "core")):
        if not part.startswith(word) or not part[len(word):].isdigit():
            raise ValueError(path)
        out.append(int(part[len(word):]))
    if len(out) != len(parts):
        raise ValueError(path)
    return tuple(out)


def allocation_faults(paths: List[str], nodes: int, sockets_per_node: int,
                      cores_per_socket: int, site: dict) -> int:
    """0 when ``paths`` is exactly ``nodes`` distinct nodes, each with
    ``sockets_per_node`` of its sockets and ``cores_per_socket`` cores
    of each; else a count of what breaks that shape (at least 1)."""
    try:
        keys = [_parse(p) for p in paths]
    except ValueError:
        return max(1, len(paths))
    bad = len(keys) - len(set(keys))
    keys = set(keys)
    node_ids = {k[0] for k in keys if len(k) == 1}
    bad += abs(len(node_ids) - nodes)
    sockets = {k for k in keys if len(k) == 2}
    bad += sum(1 for k in sockets if k[0] not in node_ids
               or k[1] >= site["sockets_per_node"])
    per_node: Dict[int, int] = {}
    for k in sockets:
        per_node[k[0]] = per_node.get(k[0], 0) + 1
    bad += sum(abs(per_node.get(i, 0) - sockets_per_node) for i in node_ids)
    cores: Dict[Tuple[int, int], int] = {}
    for k in keys:
        bad += k[0] >= site["nodes"]
        if len(k) == 3:
            bad += k[:2] not in sockets \
                or k[2] >= site["cores_per_socket"]
            cores[k[:2]] = cores.get(k[:2], 0) + 1
    bad += sum(1 for s in sockets if cores.get(s, 0) != cores_per_socket)
    return bad


def replay_allocations(events: List[Tuple[str, str]],
                       allocs: Dict[str, Tuple[List[str], int, int, int]],
                       site: dict) -> Tuple[int, Set[int]]:
    """Walk START/FREE events in order: every started job's paths must
    have its shape and share no vertex with a job still running.
    Returns (faults, node indices busy at the end)."""
    busy: Dict[str, str] = {}
    held: Dict[str, List[str]] = {}
    faults = 0
    for kind, jobid in events:
        if kind == "start":
            paths, n, s, c = allocs[jobid]
            faults += allocation_faults(paths, n, s, c, site)
            for p in paths:
                if p in busy:
                    faults += 1
                busy[p] = jobid
            held[jobid] = paths
        else:
            for p in held.pop(jobid, []):
                if busy.get(p) == jobid:
                    del busy[p]
    nodes_busy = set()
    for p in busy:
        try:
            k = _parse(p)
        except ValueError:
            continue
        if len(k) == 1:
            nodes_busy.add(k[0])
    return faults, nodes_busy
