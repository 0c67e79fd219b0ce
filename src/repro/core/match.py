"""Resource matcher: depth-first traversal with pruning filters.

MATCHALLOCATE's matching stage.  The traversal is pruned using the
per-vertex subtree free-count aggregates maintained by ``ResourceGraph``
(the analogue of Fluxion's ``ALL:core`` pruning filter): a subtree is
never entered if it cannot possibly satisfy the remaining request, so
allocated subtrees are skipped (paper Section 5.2.3).

The scheduler calls :class:`Matcher`, which picks per request, through
``flatgraph.flat_for``: the graph's flat-array mirror
(``core/flatgraph.FlatMatcher``, the same traversal, claims and result
via contiguous arrays and a vectorized feasibility prefilter) for a
large graph and request, :class:`DictMatcher` below otherwise.  The
dict DFS is also the oracle: the tier-1 suite asserts that both return
identical matches.
"""
from __future__ import annotations

from typing import List, Optional, Set

from .flatgraph import FlatMatcher, flat_for
from .graph import ResourceGraph, Vertex
from .jobspec import Jobspec, ResourceReq


class Matcher:
    """The scheduler's matcher: :class:`FlatMatcher` on the flat mirror
    where ``flat_for`` returns one for the request, :class:`DictMatcher`
    otherwise.  The choice is made per match, so a graph that grows
    past the cutoff switches over."""

    def __init__(self, graph: ResourceGraph):
        self.g = graph

    def match(self, jobspec: Jobspec) -> Optional[List[str]]:
        """Return the list of matched vertex paths, or None.

        Matching is exclusive: a matched vertex must be free, and all
        vertices named by the (nested) request under it are claimed.
        With a span collector on the graph, each match is a ``match``
        span.
        """
        col = self.g.span_collector
        if col is None:
            return self._match(jobspec)
        with col.span("match") as sp:
            got = self._match(jobspec)
            sp.attrs["ok"] = got is not None
            return got

    def _match(self, jobspec: Jobspec) -> Optional[List[str]]:
        flat = flat_for(self.g, jobspec)
        if flat is None:
            return DictMatcher(self.g).match(jobspec)
        return FlatMatcher(flat).match(jobspec)


class DictMatcher:
    """DFS matcher over the dict graph: a small graph's or a small
    request's path, and the oracle the flat matcher is held to."""

    def __init__(self, graph: ResourceGraph):
        self.g = graph
        # visit statistics, useful for verifying pruning behaviour
        self.visited = 0

    def match(self, jobspec: Jobspec) -> Optional[List[str]]:
        """Return the list of matched vertex paths, or None (the
        exclusive semantics of :meth:`Matcher.match`)."""
        self.visited = 0
        matched: List[str] = []
        claimed: Set[str] = set()
        for req in jobspec.resources:
            found = False
            for root in self.g.roots:
                got = self._match_count(root, req, claimed)
                if got is not None:
                    matched.extend(got)
                    found = True
                    break
            if not found:
                return None
        return matched

    # ------------------------------------------------------------------ #
    @staticmethod
    def _prune(v: Vertex, req: ResourceReq, needed: int) -> bool:
        """True if the subtree at ``v`` cannot hold ``needed`` free
        vertices of ``req.type`` (pruning filter).  Takes the Vertex
        the caller already holds — one dict lookup per visit, not two."""
        return v.agg_free.get(req.type, 0) < needed

    def _satisfies(self, v: Vertex, req: ResourceReq) -> bool:
        if v.type != req.type or not v.free:
            return False
        if v.size < req.size:
            return False
        for k, val in req.properties.items():
            if v.properties.get(k) != val:
                return False
        return True

    def _match_count(self, scope: str, req: ResourceReq,
                     claimed: Set[str]) -> Optional[List[str]]:
        """Find ``req.count`` matches of ``req`` within the subtree at
        ``scope``.  Returns claimed paths (and records them in ``claimed``)
        or None, leaving ``claimed`` untouched on failure."""
        got: List[str] = []
        local_claim: Set[str] = set()
        stack = [scope]
        need = req.count
        while stack and need > 0:
            path = stack.pop()
            if path in claimed or path in local_claim:
                continue
            self.visited += 1
            v = self.g.vertex(path)
            if self._prune(v, req, 1):
                continue  # no free req.type anywhere below — skip subtree
            if self._satisfies(v, req):
                sub = self._match_one(path, req, claimed, local_claim)
                if sub is not None:
                    got.extend(sub)
                    local_claim.update(sub)
                    need -= 1
                    continue  # exclusive: don't descend into a match
            stack.extend(self.g.children(path))
        if need > 0:
            return None
        claimed.update(local_claim)
        return got

    def _match_one(self, path: str, req: ResourceReq, claimed: Set[str],
                   local_claim: Set[str]) -> Optional[List[str]]:
        """Try to match ``req`` rooted exactly at ``path`` (which already
        satisfies type/free/properties), including nested requests."""
        sub: List[str] = [path]
        inner: Set[str] = set(local_claim)
        inner.add(path)
        for child_req in req.with_:
            got = self._match_count_under(path, child_req, claimed, inner)
            if got is None:
                return None
            sub.extend(got)
            inner.update(got)
        return sub

    def _match_count_under(self, scope: str, req: ResourceReq,
                           claimed: Set[str], inner: Set[str]) -> Optional[List[str]]:
        got: List[str] = []
        need = req.count
        stack = list(self.g.children(scope))
        while stack and need > 0:
            path = stack.pop()
            if path in claimed or path in inner:
                continue
            self.visited += 1
            v = self.g.vertex(path)
            if self._prune(v, req, 1):
                continue
            if self._satisfies(v, req):
                sub = self._match_one_under(path, req, claimed, inner)
                if sub is not None:
                    got.extend(sub)
                    inner.update(sub)
                    need -= 1
                    continue
            stack.extend(self.g.children(path))
        if need > 0:
            return None
        return got

    def _match_one_under(self, path: str, req: ResourceReq, claimed: Set[str],
                         inner: Set[str]) -> Optional[List[str]]:
        sub: List[str] = [path]
        nested: Set[str] = set(inner)
        nested.add(path)
        for child_req in req.with_:
            got = self._match_count_under(path, child_req, claimed, nested)
            if got is None:
                return None
            sub.extend(got)
            nested.update(got)
        return sub
