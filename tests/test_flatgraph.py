"""FlatGraph mirror tests: incremental aggregates, match agreement.

The flat-array mirror must track the dict graph exactly — same vertex
set, free flags, and pruning aggregates — through any sequence of
alloc/release flips, status flips, and structural splices, WITHOUT ever
falling back to an ``init_aggregates()`` rebuild on a hot path
(``ResourceGraph.n_agg_rebuilds`` stays frozen).  The dict DFS matcher
stays the oracle: flat and dict matching must return identical paths.

The property-based tests need ``hypothesis``; without it the
deterministic tests below still collect and run (same guard idiom as
tests/test_graph.py).
"""
import pytest

from repro.core import (DictMatcher, FlatMatcher, Jobspec, add_subgraph,
                        build_cluster, flatgraph, remove_subgraph,
                        update_metadata)
from repro.core.graph import DOWN, UP

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAS_HYPOTHESIS = True
except ImportError:      # optional dependency: property tests skipped
    HAS_HYPOTHESIS = False


# ---------------------------------------------------------------------- #
# deterministic basics
# ---------------------------------------------------------------------- #
def test_flat_mirror_agrees_after_build():
    g = build_cluster(nodes=2, gpus_per_socket=2, mem_per_socket=4)
    flat = g.flat()
    assert flat.verify_against(g)
    assert flat.n_builds == 1


def test_flat_mirror_tracks_alloc_release_incrementally():
    g = build_cluster(nodes=2)
    flat = g.flat()
    rebuilds = g.n_agg_rebuilds
    cores = sorted(g.by_type("core"))[:8]
    g.set_allocated(cores, "job-a")
    assert flat.verify_against(g)
    g.set_free(cores, "job-a")
    assert flat.verify_against(g)
    # the hot path never ran an init_aggregates() rebuild, and the
    # mirror never re-built its arrays
    assert g.n_agg_rebuilds == rebuilds
    assert flat.n_builds == 1
    assert flat.n_bubbles >= 2


def test_flat_mirror_tracks_status_flips():
    g = build_cluster(nodes=2)
    flat = g.flat()
    rebuilds = g.n_agg_rebuilds
    node = sorted(g.by_type("node"))[0]
    g.set_status(node, DOWN)
    assert flat.verify_against(g)
    assert g.validate_tree()
    g.set_status(node, UP)
    assert flat.verify_against(g)
    assert g.n_agg_rebuilds == rebuilds


def test_flat_mirror_tracks_splices():
    g = build_cluster(nodes=2)
    flat = g.flat()
    ext = build_cluster(nodes=1, node_prefix="burst")
    sub = ext.extract([p for p in ext.paths() if "burst" in p])
    res = add_subgraph(g, sub)
    update_metadata(g, res, jobid="burst-job")
    assert flat.verify_against(g)
    remove_subgraph(g, res.new_paths, jobid="burst-job")
    assert flat.verify_against(g)


def test_flat_and_dict_matchers_identical():
    g = build_cluster(nodes=4, gpus_per_socket=2, mem_per_socket=4)
    specs = [
        Jobspec.hpc(nodes=2, sockets=4, cores=32),
        Jobspec.hpc(nodes=1, sockets=2, cores=8, gpus=2),
        Jobspec.hpc(nodes=8, sockets=16, cores=64),   # unsatisfiable
    ]
    for js in specs:
        flat = FlatMatcher(g.flat()).match(js)
        oracle = DictMatcher(g).match(js)
        assert flat == oracle


def test_feasible_roots_empty_for_unknown_type():
    g = build_cluster(nodes=2)
    flat = g.flat()
    req = Jobspec.hpc(nodes=1, sockets=1, cores=1).resources[0]
    assert len(flat.feasible_roots(req)) > 0
    from repro.core.jobspec import ResourceReq
    missing = ResourceReq(type="quantum-annealer", count=1)
    assert len(flat.feasible_roots(missing)) == 0


def test_flat_match_claims_are_exclusive():
    """Two requests in one jobspec must not claim the same vertex."""
    g = build_cluster(nodes=2)
    js = Jobspec.hpc(nodes=2, sockets=4, cores=16)
    got = FlatMatcher(g.flat()).match(js)
    assert got is not None
    assert len(got) == len(set(got))


def test_env_toggle_forces_dict_path():
    """The one flat-or-dict decision, ``flat_for``: the size cutoff,
    the request ratio, and a mirror already kept."""
    from repro.core.flatgraph import (FLAT_MIN_VERTICES, FLAT_REQ_RATIO,
                                      flat_for)
    big = build_cluster(nodes=16)
    n = big.num_vertices
    assert n >= FLAT_MIN_VERTICES
    assert big._flat is None
    one_core = Jobspec.hpc(nodes=1, sockets=1, cores=1)
    whole = Jobspec.hpc(nodes=16, sockets=32, cores=128)
    assert one_core.graph_size() * FLAT_REQ_RATIO < n
    assert whole.graph_size() * FLAT_REQ_RATIO >= n
    # a small request on a big graph: the dict DFS, no mirror built
    assert flat_for(big, one_core) is None and big._flat is None
    assert flat_for(big, whole) is big.flat()
    assert flat_for(big) is big.flat()
    # small graphs keep no mirror (flat setup costs more than the whole
    # match there) ...
    small = build_cluster(nodes=2)
    assert small.num_vertices < FLAT_MIN_VERTICES
    assert flat_for(small) is None and small._flat is None
    assert flat_for(small, whole) is None
    # ... unless one is already kept
    mirror = small.flat()
    assert flat_for(small) is mirror
    assert flat_for(small, whole) is mirror
    assert flat_for(small, one_core) is None


def test_tombstone_compaction_rebuilds_once():
    """Enough removals trigger one compacting rebuild, after which the
    mirror still agrees exactly."""
    g = build_cluster(nodes=8)
    flat = g.flat()
    for k in range(6):
        remove_subgraph(g, [f"/cluster0/node{k}"])
    assert flat.verify_against(g)
    # add after heavy removal: may compact, must stay correct
    ext = build_cluster(nodes=1, node_prefix="late")
    sub = ext.extract([p for p in ext.paths() if "late" in p])
    res = add_subgraph(g, sub)
    update_metadata(g, res, jobid="late-job")
    assert flat.verify_against(g)


# ---------------------------------------------------------------------- #
# batched feasibility plane (feasible_roots_batch + compiled-req cache)
# ---------------------------------------------------------------------- #
def _churned_graph():
    """A mid-size graph with enough churn that feasibility genuinely
    varies across vertices: some cores allocated, one node down."""
    g = build_cluster(nodes=4, gpus_per_socket=2, mem_per_socket=4)
    g.set_allocated(sorted(g.by_type("core"))[:24], "busy")
    g.set_status(sorted(g.by_type("node"))[1], DOWN)
    return g


def _batch_specs():
    return [
        Jobspec.hpc(nodes=1, sockets=1, cores=4),
        Jobspec.hpc(nodes=1, sockets=2, cores=8, gpus=2),
        Jobspec.hpc(nodes=2, sockets=4, cores=32),
        Jobspec.hpc(nodes=1, sockets=1, cores=4),     # repeated shape
        Jobspec.hpc(nodes=8, sockets=16, cores=64),   # unsatisfiable
    ]


def test_feasible_roots_batch_matches_sequential():
    """Row i of the batched mask must equal feasible_roots(reqs[i]) —
    including repeated shapes (dedup path) and unsatisfiable rows."""
    import numpy as np
    g = _churned_graph()
    flat = g.flat()
    reqs = [r for js in _batch_specs() for r in js.resources]
    from repro.core.jobspec import ResourceReq
    reqs.append(ResourceReq(type="quantum-annealer", count=1))
    mask = flat.feasible_roots_batch(reqs)
    assert mask.shape == (len(reqs), flat.n)
    for i, r in enumerate(reqs):
        assert np.array_equal(np.nonzero(mask[i])[0],
                              flat.feasible_roots(r)), i
    assert not mask[-1].any()       # unknown type: empty row, no crash


def test_feasible_roots_batch_jax_parity(monkeypatch):
    """The device half (the kernels/feasibility.py kernel, interpreted
    on the CPU) must be element-wise identical to the numpy half."""
    import numpy as np
    g = _churned_graph()
    flat = g.flat()
    reqs = [r for js in _batch_specs() for r in js.resources]
    m_np = flat.feasible_roots_batch(reqs)
    assert flat.scan_h2d_bytes == 0
    monkeypatch.setattr(flatgraph, "on_device", lambda: True)
    m_dev = flat.feasible_roots_batch(reqs)
    assert flat.scan_h2d_bytes > 0 and flat.scan_d2h_bytes > 0
    assert np.array_equal(m_np, m_dev)


def test_batched_path_agrees_with_dict_oracle():
    """Tier-1 oracle agreement for the batched plane: an all-empty
    batched mask row set implies the dict DFS fails too, and the flat
    matcher (whose policies consume the mask) returns the dict oracle's
    exact paths whenever it matches."""
    g = _churned_graph()
    flat = g.flat()
    for js in _batch_specs():
        mask = flat.feasible_roots_batch(js.resources)
        oracle = DictMatcher(g).match(js)
        got = FlatMatcher(flat).match(js)
        assert got == oracle
        if not mask.any(axis=1).all():
            # some request has no feasible root anywhere: the oracle
            # must agree the jobspec is unmatchable (prefilter safety)
            assert oracle is None


def test_aggregate_sweep_jax_cpu_parity():
    """The jitted aggregate sweep must agree element-wise with the
    numpy sweep on the CPU."""
    import numpy as np
    g = _churned_graph()
    flat = g.flat()
    flat.sync()
    n, T = flat.n, len(flat.types)
    own = np.zeros((n, T), np.int32)
    live = np.nonzero(flat.present[:n] & flat.free[:n])[0]
    own[live, flat.type_id[live]] = 1
    a_np = flatgraph._aggregate_sweep_numpy(own, flat.parent[:n],
                                            flat._levels)
    a_jax = flatgraph._aggregate_sweep_jax(own, flat.parent[:n],
                                           flat._levels)
    assert np.array_equal(a_np, np.asarray(a_jax))
    assert np.array_equal(a_np, flat.agg[:n, :T])


def test_compiled_req_cache_survives_version_bumps():
    """The same request object never recompiles across alloc/release
    churn (version bumps leave the type/prop tables untouched); a
    compacting rebuild refreshes the cache but keeps answers right."""
    g = build_cluster(nodes=8)
    flat = g.flat()
    req = Jobspec.hpc(nodes=1, sockets=1, cores=4).resources[0]
    c1 = flat.compiled(req)
    cores = sorted(g.by_type("core"))[:8]
    g.set_allocated(cores, "churn")
    g.set_free(cores, "churn")
    assert flat.compiled(req) is c1
    assert len(flat.feasible_roots(req)) > 0
    # tombstone compaction (triggered by the add after heavy removal)
    # forces a _build: new tables, fresh cache
    builds = flat.n_builds
    for k in range(6):
        remove_subgraph(g, [f"/cluster0/node{k}"])
    ext = build_cluster(nodes=1, node_prefix="late")
    sub = ext.extract([p for p in ext.paths() if "late" in p])
    res = add_subgraph(g, sub)
    update_metadata(g, res, jobid="late-job")
    assert flat.n_builds > builds
    c2 = flat.compiled(req)
    assert c2 is not c1
    assert len(flat.feasible_roots(req)) > 0


def test_sync_fast_path_once_per_version():
    """One kick syncs at most once: the first sync after a mutation
    settles, every repeat at the same graph version takes the version
    fast-path (the FlatMatcher/feasible_roots double-sync fix)."""
    g = build_cluster(nodes=2)
    flat = g.flat()
    req = Jobspec.hpc(nodes=1, sockets=1, cores=4).resources[0]
    flat.sync()
    base = flat.n_sync_fast
    flat.feasible_roots(req)
    flat.feasible_roots_batch([req])
    assert flat.n_sync_fast == base + 2
    g.set_allocated(sorted(g.by_type("core"))[:4], "j")
    flat.feasible_roots(req)        # settles: not a fast sync
    assert flat.n_sync_fast == base + 2
    flat.feasible_roots(req)
    assert flat.n_sync_fast == base + 3
    # a FlatMatcher.match on the settled graph is one fast sync, not two
    FlatMatcher(flat).match(Jobspec.hpc(nodes=1, sockets=1, cores=4))
    assert flat.n_sync_fast == base + 4


# ---------------------------------------------------------------------- #
# property-based churn
# ---------------------------------------------------------------------- #
if HAS_HYPOTHESIS:
    _op = st.one_of(
        st.tuples(st.just("alloc"), st.integers(0, 63)),
        st.tuples(st.just("free"), st.integers(0, 63)),
        st.tuples(st.just("down"), st.integers(0, 3)),
        st.tuples(st.just("up"), st.integers(0, 3)),
        st.tuples(st.just("splice_in"), st.integers(0, 3)),
        st.tuples(st.just("splice_out"), st.integers(0, 3)),
    )

    @settings(max_examples=25, deadline=None)
    @given(st.lists(_op, min_size=1, max_size=40))
    def test_flat_mirror_invariant_under_random_churn(ops):
        """Property: after ANY alloc/release/status/splice sequence the
        flat mirror agrees exactly with the dict graph, the tree stays
        valid, and no hot-path operation fell back to a full
        ``init_aggregates()`` rebuild."""
        g = build_cluster(nodes=2, sockets_per_node=2, cores_per_socket=16)
        flat = g.flat()
        rebuilds = g.n_agg_rebuilds
        cores = sorted(g.by_type("core"))
        nodes = sorted(g.by_type("node")) * 2   # pad to 4 indices
        spliced = {}
        for kind, idx in ops:
            if kind == "alloc":
                g.set_allocated([cores[idx]], f"job{idx}")
            elif kind == "free":
                g.set_free([cores[idx]], f"job{idx}")
            elif kind == "down":
                g.set_status(nodes[idx], DOWN)
            elif kind == "up":
                g.set_status(nodes[idx], UP)
            elif kind == "splice_in":
                if idx in spliced:
                    continue
                ext = build_cluster(nodes=1, sockets_per_node=1,
                                    cores_per_socket=4,
                                    node_prefix=f"burst{idx}-")
                sub = ext.extract(
                    [p for p in ext.paths() if f"burst{idx}-" in p])
                res = add_subgraph(g, sub)
                update_metadata(g, res, jobid=f"bjob{idx}")
                spliced[idx] = res.new_paths
            elif kind == "splice_out":
                paths = spliced.pop(idx, None)
                if paths:
                    remove_subgraph(g, paths, jobid=f"bjob{idx}")
            assert g.validate_tree()
            assert flat.verify_against(g)
        assert g.n_agg_rebuilds == rebuilds, \
            "a hot-path operation fell back to init_aggregates()"

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 63)),
                    min_size=0, max_size=30),
           st.integers(1, 3), st.integers(1, 2), st.integers(2, 8))
    def test_flat_and_dict_match_identical_after_churn(ops, nodes,
                                                       sockets, cores):
        """Property: after any alloc/free churn, the flat matcher and
        the dict oracle return the SAME paths (or both None)."""
        g = build_cluster(nodes=2, sockets_per_node=2, cores_per_socket=16)
        g.flat()
        pool = sorted(g.by_type("core"))
        for alloc, idx in ops:
            if alloc:
                g.set_allocated([pool[idx]], f"j{idx}")
            else:
                g.set_free([pool[idx]], f"j{idx}")
        js = Jobspec.hpc(nodes=nodes, sockets=sockets * nodes,
                         cores=cores * sockets * nodes)
        flat = FlatMatcher(g.flat()).match(js)
        oracle = DictMatcher(g).match(js)
        assert flat == oracle
else:
    def test_property_tests_skipped_without_hypothesis():
        pytest.skip("hypothesis not installed; property tests not defined")
