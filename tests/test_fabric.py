"""Route/tree invariants of the fabric engine (ISSUE 2 satellite):

  - every routed link physically exists in Topology.links()
  - up-down routes are loop-free; agg->core hops obey the attachment rule
    (core c hangs off agg c // (k/2) — the seed's ECMP inconsistency)
  - multicast trees are connected, span root + all members, and are trees
  - the routed ENGINE's per-link bytes equal the old static LinkCounters
    pass for identical schedules (ring and multicast-composition allgather)

Property-driven via hypothesis or the offline seeded shim.
"""
try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # offline: seeded-random shim (tests/_hypothesis_shim.py)
    from _hypothesis_shim import given, settings, strategies as st
import pytest

from repro.core import cost_model as cm
from repro.core.engine import Engine
from repro.core.topology import FatTree, Torus2D, Topology


def _assert_physical(topo, links):
    table = topo.links()
    for link in links:
        assert table.get((link.src, link.dst)) is link, (link.src, link.dst)


def _assert_tree(topo, root_name, member_names, links):
    """Connected, spanning, acyclic: every non-root node has exactly one
    in-edge and is reachable from the root."""
    children = {}
    in_deg = {}
    nodes = {root_name}   # a root-only group is a tree with no links
    for link in links:
        children.setdefault(link.src, []).append(link.dst)
        in_deg[link.dst] = in_deg.get(link.dst, 0) + 1
        nodes.update((link.src, link.dst))
    assert all(d == 1 for d in in_deg.values()), in_deg
    assert root_name not in in_deg
    reached = {root_name}
    stack = [root_name]
    while stack:
        for nxt in children.get(stack.pop(), []):
            if nxt not in reached:
                reached.add(nxt)
                stack.append(nxt)
    assert reached == nodes
    for m in member_names:
        assert m in nodes, m
    assert len(links) == len(nodes) - 1


# ------------------------------------------------------------ fat-tree routes


@given(st.integers(2, 5).map(lambda h: 2 * h),        # k in {4, 6, 8, 10}
       st.integers(0, 10_000), st.integers(0, 10_000))
@settings(max_examples=60, deadline=None)
def test_fat_tree_route_links_exist_and_loop_free(k, a, b):
    tree = FatTree(k=k)
    src, dst = a % tree.n_hosts, b % tree.n_hosts
    route = tree.route(src, dst)
    if src == dst:
        assert route == []
        return
    _assert_physical(tree, route)
    # contiguous path host(src) -> ... -> host(dst)
    assert route[0].src == tree.host(src)
    assert route[-1].dst == tree.host(dst)
    for x, y in zip(route, route[1:]):
        assert x.dst == y.src
    # loop-free: no node visited twice
    visited = [route[0].src] + [l.dst for l in route]
    assert len(visited) == len(set(visited))
    assert len(route) <= 6                      # up-down: at most 6 hops


@given(st.integers(2, 5).map(lambda h: 2 * h), st.integers(0, 10_000),
       st.integers(0, 10_000))
@settings(max_examples=60, deadline=None)
def test_fat_tree_core_attachment_rule(k, a, b):
    """The regression for the seed's ECMP bug: on inter-pod routes the
    agg->core and core->agg hops must obey core // (k/2) == agg index."""
    tree = FatTree(k=k)
    h2 = k // 2
    route = tree.route(a % tree.n_hosts, b % tree.n_hosts)
    for link in route:
        ends = {link.src, link.dst}
        cores = [n for n in ends if n.startswith("c")]
        if cores:
            (core,) = cores
            (agg,) = ends - set(cores)
            c = int(core[1:])
            a_ix = int(agg.split(".")[1])
            assert c // h2 == a_ix, (link.src, link.dst)


@given(st.integers(2, 5).map(lambda h: 2 * h), st.integers(0, 10_000),
       st.lists(st.integers(0, 10_000), min_size=1, max_size=24))
@settings(max_examples=60, deadline=None)
def test_fat_tree_multicast_tree_spans_members(k, root, members):
    tree = FatTree(k=k)
    root = root % tree.n_hosts
    members = sorted({m % tree.n_hosts for m in members} | {root})
    links = tree.multicast_tree(root, members)
    _assert_physical(tree, links)
    _assert_tree(tree, tree.host(root), [tree.host(m) for m in members if m != root],
                 links)


# --------------------------------------------------------------- torus routes


@given(st.integers(2, 6), st.integers(2, 6),
       st.integers(0, 10_000), st.integers(0, 10_000))
@settings(max_examples=60, deadline=None)
def test_torus_route_shortest_and_physical(nx, ny, a, b):
    tz = Torus2D(nx, ny)
    n = nx * ny
    src, dst = a % n, b % n
    route = tz.route(src, dst)
    _assert_physical(tz, route)
    sx, sy = tz.coord(src)
    dx, dy = tz.coord(dst)
    dist = min((dx - sx) % nx, (sx - dx) % nx) + min((dy - sy) % ny, (sy - dy) % ny)
    assert len(route) == dist
    if route:
        assert route[0].src == tz.node(sx, sy)
        assert route[-1].dst == tz.node(dx, dy)
        for x, y in zip(route, route[1:]):
            assert x.dst == y.src


@given(st.integers(2, 6), st.integers(2, 6), st.integers(0, 10_000),
       st.lists(st.integers(0, 10_000), min_size=1, max_size=16))
@settings(max_examples=60, deadline=None)
def test_torus_multicast_tree_spans_members(nx, ny, root, members):
    tz = Torus2D(nx, ny)
    n = nx * ny
    root = root % n
    members = sorted({m % n for m in members} | {root})
    links = tz.multicast_tree(root, members)
    _assert_physical(tz, links)
    _assert_tree(tz, tz.node(*tz.coord(root)),
                 [tz.node(*tz.coord(m)) for m in members if m != root], links)


def test_topologies_satisfy_protocol():
    assert isinstance(FatTree(k=4), Topology)
    assert isinstance(Torus2D(2, 2), Topology)


def test_aggregation_tree_is_reversed_multicast_tree():
    tree = FatTree(k=8, n_hosts=32)
    members = list(range(0, 32, 3))
    down = tree.multicast_tree(3, members)
    up = tree.aggregation_tree(3, members)
    assert {(l.src, l.dst) for l in up} == {(l.dst, l.src) for l in down}
    _assert_physical(tree, up)


def test_nonexistent_link_asserts():
    tree = FatTree(k=4)
    with pytest.raises(AssertionError, match="nonexistent fabric link"):
        tree.link("a0.0", "c3")       # core 3 hangs off agg 1, not agg 0


# ------------------------------- routed engine == static counters equivalence


def _engine_per_link(eng):
    return {name: b for name, b in eng.link_bytes().items() if b}


def test_routed_ring_equals_static_counters():
    """The compressed routed ring schedule (one flow per neighbor route
    carrying (P-1)*shard) must charge exactly the bytes the old static
    per-round unicast pass counts."""
    p, nbytes = 24, 3 << 20
    tree = FatTree(k=8, n_hosts=p)
    _, engine_bytes = cm.routed_ring_allgather(tree, p, nbytes)
    engine_bytes = {k: v for k, v in engine_bytes.items() if v}

    tree.reset()
    shard = nbytes // p
    for _ in range(p - 1):
        for src in range(p):
            tree.unicast(src, (src + 1) % p, shard)
    static = {l.name: l.bytes_served for l in tree.links().values()
              if l.bytes_served}
    assert static.keys() == engine_bytes.keys()
    for name, b in static.items():
        assert engine_bytes[name] == pytest.approx(b, rel=1e-9), name


def test_routed_mcast_allgather_equals_static_counters():
    """P concurrent multicast tree flows through the engine charge the same
    per-link bytes as the static broadcast-composition pass (Insight 1:
    every byte on every tree link exactly once)."""
    p, shard = 16, 1 << 16
    tree = FatTree(k=8, n_hosts=p)
    hosts = list(range(p))

    tree.reset()
    eng = Engine()
    flows = [eng.submit_tree(tree.multicast_tree(h, hosts), shard, tag=f"c{h}")
             for h in hosts]
    eng.run()
    assert all(f.done for f in flows)
    engine_bytes = _engine_per_link(eng)

    tree.reset()
    for root in hosts:
        tree.multicast(root, hosts, shard)
    static = {l.name: l.bytes_served for l in tree.links().values()
              if l.bytes_served}
    assert static.keys() == engine_bytes.keys()
    for name, b in static.items():
        assert engine_bytes[name] == pytest.approx(b, rel=1e-9), name


def test_routed_flow_rate_is_min_share_over_route():
    """A route flow crossing a thin tier runs at the thin link's share even
    while its host links are idle-fast (oversubscription bites)."""
    tree = FatTree(k=4, n_hosts=4, b_host=100.0, oversubscription=4.0)
    eng = Engine()
    r = tree.route(0, 2)                      # crosses edge->agg at cap 25
    assert any(l.capacity == pytest.approx(25.0) for l in r)
    f = eng.submit_route(r, 250.0)
    eng.run()
    assert f.t_end == pytest.approx(10.0)     # 250 bytes at 25 B/s

    # the same path at full bisection runs at host line rate
    flat = FatTree(k=4, n_hosts=4, b_host=100.0)
    eng2 = Engine()
    f2 = eng2.submit_route(flat.route(0, 2), 250.0)
    eng2.run()
    assert f2.t_end == pytest.approx(2.5)


# --------------------------------------------- torus packet fidelity (PR 9)


def test_torus_supports_packet():
    assert Torus2D(4, 4).supports_packet is True
    assert Torus2D(4, 4).host(6) == "t1.2"


def test_torus_zero_loss_packet_reproduces_fluid_broadcast():
    """Loss-0 packet == fluid on Torus2D, same pin the fat-tree fabrics
    carry: leaf paths resolve through topology.host(), so receivers that
    are interior tree nodes (every non-leaf torus member) work too."""
    from repro.core.engine import FabricParams, WorkerParams
    from repro.core.simulator import simulate_broadcast
    import numpy as np

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    tz = Torus2D(4, 4)
    a = simulate_broadcast(16, 1 << 20, fab, wk, np.random.default_rng(0),
                           topology=tz)
    b = simulate_broadcast(16, 1 << 20, fab, wk, np.random.default_rng(0),
                           topology=tz, fidelity="packet")
    assert b.time == pytest.approx(a.time, rel=1e-9)
    assert a.link_bytes == pytest.approx(b.link_bytes)


def test_torus_zero_loss_packet_reproduces_fluid_allgather():
    """Routed allgather at loss 0: the packet engine lands within the same
    per-hop-handshake margin of the fluid time on Torus2D as on FatTree —
    and EXACTLY matches the fat-tree packet time at equal line rate (both
    fabrics are non-blocking for this pattern), so the torus leaf-path
    resolution introduces no deviation of its own."""
    from repro.core import sched_ir
    from repro.core.engine import FabricParams, WorkerParams
    from repro.core.topology import FatTree
    import numpy as np

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    sched = sched_ir.build_allgather(16, 1 << 20, 4)
    res = {}
    for fid in ("fluid", "packet"):
        res[fid] = sched_ir.execute(sched, fab, wk, np.random.default_rng(0),
                                    fidelity=fid, topology=Torus2D(4, 4))
    assert res["packet"].time == pytest.approx(res["fluid"].time, rel=0.05)
    assert res["packet"].recovered == 0 and res["packet"].completed
    ft = sched_ir.execute(sched, fab, wk, np.random.default_rng(0),
                          fidelity="packet", topology=FatTree(k=8, n_hosts=16))
    assert res["packet"].time == pytest.approx(ft.time, rel=1e-12)


def test_torus_lossy_packet_converges_and_is_slower():
    from repro.core import sched_ir
    from repro.core.engine import FabricParams, WorkerParams
    import numpy as np

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    sched = sched_ir.build_allgather(16, 1 << 20, 4)
    tz = Torus2D(4, 4)
    clean = sched_ir.execute(sched, fab, wk, np.random.default_rng(0),
                             fidelity="packet", topology=tz)
    tz2 = Torus2D(4, 4)
    lossy = sched_ir.execute(sched, fab, wk, np.random.default_rng(0),
                             fidelity="packet", topology=tz2, loss=0.01)
    assert lossy.completed and lossy.recovered > 0
    assert lossy.time > clean.time
